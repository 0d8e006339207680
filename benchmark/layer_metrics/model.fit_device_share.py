"""Model: percent of the device's busy seconds in the program's phases
``hpb.kde_fit`` and ``hpb.sample``; the scorer (``hpb.kde_score``) is
``scorer.device_share``'s."""

import span_reduce


def read(ctx):
    return span_reduce.phase_share(span_reduce.of(ctx), "hpb.kde_fit", "hpb.sample")
