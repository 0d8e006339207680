"""Host replay: seconds in ``hpb:replay.configs`` (``from_vector`` and
``add_configuration`` for every configuration of a bracket) per 1,000
evaluations of the schedule, over the traced sweeps."""

import span_reduce


def read(ctx):
    return span_reduce.per_keval(ctx, "replay.configs")
