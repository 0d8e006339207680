"""Device: percent of the device's busy seconds in operations that the
program's map gives no phase. ``trainer.roofline_share`` divides by named
seconds only, so it is read beside this."""

import span_reduce


def read(ctx):
    return span_reduce.phase_share(span_reduce.of(ctx), span_reduce.UNNAMED)
