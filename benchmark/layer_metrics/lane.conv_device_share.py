"""Lane: percent of the device's busy seconds in the program's scope
``lane.conv`` (a gated short convolution's mixer, norm's output to ``W_out``:
its two products, the two gates, the taps), from the trace joined with the
program's map from instruction to lane part."""

import lane_counts


def read(ctx):
    return lane_counts.device_share(ctx, "conv")
