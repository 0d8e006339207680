"""Lane: percent of the device's busy seconds in the program's scope
``lane.swa`` (a window layer's mixer, norm's output to ``W_o``, rotary included),
from the trace joined with the program's map from instruction to lane part."""

import lane_counts


def read(ctx):
    return lane_counts.device_share(ctx, "swa")
