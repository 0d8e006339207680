"""Lane: percent of the device's busy seconds in the program's scope
``lane.bda`` (an attention layer's mixer under the block-diffusion rule of
sight, norm's output to ``W_o`` over the clean and the masked copy, per-head
norms and rotary included), from the trace joined with the program's map from
instruction to lane part."""

import lane_counts


def read(ctx):
    return lane_counts.device_share(ctx, "bda")
