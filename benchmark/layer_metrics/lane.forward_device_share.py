"""Lane: percent of the device's busy seconds in the forward trace that
training and held-out passes share (``pass.forward``), from the trace joined
with the program's map from instruction to pass."""

import lane_pieces


def read(ctx):
    return lane_pieces.pass_share(ctx, "pass.forward")
