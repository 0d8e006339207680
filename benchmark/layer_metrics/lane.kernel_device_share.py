"""Lane: percent of the device's busy seconds in kernels (a custom call into
a Mosaic kernel, what the compiler makes of ``ragged_dot``), in any part of
the lane: the program's kind ``kernel`` (``lane_kinds.py``)."""

import lane_kinds


def read(ctx):
    return lane_kinds.kernel_share(ctx)
