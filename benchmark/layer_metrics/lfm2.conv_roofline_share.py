"""Lane: the gated short convolutions' mixers against their roofline while
they run: the least seconds the chip could take for the traced sweeps' two
products a layer (``D -> 3D``, ``D -> D``) and the bytes of ``u``, ``z``,
``c``, ``y`` written and read once in float32 beside weights and rows
(``lane_counts_lfm2.py``), over the device's busy seconds in ``lane.conv``."""

import lane_counts_lfm2


def read(ctx):
    return lane_counts_lfm2.roofline_share(ctx, "conv")
