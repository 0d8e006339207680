"""Lane: the attention layers' mixers against their roofline while they run:
the least seconds the chip could take for the traced sweeps' four projections
on 2 S rows and the pairs the block-diffusion rule of sight holds
(``lane_counts_sdar.py``: ``S^2 + L S`` pairs a head of 128, a quarter of the
square, whatever blocks they are computed in) over the device's busy seconds
in ``lane.bda``."""

import lane_counts_sdar


def read(ctx):
    return lane_counts_sdar.roofline_share(ctx, "bda")
