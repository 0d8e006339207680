"""Lane: the Gated-DeltaNet mixers' share of their roofline while they run:
the least seconds the chip could take for the traced sweeps' linear layers
(``lane_counts_olmo_hybrid.py``: the seven projections and the recurrence's 7
d_k d_v a token and head, against the float32 parameters and rows moved;
compute bounds it) over the device's busy seconds in ``lane.gdn``."""

import lane_counts_olmo_hybrid


def read(ctx):
    return lane_counts_olmo_hybrid.roofline_share(ctx, "gdn")
