"""Lane: KDA's share of its roofline while it runs: the least seconds the
chip could take for the traced sweeps' KDA layers (``lane_counts.py``:
projections and the recurrence's 7 d_k d_v a token and head, against the
float32 parameters and rows moved; compute bounds it) over the device's
busy seconds in ``lane.kda``."""

import lane_counts


def read(ctx):
    return lane_counts.roofline_share(ctx, "kda")
