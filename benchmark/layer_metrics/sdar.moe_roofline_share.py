"""Lane: the expert layers' share of their roofline while they run, by this
configuration's counts (``lane_counts_sdar.py``: the router and the even load
of the held experts' three products on 2 S rows, 512 token-choices an expert
a pass, against 12 bytes a parameter a step) over the device's busy seconds
in ``lane.moe``."""

import lane_counts_sdar


def read(ctx):
    return lane_counts_sdar.roofline_share(ctx, "moe")
