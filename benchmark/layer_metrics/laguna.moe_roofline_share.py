"""Lane: the expert layers' share of their roofline while they run, by this
configuration's counts (``lane_counts_laguna.py``: the router, the shared
expert on every token and the even load of the 32 held experts' three
products, 256 token-choices an expert a pass, against 12 bytes a parameter a
step) over the device's busy seconds in ``lane.moe``."""

import lane_counts_laguna


def read(ctx):
    return lane_counts_laguna.roofline_share(ctx, ("moe",))
