"""Lane: the expert layers' share of their roofline while they run: the
least seconds the chip could take for the traced sweeps' expert layers
(``lane_counts.py``: router, shared expert and the even load of the held
experts, against 12 bytes a parameter a step; at 128 token-choices an expert
the two bounds lie close) over the device's busy seconds in ``lane.moe``."""

import lane_counts


def read(ctx):
    return lane_counts.roofline_share(ctx, "moe")
