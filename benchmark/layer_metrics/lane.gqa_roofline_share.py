"""Lane: the full-attention layer's mixer against its roofline while it
runs: the least seconds the chip could take for the traced sweeps'
(``lane_counts_mellum2.py``: four projections and the causal half-square, ``S
(S + 1) / 2`` pairs a head) over the device's busy seconds in ``lane.gqa``."""

import lane_counts_mellum2


def read(ctx):
    return lane_counts_mellum2.roofline_share(ctx, "gqa")
