"""Lane: the attention layer's mixer against its roofline while it runs: the
least seconds the chip could take for the traced sweeps' four projections and
causal half-square (``lane_counts_lfm2.py``: ``S (S + 1) / 2`` pairs a head
of 64) over the device's busy seconds in ``lane.gqa``."""

import lane_counts_lfm2


def read(ctx):
    return lane_counts_lfm2.roofline_share(ctx, "gqa")
