"""Lane: the looped layers' mixer against its roofline while it runs: the
least seconds the chip could take for the traced sweeps' four projections and
causal half-square (``lane_counts_ouro.py``: ``S (S + 1) / 2`` pairs a head, a
layer's weights read once a visit) over the device's busy seconds in
``lane.gqa``."""

import lane_counts_ouro


def read(ctx):
    return lane_counts_ouro.roofline_share(ctx, "gqa")
