"""Lane: the attention mixers of both kinds against their roofline while they
run: the least seconds the chip could take for the traced sweeps' two full
layers at 6 query heads a key/value head (48: the causal half-square, half of
each head turned) and three window layers at 8 (64: the 512-token band's
pairs exactly), their projections and gates (``lane_counts_laguna.py``), over
the device's busy seconds in ``lane.gqa`` and ``lane.swa`` together."""

import lane_counts_laguna


def read(ctx):
    return lane_counts_laguna.roofline_share(ctx, lane_counts_laguna.ATTENTION)
