"""Lane: the looped layers' feed-forward against its roofline while it runs:
the least seconds the chip could take for the traced sweeps' three SwiGLU
products a visit (``lane_counts_ouro.py``: a layer's weights read once a
visit, ``total_ut_steps`` visits a pass) over the device's busy seconds in
``lane.dense_ffn``."""

import lane_counts_ouro


def read(ctx):
    return lane_counts_ouro.roofline_share(ctx, "dense_ffn")
