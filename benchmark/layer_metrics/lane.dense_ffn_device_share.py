"""Lane: percent of the device's busy seconds in the program's scope
``lane.dense_ffn`` (a dense feed-forward layer: its norms, the SwiGLU's three products),
from the trace joined with the program's map from instruction to lane part."""

import lane_counts


def read(ctx):
    return lane_counts.device_share(ctx, "dense_ffn")
