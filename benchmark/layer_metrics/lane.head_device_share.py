"""Lane: percent of the device's busy seconds in the program's scope
``lane.head`` (embedding, final norm, head, the exits' cross-entropies),
from the trace joined with the program's map from instruction to lane part."""

import lane_counts


def read(ctx):
    return lane_counts.device_share(ctx, "head")
