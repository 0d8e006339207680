"""Lane: percent of the device's busy seconds in the program's scope
``lane.accumulate`` (adding a visit's gradient into the sum of a leaf that several visits share),
from the trace joined with the program's map from instruction to lane part."""

import lane_counts


def read(ctx):
    return lane_counts.device_share(ctx, "accumulate")
