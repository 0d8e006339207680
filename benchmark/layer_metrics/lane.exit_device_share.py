"""Lane: percent of the device's busy seconds in the program's scope
``lane.exit`` (a looped model's exits: gate, exit distribution, the losses' weighted sum, entropy),
from the trace joined with the program's map from instruction to lane part."""

import lane_counts


def read(ctx):
    return lane_counts.device_share(ctx, "exit")
