"""Expert layer: percent of token-choices that fell on experts this chip
holds, the mean over the last sweep's evaluations (its validation passes),
counted on the device and published by the program as the gauge
``sweep.lane.moe_held_choice_share``; 8 / 256 = 3.125 if routing is even."""

import program_lane_parts


def read(ctx):
    gauges = program_lane_parts.lane_gauges()
    if not gauges or "moe_held_choice_share" not in gauges:
        return None
    return 100.0 * gauges["moe_held_choice_share"]
