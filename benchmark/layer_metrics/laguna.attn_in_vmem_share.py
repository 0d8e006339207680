"""Lane: percent of the lane's attention layers whose scores never leave VMEM
(the fused kernels take them), each layer answering at its own head count: the
static fact the program publishes as the gauge
``sweep.lane.attn_scores_in_vmem`` (``lane.attention_counters``); 100 where
the full layers' groups of 6 query heads and the window layers' groups of 8
are both in the kernels."""

import program_lane_parts


def read(ctx):
    gauges = program_lane_parts.lane_gauges()
    if not gauges or "attn_scores_in_vmem" not in gauges:
        return None
    return 100.0 * gauges["attn_scores_in_vmem"]
