"""Lane: percent of the device's busy seconds in no part of the lane (no
scope of ``obs.timeline.LANE_SCOPES``): the draw of the initial weights, the
sweep's own phases, and what the compiler makes itself and leaves without a
name. The parts' shares and rooflines are read beside it: busy time that
lands here is charged to no part."""

import lane_counts
import span_reduce


def read(ctx):
    return span_reduce.phase_share(lane_counts.lane_spans(ctx), span_reduce.UNNAMED)
