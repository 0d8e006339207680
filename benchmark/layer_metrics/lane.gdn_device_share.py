"""Lane: percent of the device's busy seconds in the program's scope
``lane.gdn`` (a Gated-DeltaNet layer's mixer, the layer's input to ``W_o``:
projections, taps, gates, the chunked delta rule and its backward rule), from
the trace joined with the program's map from instruction to lane part.
Nothing where the program has no such scope."""

import lane_counts


def read(ctx):
    return lane_counts.device_share(ctx, "gdn")
