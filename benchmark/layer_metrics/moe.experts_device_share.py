"""Expert layer: percent of the device's busy seconds in ``moe.experts``
inside ``lane.moe`` (the grouped products, the experts' gradient sums, the
tiles' loops), from the trace joined with the program's maps from
instruction to lane part and to piece."""

import lane_pieces


def read(ctx):
    return lane_pieces.piece_share(ctx, "moe.experts")
