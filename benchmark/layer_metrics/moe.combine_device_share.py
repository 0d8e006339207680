"""Expert layer: percent of the device's busy seconds in ``moe.combine``
inside ``lane.moe`` (rows gathered back by ``place`` and summed over the top
k, both ways; the weights' gradient), from the trace joined with the
program's maps from instruction to lane part and to piece."""

import lane_pieces


def read(ctx):
    return lane_pieces.piece_share(ctx, "moe.combine")
