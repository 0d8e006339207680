"""Expert layer: percent of the device's busy seconds in ``moe.router``
inside ``lane.moe`` (the router's logits, scores and top k, the chosen
scores and weights), from the trace joined with the program's maps from
instruction to lane part and to piece."""

import lane_pieces


def read(ctx):
    return lane_pieces.piece_share(ctx, "moe.router")
