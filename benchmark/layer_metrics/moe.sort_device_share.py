"""Expert layer: percent of the device's busy seconds in ``moe.sort`` inside
``lane.moe`` (the choices' slots, the counting sort and its two
permutations), from the trace joined with the program's maps from
instruction to lane part and to piece."""

import lane_pieces


def read(ctx):
    return lane_pieces.piece_share(ctx, "moe.sort")
