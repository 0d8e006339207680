"""Expert layer: percent of the device's busy seconds in ``moe.dispatch``
inside ``lane.moe`` (rows gathered into expert order by ``order``, in either
pass), from the trace joined with the program's maps from instruction to
lane part and to piece."""

import lane_pieces


def read(ctx):
    return lane_pieces.piece_share(ctx, "moe.dispatch")
