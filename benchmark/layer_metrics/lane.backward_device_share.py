"""Lane: percent of the device's busy seconds in the pull-backs
(``pass.backward``: the exits', each visit's, the embedding's; a kernel's
own recomputation of its scores with them), from the trace joined with the
program's map from instruction to pass."""

import lane_pieces


def read(ctx):
    return lane_pieces.pass_share(ctx, "pass.backward")
