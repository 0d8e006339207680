"""Lane: percent of the device's busy seconds in what a training pass
computes again for its gradient (``pass.recompute``: a visit's inside under
the trainer's ``jax.vjp``, a tile of the expert layer inside its backward
rule, a block of scores under ``jax.checkpoint``), from the trace joined
with the program's map from instruction to pass."""

import lane_pieces


def read(ctx):
    return lane_pieces.pass_share(ctx, "pass.recompute")
