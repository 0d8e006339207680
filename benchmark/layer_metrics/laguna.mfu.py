"""Lane: an end-to-end utilisation, the share of the whole step's peak, not a
kernel's roofline share: the operations the window's sweeps needed
(``lane_counts_laguna.py``: each layer's projections and gate at its own head
count, the band's and the causal half-square's pairs exactly, the rotated
half, the router, the shared expert and the even load of the held experts,
the dense SwiGLU, the head over the slice; three forward passes a training
step, one a held-out pass, no recomputation) over window seconds, chips and
the chip's peak."""

import lane_counts_laguna


def read(ctx):
    flops = lane_counts_laguna.sweep_flops(ctx["config"], ctx["plans"]) * len(ctx["sweeps"])
    return 100.0 * flops / ctx["window_s"] / ctx["chips"] / ctx["peaks"]["flops_per_s"]
