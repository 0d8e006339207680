"""Lane: an end-to-end utilisation, the share of the whole step's peak, not a
kernel's roofline share: the operations the window's sweeps needed
(``lane_counts_olmo_hybrid.py``: the linear mixers' products and recurrence,
attention's projections and causal half-square, the dense SwiGLU, the head;
three forward passes a training step, one a held-out pass, no recomputation)
over window seconds, chips and the chip's peak."""

import lane_counts_olmo_hybrid


def read(ctx):
    flops = lane_counts_olmo_hybrid.sweep_flops(ctx["config"], ctx["plans"]) * len(ctx["sweeps"])
    return 100.0 * flops / ctx["window_s"] / ctx["chips"] / ctx["peaks"]["flops_per_s"]
