"""Lane: an end-to-end utilisation, the share of the whole step's peak, not a
kernel's roofline share: the operations the window's sweeps needed
(``lane_counts_ouro.py``: every layer visit and every trained exit, three
forward passes a training step, one a held-out pass, no recomputation, the
causal half-square) over window seconds, chips and the chip's peak."""

import lane_counts_ouro


def read(ctx):
    flops = lane_counts_ouro.sweep_flops(ctx["config"], ctx["plans"]) * len(ctx["sweeps"])
    return 100.0 * flops / ctx["window_s"] / ctx["chips"] / ctx["peaks"]["flops_per_s"]
