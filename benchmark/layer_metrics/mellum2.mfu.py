"""Lane: an end-to-end utilisation, not a kernel's roofline share: the
operations the window's sweeps needed (``lane_counts_mellum2.py``: three
forward passes a training step, one a validation pass, no recomputation, the
exact band and the causal half-square) over window seconds, chips and the
chip's peak."""

import lane_counts_mellum2


def read(ctx):
    flops = lane_counts_mellum2.sweep_flops(ctx["config"], ctx["plans"]) * len(ctx["sweeps"])
    return 100.0 * flops / ctx["window_s"] / ctx["chips"] / ctx["peaks"]["flops_per_s"]
