"""Lane: an end-to-end utilisation, the share of the whole step's peak, not a
kernel's roofline share: the operations the window's sweeps needed
(``lane_counts_sdar.py``: attention's projections on 2 S rows and the pairs
its rule of sight holds, the router and the even load of the held experts on
2 S rows, the head on the S masked rows; three forward passes a training
step, one a held-out pass, no recomputation) over window seconds, chips and
the chip's peak."""

import lane_counts_sdar


def read(ctx):
    flops = lane_counts_sdar.sweep_flops(ctx["config"], ctx["plans"]) * len(ctx["sweeps"])
    return 100.0 * flops / ctx["window_s"] / ctx["chips"] / ctx["peaks"]["flops_per_s"]
