"""Trainer: an end-to-end utilisation, not a kernel's roofline share.

Lane-SGD-steps the window's sweeps trained (the schedule's exact count: a
promoted lane trains only the steps it has not had) times the matmul FLOPs
of one momentum-SGD step of one lane, over window seconds, chips and the
chip's peak. A step is charged three forward passes (forward, input
gradient, weight gradient) of 2 FLOPs a multiply-add; the validation pass
after each rung, elementwise work and the update are not counted.
"""

from reference import halving


def mlp_step_flops(mlp):
    batch = min(mlp["batch_size"], mlp["n_train"])
    d, w, c = mlp["d_in"], mlp["width"], mlp["n_classes"]
    return 3.0 * 2.0 * batch * (d * w + w * w + w * c)


def read(ctx):
    steps = halving.schedule_lane_steps(ctx["plans"]) * len(ctx["sweeps"])
    flops = steps * mlp_step_flops(ctx["config"]["mlp"])
    return 100.0 * flops / ctx["window_s"] / ctx["chips"] / ctx["peaks"]["flops_per_s"]
