"""Trainer: the SGD step's share of the chip's peak while it runs.

Lane-SGD-steps of the traced sweeps (the schedule's exact count) times the
matmul FLOPs of one step of one lane, as ``trainer.mfu`` counts them, over
the device's busy seconds in the phase ``hpb.train``, chips and the chip's
peak FLOP/s. The bound is compute: a step's matmuls at batch 64. Training
work that the phase map leaves unnamed would inflate it, so it is read
beside ``device.unnamed_share``.
"""

import importlib.util
import os

import span_reduce
from reference import halving


def step_flops(mlp):
    """``trainer.mfu.py``'s count, from that file: its name is no module
    name."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "trainer.mfu.py")
    spec = importlib.util.spec_from_file_location("bench_trainer_mfu", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.mlp_step_flops(mlp)


def read(ctx):
    spans = span_reduce.of(ctx)
    if spans is None or spans["phase_s"] is None or "mlp" not in ctx["config"]:
        return None
    train_s = spans["phase_s"].get("hpb.train", 0.0)
    if not train_s:
        return None
    steps = halving.schedule_lane_steps(ctx["plans"]) * spans["sweeps"]
    flops = steps * step_flops(ctx["config"]["mlp"])
    return 100.0 * flops / train_s / ctx["chips"] / ctx["peaks"]["flops_per_s"]
