"""Sweep driver: seconds a traced sweep spends constructing its optimizer
(the program's ``hpb:construct`` span, ``eval_shape`` of the evaluation
included), the mean over the traced sweeps."""

import span_reduce


def read(ctx):
    return span_reduce.per_sweep(ctx, "construct")
