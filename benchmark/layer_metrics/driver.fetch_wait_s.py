"""Sweep driver: seconds a traced sweep spends in ``hpb:fetch``, the host
blocked on the device until the program's outputs are in its hands. The
mean over the traced sweeps."""

import span_reduce


def read(ctx):
    return span_reduce.per_sweep(ctx, "fetch")
