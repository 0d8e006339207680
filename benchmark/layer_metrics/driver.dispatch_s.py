"""Sweep driver: seconds a traced sweep spends in ``hpb:dispatch``, the
call of the compiled program to its return: arguments up and the program
enqueued. The mean over the traced sweeps."""

import span_reduce


def read(ctx):
    return span_reduce.per_sweep(ctx, "dispatch")
