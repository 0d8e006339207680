"""Sweep driver: median per sweep of the program's own dispatch-to-fetch
clock (``run_stats``' ``execute_fetch_s``, summed over the sweep's chunks)."""

import statistics


def read(ctx):
    return statistics.median(s["execute_fetch_s"] for s in ctx["sweeps"])
