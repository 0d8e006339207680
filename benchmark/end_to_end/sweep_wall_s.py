"""Median wall of one whole warm sweep, construction to result, over every
sweep the window ran."""

import statistics


def read(ctx):
    return statistics.median(s["wall_s"] for s in ctx["sweeps"])
