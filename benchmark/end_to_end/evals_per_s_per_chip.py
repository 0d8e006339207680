"""Evaluations the window completed, over the window's own clock and the
cell's chips: what a chip-hour buys."""


def read(ctx):
    return sum(s["evaluations"] for s in ctx["sweeps"]) / ctx["window_s"] / ctx["chips"]
