"""Sweep driver: 90th percentile of the window's per-sweep walls (linear
interpolation): the sweep that hit a slow replay or a stall. A per-layer
metric because its run-to-run spread (3 %, PERF.md section 2) is too wide
for a bound the contract allows."""


def read(ctx):
    walls = sorted(s["wall_s"] for s in ctx["sweeps"])
    pos = 0.9 * (len(walls) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(walls) - 1)
    return walls[lo] + (walls[hi] - walls[lo]) * (pos - lo)
