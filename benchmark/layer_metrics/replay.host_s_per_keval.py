"""Host replay: per sweep, the wall that is neither build nor
dispatch-to-fetch, per 1,000 evaluations; the median over the window."""

import statistics


def read(ctx):
    return statistics.median(
        (s["wall_s"] - s["build_compile_s"] - s["execute_fetch_s"])
        / s["evaluations"] * 1000.0 for s in ctx["sweeps"])
