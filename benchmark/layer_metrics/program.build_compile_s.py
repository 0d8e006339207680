"""Sweep program: trace + lower + compile (or load from the cache) seconds
of the first warm-up sweep, as the program's ``run_stats`` clock it.
Nothing where the entry point reports none."""


def read(ctx):
    return ctx["warmup"][0]["build_compile_s"]
