"""Sweep program: seconds of Python tracing and lowering over the sweep
programs this process built, as the program's gauge
``sweep.build.trace_lower_s`` sums them (the first warm-up sweep's, the
window having compiled nothing); with ``program.compile_s`` it adds up to
``program.build_compile_s``."""

import program_lane_pieces


def read(ctx):
    gauges = program_lane_pieces.build_gauges()
    return gauges and gauges.get("trace_lower_s")
