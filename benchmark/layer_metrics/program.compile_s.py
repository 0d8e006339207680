"""Sweep program: seconds in the compiler, or in the compile cache's load,
over the sweep programs this process built, as the program's gauge
``sweep.build.compile_s`` sums them; with ``program.trace_lower_s`` it adds
up to ``program.build_compile_s``."""

import program_lane_pieces


def read(ctx):
    gauges = program_lane_pieces.build_gauges()
    return gauges and gauges.get("compile_s")
