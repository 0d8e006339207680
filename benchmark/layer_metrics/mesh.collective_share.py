"""Mesh: device time of collective operations over device busy time, from
the trace, averaged over the chips. Exposed or hidden is not told apart."""

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "collective-broadcast")


def read(ctx):
    trace = ctx["trace"]
    collective_s = sum(s for name, s in trace["op_s"].items()
                       if name.startswith(COLLECTIVES))
    return 100.0 * collective_s / trace["busy_s"]
