"""Device: of the traced window's idle seconds, the percent that lie under
no ``hpb:`` span of the program: the idle time no phase answers for.
Nothing from a program that writes no such span."""

import span_reduce


def read(ctx):
    spans = span_reduce.of(ctx)
    if span_reduce.total(spans, "run") is None or not spans["idle_s"]:
        return None
    return 100.0 * spans["idle_unnamed_s"] / spans["idle_s"]
