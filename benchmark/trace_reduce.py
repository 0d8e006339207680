"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

One pass over the device planes' operation line gives the busy union and
the time per operation name; the harness's own ``TraceAnnotation`` spans
(names starting ``bench:``) on the host plane give the traced window and a
name for every idle gap: the span that covers it, or ``between-spans``.
Inside a span that held device work the gap is told apart as before the
span's first operation, between operations, or after its last.

    python benchmark/trace_reduce.py <file.xplane.pb>    # look at a trace
"""

import glob
import os
import sys
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
TOP = 10


def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return max(files, key=os.path.getmtime)


def reduce_dir(trace_dir, chips):
    return reduce_file(newest_xplane(trace_dir), chips)


def reduce_file(path, chips):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, ops = [], {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                        for e in line.events]
        else:
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return reduce_events(spans, ops, chips)


def op_name(text):
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: the line
    prints an operation as its whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(events):
    """Seconds per operation name, a parent's time less its children's:
    the line nests a loop's body inside the loop."""
    per_name = defaultdict(float)
    open_ops = []  # innermost last: [end, name, own ns so far]

    def close():
        _, name, own = open_ops.pop()
        per_name[name] += own

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while open_ops and open_ops[-1][0] <= start:
            close()
        if open_ops:
            open_ops[-1][2] -= min(end, open_ops[-1][0]) - start
        open_ops.append([end, name, end - start])
    while open_ops:
        close()
    return {name: ns / 1e9 for name, ns in per_name.items()}


def reduce_events(spans, ops, chips):
    """``spans``: ``[(start_ns, end_ns, name)]`` of the harness;
    ``ops``: ``{device plane: [(start_ns, end_ns, name)]}``."""
    if not spans:
        raise ValueError("the trace holds no %s* span" % SPAN_PREFIX)
    if len(ops) < chips or not all(ops.values()):
        raise ValueError("device operations on %d plane(s), the cell has %d chips"
                         % (sum(bool(v) for v in ops.values()), chips))
    w0, w1 = min(s[0] for s in spans), max(s[1] for s in spans)
    busy_ns, op_s, gap_s = 0.0, defaultdict(float), defaultdict(float)
    for events in ops.values():
        inside = [(max(a, w0), min(b, w1), n) for a, b, n in events
                  if b > w0 and a < w1]
        merged = union((a, b) for a, b, _ in inside)
        busy_ns += sum(b - a for a, b in merged)
        for name, s in self_times(inside).items():
            op_s[name] += s / len(ops)
        edges = [w0] + [t for ab in merged for t in ab] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            for name, ns in name_gap(g0, g1, spans, merged):
                gap_s[name] += ns / 1e9 / len(ops)
    busy_s, window_s = busy_ns / 1e9 / len(ops), (w1 - w0) / 1e9
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "op_s": dict(op_s),
        "gap_s": dict(gap_s),
        "spans": len(spans),
        "breakdown": {"device_ops": top(op_s), "idle_gaps": top(gap_s)},
    }


def name_gap(g0, g1, spans, merged):
    """Cut the idle gap ``[g0, g1)`` at span boundaries and name each piece."""
    if g1 <= g0:
        return
    covered = g0
    for s0, s1, name in sorted(spans):
        a, b = max(s0, covered), min(s1, g1)
        if b <= a:
            continue
        if a > covered:
            yield "between-spans", a - covered
        works = [m for m in merged if m[1] > s0 and m[0] < s1]
        if not works:
            yield name, b - a
        elif b <= works[0][0]:
            yield name + ":before-first-op", b - a
        elif a >= works[-1][1]:
            yield name + ":after-last-op", b - a
        else:
            yield name + ":between-ops", b - a
        covered = b
    if g1 > covered:
        yield "between-spans", g1 - covered


def describe(path):
    """Planes, lines and their longest events: what to look at by hand."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  line %r: %d events" % (line.name, len(events)))
            for e in sorted(events, key=lambda e: -e.duration_ns)[:5]:
                print("    %-60s start %d dur %d" % (e.name[:60], e.start_ns, e.duration_ns))


if __name__ == "__main__":
    describe(sys.argv[1])
