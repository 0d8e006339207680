"""From a traced run's ``.xplane.pb`` to what the program's own names say.

``trace_reduce.py`` reads the harness's two spans and the compiler's
operation names. The program (PR 25) also writes a span for every phase of a
sweep, ``hpb:<name>`` on the host plane, and offers a map from a compiled
program's instruction names to the phases its ``jax.named_scope`` names
give (``program_phases.py``). This file reduces both: seconds and self
seconds per span, the device's idle seconds under no ``hpb:`` span, and the
device's busy (self) seconds per phase. A program without the spans or
without the map (the parent commit) gives empty tables, and the metrics
that read them give nothing.

    python benchmark/span_reduce.py <trace dir or file.xplane.pb>
"""

import bisect
import json
import os
import sys
from collections import defaultdict

import trace_reduce
from reference import halving

HERE = os.path.dirname(os.path.abspath(__file__))
#: where ``run.traced_sweeps`` leaves its profile
TRACE_DIR = os.path.join(os.path.dirname(HERE), ".bench_out", "trace")
PROGRAM_PREFIX = "hpb:"
MODULE_LINE = "XLA Modules"
SWEEP_SPAN = trace_reduce.SPAN_PREFIX + "run"
UNNAMED = "unnamed"
TOP = 10


def of(ctx):
    """The reduction of this traced run, made once and kept in ``ctx``;
    ``None`` where the run was not traced or left no trace file."""
    if "spans" not in ctx:
        ctx["spans"] = None
        if ctx.get("trace") is not None and os.path.isdir(TRACE_DIR):
            import program_phases

            ctx["spans"] = reduce_file(
                trace_reduce.newest_xplane(TRACE_DIR), ctx["chips"],
                program_phases.phase_maps())
            print("spans %s" % json.dumps(summary(ctx["spans"])))
    return ctx["spans"]


def reduce_file(path, chips, phase_maps=None):
    from jax.profiler import ProfileData

    spans, ops, modules = [], {}, {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(trace_reduce.DEVICE_PLANE)
        for line in plane.lines:
            events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events]
            if device and line.name == trace_reduce.OP_LINE:
                ops[plane.name] = [(a, b, trace_reduce.op_name(n)) for a, b, n in events]
            elif device and line.name == MODULE_LINE:
                modules[plane.name] = [(a, b, module_name(n)) for a, b, n in events]
            elif not device:
                # one list per host thread: spans nest within a thread
                spans.append([e for e in events if e[2].startswith(
                    (PROGRAM_PREFIX, trace_reduce.SPAN_PREFIX))])
    return reduce_spans([s for s in spans if s], ops, modules, chips, phase_maps)


def module_name(text):
    """``jit_hpb_sweep(12505552555171170140)`` -> ``jit_hpb_sweep``."""
    return text.split("(", 1)[0]


def reduce_spans(threads, ops, modules, chips, phase_maps=None):
    """``threads``: per host thread ``[(start_ns, end_ns, name)]`` of the
    harness's (``bench:``) and the program's (``hpb:``) spans; ``ops`` and
    ``modules``: ``{device plane: [(start_ns, end_ns, name)]}`` of the
    operations and of the programs that enclose them; ``phase_maps``:
    ``{module name: {instruction name: phase}}`` or ``None``."""
    flat = [s for thread in threads for s in thread]
    harness = [s for s in flat if s[2].startswith(trace_reduce.SPAN_PREFIX)]
    if not harness:
        raise ValueError("the trace holds no %s* span" % trace_reduce.SPAN_PREFIX)
    if len(ops) < chips or not all(ops.values()):
        raise ValueError("device operations on %d plane(s), the cell has %d chips"
                         % (sum(bool(v) for v in ops.values()), chips))
    w0, w1 = min(s[0] for s in harness), max(s[1] for s in harness)
    span_s, self_s = defaultdict(float), defaultdict(float)
    for thread in threads:
        for a, b, name in thread:
            span_s[name] += (b - a) / 1e9
        for name, s in trace_reduce.self_times(thread).items():
            self_s[name] += s
    named = trace_reduce.union(
        (a, b) for a, b, n in flat if n.startswith(PROGRAM_PREFIX))
    idle_ns = idle_unnamed_ns = 0.0
    phase_s, op_s = defaultdict(float), defaultdict(lambda: defaultdict(float))
    for plane, events in ops.items():
        inside = [(max(a, w0), min(b, w1), n) for a, b, n in events
                  if b > w0 and a < w1]
        busy = trace_reduce.union((a, b) for a, b, _ in inside)
        edges = [w0] + [t for ab in busy for t in ab] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            idle_ns += g1 - g0
            idle_unnamed_ns += (g1 - g0) - covered(g0, g1, named)
        phases = instruction_phases(inside, modules.get(plane, ()), phase_maps)
        for name, s in trace_reduce.self_times(inside).items():
            phase = phases.get(name, UNNAMED)
            phase_s[phase] += s / len(ops)
            op_s[phase][name] += s / len(ops)
    return {
        "window_s": (w1 - w0) / 1e9,
        "sweeps": sum(s[2] == SWEEP_SPAN for s in harness),
        "span_s": dict(span_s),
        "self_s": dict(self_s),
        "idle_s": idle_ns / 1e9 / len(ops),
        "idle_unnamed_s": idle_unnamed_ns / 1e9 / len(ops),
        "busy_s": sum(phase_s.values()),
        # what the trace held: a lossy one shows here
        "events": {"ops": sum(len(v) for v in ops.values()),
                   "modules": sum(len(v) for v in modules.values())},
        # None: the program offered no map, so no phase can be told
        "phase_s": dict(phase_s) if phase_maps else None,
        # {phase: {instruction name: self seconds}}: what to look at
        "phase_op_s": {p: dict(o) for p, o in op_s.items()} if phase_maps else None,
    }


def covered(g0, g1, merged):
    """Nanoseconds of ``[g0, g1)`` that the merged intervals cover."""
    return sum(max(0, min(b, g1) - max(a, g0)) for a, b in merged)


def instruction_phases(events, modules, phase_maps):
    """``{instruction name: phase}`` for the operations of one plane, each
    joined through the program (``XLA Modules`` event) that encloses it:
    two programs may both have a ``fusion.12``. An operation that no such
    event encloses (a trace can lose them) is joined by its name alone, if
    every program that has the name gives it one phase. An instruction
    that comes out with different phases keeps none."""
    if not phase_maps:
        return {}
    by_name = {}
    for phases in phase_maps.values():
        for name, phase in phases.items():
            if by_name.setdefault(name, phase) != phase:
                by_name[name] = None
    found, clashed = {}, set()
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    for a, _, name in events:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and a < modules[i][1]:
            phase = phase_maps.get(modules[i][2], {}).get(name)
        else:
            phase = by_name.get(name)
        if found.setdefault(name, phase) != phase:
            clashed.add(name)
    return {n: p for n, p in found.items() if p is not None and n not in clashed}


def total(spans, *names):
    """Seconds under the program's spans ``names``; ``None`` if the trace
    holds none of them."""
    if spans is None:
        return None
    have = [spans["span_s"][PROGRAM_PREFIX + n] for n in names
            if PROGRAM_PREFIX + n in spans["span_s"]]
    return sum(have) if have else None


def per_sweep(ctx, *names):
    """Mean seconds a traced sweep spends under the spans ``names``."""
    spans = of(ctx)
    seconds = total(spans, *names)
    return None if seconds is None else seconds / spans["sweeps"]


def per_keval(ctx, *names):
    """Seconds under the spans ``names`` per 1,000 evaluations of the
    schedule, over the traced sweeps."""
    seconds = per_sweep(ctx, *names)
    if seconds is None:
        return None
    return seconds / halving.schedule_evaluations(ctx["plans"]) * 1000.0


def phase_share(spans, *phases):
    """Percent of the device's busy seconds in ``phases``; ``None`` where
    the program offered no phase map."""
    if spans is None or spans["phase_s"] is None or not spans["busy_s"]:
        return None
    return 100.0 * sum(spans["phase_s"].get(p, 0.0) for p in phases) / spans["busy_s"]


def summary(spans):
    """The reduction with each phase's operations cut to its largest: the
    unnamed ones to ten, so that what stayed unnamed can be listed."""
    def top(ops, n):
        return [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:n]]

    return dict(spans, phase_op_s={
        phase: top(ops, TOP if phase == UNNAMED else 3)
        for phase, ops in (spans["phase_op_s"] or {}).items()})


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        target = trace_reduce.newest_xplane(target)
    print(json.dumps(summary(reduce_file(target, 1)), indent=1))
