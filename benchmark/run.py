#!/usr/bin/env python3
"""One run of one benchmark cell:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): compile cache on, the cell's evaluation
object and optimizer built from the seed, two warm-up sweeps. Window: one
client runs whole sweeps back to back until ``--seconds`` have passed; every
end-to-end metric is read off that one list of per-sweep walls and that one
window clock. Check: the window's sweeps against the plain reference. The
last line of standard output is the result. No TPU, no result.

The cell, its configuration, its traffic and its per-layer metrics are
found by the names in ``BENCHMARK.json``; nothing here names one.
"""

import time

T_START = time.perf_counter()

import argparse
import gc
import importlib.util
import itertools
import json
import os
import shutil
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WARMUP_SWEEPS = 2
TRACED_SWEEPS = 4
#: where a traced run leaves its profile; inside the checkout, gitignored
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")


def load_module(*parts):
    """A benchmark file found by name (names may hold ``-`` and ``.``)."""
    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(parts).replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload, root=ROOT):
    """``(cell, config, traffic, end-to-end, per-layer)`` of one cell of
    ``<root>/BENCHMARK.json``; data files are found under ``root``, code by
    the same names beside this file."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        sys.exit("run.py: no cell %r in BENCHMARK.json; it has %s"
                 % (workload, sorted(cells)))
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(root, entry["file"])
    traffic = load_json(root, bench["paths"][0], "traffic", cell["traffic"] + ".json")
    mine = lambda m: workload in m.get("workloads", [workload])
    return (cell, config, traffic,
            [m for m in bench["end_to_end"] if mine(m)],
            [m for m in bench["per_layer"] if mine(m)])


def sweep_seed(seed, i):
    """Sweep ``i`` of run ``seed``; folded into what a 32-bit seed holds."""
    return (seed * 1000 + i) % (2 ** 31 - 1)


def cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


class CompileCounter:
    """Counts programs lowered or compiled, from JAX's own monitoring
    events: it sees a compilation whichever entry point caused it."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **kwargs):
        self.count += name in self.EVENTS


def run_sweeps(sweep, seed, indices=None, seconds=None):
    """Whole sweeps back to back: over ``indices``, or from index 0 until
    ``seconds`` have passed (a sweep that has started is finished).
    Returns ``(raws, window seconds, sweeps that raised)``."""
    raws, raised = [], 0
    t0 = time.perf_counter()
    for index in itertools.count() if indices is None else indices:
        if seconds is not None and time.perf_counter() - t0 >= seconds:
            break
        t_sweep = time.perf_counter()
        try:
            raw = sweep(sweep_seed(seed, index))
        except Exception:
            traceback.print_exc()
            raised += 1
            continue
        raw["wall_s"] = time.perf_counter() - t_sweep
        raws.append(raw)
        # the harness keeps every result for the check; frozen, what it
        # keeps is not walked again by later sweeps' collections
        gc.freeze()
    return raws, time.perf_counter() - t0, raised


def traced_sweeps(sweep, seed, first_index, devices):
    """A few more sweeps under the profiler, reduced to busy time, time per
    operation and idle gaps by the harness's spans."""
    import jax

    import trace_reduce

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the spans wanted are the harness's own
    options.enable_hlo_proto = False  # the program's text is not read, and is large
    with jax.profiler.trace(TRACE_DIR, profiler_options=options):
        run_sweeps(sweep, seed,
                   indices=range(first_index, first_index + TRACED_SWEEPS))
    return trace_reduce.reduce_dir(TRACE_DIR, len(devices))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    cell, config, traffic, end_to_end, per_layer = load_cell(args.workload)

    sys.path.insert(0, ROOT)
    import jax

    if jax.default_backend() != "tpu" or len(jax.devices()) < cell["chips"]:
        sys.exit("run.py: cell %s needs %d TPU chip(s); jax found backend %r "
                 "with %d device(s)" % (cell["name"], cell["chips"],
                                        jax.default_backend(), len(jax.devices())))
    devices = jax.devices()[:cell["chips"]]
    result = measure(args, cell, config, traffic, end_to_end, per_layer, devices)
    print(json.dumps(result), flush=True)


def device_peaks(kind):
    """The one peak table; a device that is not in it is an error."""
    peaks = load_json(HERE, "peaks.json")["by_device_kind"]
    if kind not in peaks:
        raise KeyError("device_kind %r has no row in benchmark/peaks.json" % kind)
    return peaks[kind]


def memory_peak_bytes(devices):
    """The fullest chip's peak: buffers in use plus what the runtime
    reserved for the programs' own temporaries, which it counts apart."""
    stats = [d.memory_stats() for d in devices]
    print("memory_stats of %s: %s" % (devices[0], json.dumps(stats[0])))
    return max(s["peak_bytes_in_use"] + s["peak_bytes_reserved"] for s in stats)


def measure(args, cell, config, traffic, end_to_end, per_layer, devices):
    """Everything after the look for a chip: set-up, window, check."""
    import program
    from reference import halving

    kind = devices[0].device_kind
    peaks = device_peaks(kind)

    # ---- set-up
    cache_dir = program.enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    compiles = CompileCounter()
    sweep = load_module("configs", cell["config"] + ".py").build(
        config, traffic, args.seed, devices)
    warmup, _, raised = run_sweeps(
        sweep, args.seed, indices=range(998, 998 + WARMUP_SWEEPS))
    if raised:
        raise SystemExit("run.py: a warm-up sweep raised")
    gc.collect()
    setup_s = time.perf_counter() - T_START
    print("set-up %.3f s; warm-up sweeps: build %s s, wall %s s" % (
        setup_s, [r["build_compile_s"] for r in warmup],
        [round(r["wall_s"], 3) for r in warmup]))

    # ---- window
    compiles_before = compiles.count
    raws, window_s, raised = run_sweeps(sweep, args.seed, seconds=args.seconds)
    compiled_in_window = (compiles.count - compiles_before
                          + sum(r["compiles"] for r in raws))
    memory_peak = memory_peak_bytes(devices)
    print("window %.3f s, %d sweeps; walls in ms: %s" % (
        window_s, len(raws), " ".join("%.0f" % (r["wall_s"] * 1e3) for r in raws)))
    trace = (traced_sweeps(sweep, args.seed, len(raws), devices)
             if args.trace else None)

    # ---- check, on what the window's sweeps returned
    plans = halving.schedule(config, traffic, len(devices))
    records = [r["extract"]() for r in raws]
    reference = load_module("reference", cell["config"] + ".py")
    comparisons = (
        [("sweeps_raised", raised, 0),
         ("programs_compiled_in_window", compiled_in_window, 0)]
        + halving.bookkeeping(records, plans)
        + reference.compare(config, traffic, records, args.seed))
    for name, value, limit in comparisons:
        print("check %-40s %-12.6g limit %g" % (name, value, limit))
    correct = bool(raws) and all(value <= limit for _, value, limit in comparisons)
    expected = halving.schedule_evaluations(plans)
    failed = raised + sum(r["evaluations"] != expected for r in raws)

    # ---- the result
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    ctx = {
        "setup_s": setup_s, "sweeps": raws, "warmup": warmup,
        "window_s": window_s, "chips": len(devices), "config": config,
        "traffic": traffic, "plans": plans, "peaks": peaks,
        "trace": trace, "memory_peak_bytes": memory_peak,
        "cache_new_entries": cache_entries(cache_dir) - entries_before,
    }
    folder, metrics = (("layer_metrics", per_layer) if args.trace
                       else ("end_to_end", end_to_end))
    values = {m["name"]: load_module(folder, m["name"] + ".py").read(ctx)
              for m in metrics}
    if args.trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
    result = {
        "correct": correct,
        "attempted": len(raws) + raised,
        "failed": failed,
        "metrics": {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                    for m in metrics if values[m["name"]] is not None},
        "device": device,
        "sweeps": len(raws),
        "window_s": window_s,
    }
    if args.trace:
        result["breakdown"] = trace["breakdown"]
    return result


if __name__ == "__main__":
    main()
