#!/usr/bin/env python
"""Benchmark: configs evaluated per second per chip, all execution tiers.

Workload: BASELINE.json config #1 — BOHB on the 2-D Branin toy, eta=3,
budget ladder 1..81 — measured on the same machine across the framework's
execution tiers, fastest last:

* **RPC pool** (reference architecture): nameserver/dispatcher/worker,
  strictly one config per worker per TCP RPC round-trip — the reference's
  throughput ceiling (``n_workers / mean_job_seconds``).
* **Per-bracket batched**: ``BOHB + BatchedExecutor(VmapBackend)`` with
  ``parallel_brackets=3`` pipelining — each stage is one device dispatch.
* **Fused whole-sweep** (north star): the ENTIRE multi-bracket sweep —
  KDE proposals, evaluations, top-k promotions, model refits — is one
  compiled device program (``ops/sweep.py``).

Also measured: the fused sweep at 10k-config scale (36 brackets, 1..729)
and the training workloads (budget = SGD steps); ``TIER_ORDER`` is the
full list.

Backend: one process, on the TPU. With no TPU the run FAILS before
measuring anything — a CPU number is never written under a per-chip
name. ``JAX_PLATFORMS=cpu`` set by the caller is the one explicit
exception: a correctness run of the same pipeline (the test suite's
use), whose artifact says ``platform: cpu``.

Methodology: the headline is the MEDIAN of 5 paired runs with the IQR
persisted alongside; every tier's raw runs are in the JSON.

Output contract: the FINAL printed line is a COMPACT JSON summary
({"metric", "value", "unit", "vs_baseline", "platform", "detail_file",
...}, guaranteed < 2000 chars). The full result dict (every tier's
numbers) is written to ``--detail-out`` (default ``BENCH_DETAIL.json``)
and each tier is ALSO appended to ``--partial-out`` (default
``BENCH_PARTIAL.jsonl``) the moment it finishes, so a mid-run death
keeps every finished tier's numbers. A tier that raises is recorded and
the remaining tiers still run, but the process then exits non-zero.

``--tiers a,b,c`` runs a subset, in ``TIER_ORDER``.
"""

import argparse
import json
import logging
import math
import os
import statistics
import sys
import time

logging.getLogger().setLevel(logging.ERROR)
logging.disable(logging.WARNING)

HEADLINE_BRACKETS = 27

#: execution + --tiers order: the tiers with no chip number yet run first
TIER_ORDER = (
    "cnn", "cnn_wide", "pallas", "resnet", "transformer", "fused_1M",
    "fused_100k", "resident_100k", "ensemble_smoke", "fused10k",
    "chunked10k",
    "chunked_compile", "fused",
    "rpc", "batched", "teacher", "multitenant", "serve_continuous",
    "chaos", "async_straggler", "obs_overhead", "timeline_overhead",
    "runtime_overhead", "collector_overhead", "slo_overhead",
    "report_100k",
)

#: per-tier sample size after one warmup run (compile excluded). The driver
#: wrapper that archives this output adds its own top-level ``"n"`` — that is
#: the ROUND COUNTER, not a sample size; sample sizes live here and as
#: ``len(runs_configs_per_s)`` inside each tier dict.
RUNS_PER_TIER = 5


def _require_backend():
    """The bench measures the chip. No TPU -> fail before measuring,
    unless the caller explicitly asked for a CPU correctness run
    (``JAX_PLATFORMS=cpu`` in the environment)."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu" and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        sys.exit(
            "bench: jax found no TPU (default backend %r); refusing to "
            "measure. Set JAX_PLATFORMS=cpu for an explicit CPU "
            "correctness run." % backend
        )
    return backend


def _enable_persistent_compile_cache():
    """Persist XLA executables across processes: the fused sweep's one-time
    compile then amortizes over every later run on this machine. The one
    shared switch lives in utils/compile_cache.py — workers and executors
    call the same function at startup, so non-bench processes stopped
    compiling cold (docs/perf_notes.md "Persistent compile cache")."""
    from hpbandster_tpu.utils.compile_cache import (
        enable_persistent_compile_cache,
    )

    enable_persistent_compile_cache()


def _summary(rates):
    """Median + IQR of per-run rates. Callers must pass >= 3 runs — with
    fewer, a [min, max] spread would masquerade as an IQR."""
    assert len(rates) >= 3, "need >= 3 runs for an honest IQR"
    rates = sorted(rates)
    q = statistics.quantiles(rates, n=4)
    return {
        "median": round(statistics.median(rates), 2),
        "iqr": [round(q[0], 2), round(q[2], 2)],
        "runs_configs_per_s": [round(r, 2) for r in rates],
    }


def _mesh_or_none():
    import jax

    from hpbandster_tpu.parallel import config_mesh

    devices = jax.devices()
    return (config_mesh(devices) if len(devices) > 1 else None), len(devices)


def bench_fused(n_iterations, repeats=5, max_budget=81, seed=0):
    """Fused whole-sweep path; returns (per-run configs/s, eval count,
    per-run timing splits, IQR attribution). The splits let an IQR be
    ATTRIBUTED from the artifact — a wide spread with flat
    device_execute_s is link/host noise, one with moving execute_s is
    real device variance. Each repeat ALSO snapshots the process compile
    ledger (obs/runtime.py): ``ledger_compiles``/``ledger_compile_s`` are
    the compiles the repeat actually paid ANYWHERE in the process (the
    run_stats split only sees the driver's own AOT boundary), and
    ``host_residual_s`` is wall minus device time — the long-standing
    "weak #1" 2.2x 10k-tier IQR decomposes into exactly these three
    components in ``iqr_attribution``."""
    from hpbandster_tpu.obs.runtime import get_compile_tracker
    from hpbandster_tpu.optimizers import FusedBOHB
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    mesh, _ = _mesh_or_none()

    def run(n_iter, seed):
        cs = branin_space(seed=seed)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id=f"bench-{seed}",
            min_budget=1, max_budget=max_budget, eta=3, seed=seed, mesh=mesh,
        )
        t0 = time.perf_counter()
        opt.run(n_iterations=n_iter)
        dt = time.perf_counter() - t0
        compile_s = sum(s["build_compile_s"] for s in opt.run_stats)
        execute_s = sum(s["execute_fetch_s"] for s in opt.run_stats)
        opt.shutdown()
        return opt.total_evaluated, dt, compile_s, execute_s

    run(n_iterations, seed=99)  # warmup: populate jit caches (compile excluded)
    rates, n_evals, splits = [], 0, []
    for i in range(repeats):
        led0 = get_compile_tracker().snapshot()
        n, dt, compile_s, execute_s = run(n_iterations, seed + i)
        led1 = get_compile_tracker().snapshot()
        rates.append(n / dt)
        n_evals = n
        splits.append({
            "wall_s": round(dt, 3),
            "device_compile_s": round(compile_s, 3),
            "device_execute_s": round(execute_s, 3),
            "ledger_compiles": led1["total_compiles"] - led0["total_compiles"],
            "ledger_compile_s": round(
                led1["total_compile_s"] - led0["total_compile_s"], 3
            ),
            "host_residual_s": round(max(dt - compile_s - execute_s, 0.0), 3),
            "configs_per_s_execute": round(n / execute_s, 2)
            if execute_s else None,
        })

    def spread(key):
        vals = [s[key] for s in splits]
        return round(max(vals) - min(vals), 3)

    spreads = {
        "wall_s": spread("wall_s"),
        "device_execute_s": spread("device_execute_s"),
        "ledger_compile_s": spread("ledger_compile_s"),
        "host_residual_s": spread("host_residual_s"),
    }
    dominant = max(
        ("device_execute_s", "ledger_compile_s", "host_residual_s"),
        key=lambda k: spreads[k],
    )
    attribution = {
        "spread_s": spreads,
        # the component whose run-to-run spread explains the wall spread:
        # "host_residual_s" = host bookkeeping/link jitter; a moving
        # ledger_compile_s means a repeat recompiled (cache miss) and its
        # rate is not steady-state
        "dominant": dominant,
    }
    return rates, n_evals, splits, attribution


def bench_fused_sharded(n_configs, repeats=3, max_budget=9, seed=0,
                        single_chip_ref=True):
    """Mesh-sharded fused successive halving at 100k-1M config scale
    (``parallel.multihost.run_sharded_fused_sweep``): one deep bracket,
    per-shard on-device sampling, rung promotions reduced across shards
    on-device, incumbent-only fetch.

    Reported per run: configs/s/chip over the mesh, plus a single-chip
    reference run of the SAME workload on a 1-device mesh so the artifact
    carries ``scaling_efficiency`` = (mesh rate per chip) / (single-chip
    rate) — the near-linear-scaling claim is a number, not prose. A
    bench-side RSS probe records peak-host-RSS growth across the tier
    against the size of the full candidate array: candidates are sampled
    ON DEVICE per shard, so host growth must stay bounded (on the CPU
    backend the "device" heap lives in host RSS, so the probe is strict
    only on accelerator backends — ``rss_note`` says which applied).
    """
    import resource

    import jax

    from hpbandster_tpu.parallel.mesh import config_mesh
    from hpbandster_tpu.parallel.multihost import run_sharded_fused_sweep
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    cs = branin_space(seed=seed)
    devices = jax.devices()
    n_dev = len(devices)
    mesh = config_mesh(devices)
    platform = str(devices[0].platform)
    rss0_kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

    def run(seed, use_mesh):
        return run_sharded_fused_sweep(
            branin_from_vector, cs, n_configs=n_configs, min_budget=1,
            max_budget=max_budget, eta=3, mesh=use_mesh, seed=seed,
        )

    run(seed + 99, mesh)  # warmup: compile excluded from the timed repeats
    rates, last = [], None
    for i in range(repeats):
        r = run(seed + i, mesh)
        rates.append(r["evaluations"] / r["execute_fetch_s"])
        last = r
    out = _summary([rate / n_dev for rate in rates])
    out.update({
        "n_configs": int(n_configs),
        "evaluations_per_run": last["evaluations"],
        "n_devices": n_dev,
        "aligned_stage_counts": last["aligned_stage_counts"],
        "per_device_configs": last["per_device_configs"],
        "alignment_surplus_rows": last["alignment_surplus_rows"],
        "balance_skew": last["balance_skew"],
    })
    if single_chip_ref and n_dev > 1:
        mesh1 = config_mesh(devices[:1])
        run(seed + 98, mesh1)  # warmup the 1-device program too
        r1 = run(seed, mesh1)
        single_rate = r1["evaluations"] / r1["execute_fetch_s"]
        out["single_chip_configs_per_s"] = round(single_rate, 2)
        out["scaling_efficiency"] = round(out["median"] / single_rate, 3)
        # the acceptance bar: per-chip rate within 20% of single-chip
        out["near_linear"] = out["scaling_efficiency"] >= 0.8
    rss1_kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    candidate_mb = n_configs * 2 * 4 / 1e6  # full f32[n0, d=2] on host
    out["host_rss_delta_mb"] = round((rss1_kib - rss0_kib) / 1024.0, 1)
    out["candidate_array_mb"] = round(candidate_mb, 1)
    # strict on accelerators: host growth must not scale with the
    # candidate array (sampling is on-device, uploads are one uint32 seed)
    out["rss_bounded"] = (
        out["host_rss_delta_mb"] < max(64.0, 2.0 * candidate_mb)
        if platform != "cpu" else None
    )
    out["rss_note"] = (
        "cpu backend: device buffers live in host RSS; probe informational"
        if platform == "cpu" else
        "accelerator backend: bound asserted vs candidate-array size"
    )
    return out


def measure_kde_fit_cost(sizes=(1 << 14, 1 << 17, 1 << 20), d=2,
                         repeats=3, seed=0):
    """Truncnorm-KDE fit (``ops.kde.fit_kde_pair_masked``) wall seconds
    at growing observation counts — the "is the model fit the wall at 1M
    observations?" probe (ISSUE 12 / ROADMAP). One shape-polymorphic jit,
    compile excluded, ``block_until_ready`` timed, median of repeats.
    Returns ``{str(n_obs): seconds}``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hpbandster_tpu.ops.kde import fit_kde_pair_masked

    rng = np.random.default_rng(seed)
    cards = jnp.zeros(d, jnp.int32)

    @jax.jit
    def fit(v, l, n, k):
        return fit_kde_pair_masked(v, l, n, k, k, cards, 1e-3)

    out = {}
    for cap in sizes:
        v = jnp.asarray(rng.random((cap, d)).astype(np.float32))
        l = jnp.asarray(rng.random(cap).astype(np.float32))
        k = jnp.int32(max(cap // 10, 3))
        jax.block_until_ready(fit(v, l, jnp.int32(cap), k))  # compile
        ts = []
        for _ in range(int(repeats)):
            t0 = time.perf_counter()
            jax.block_until_ready(fit(v, l, jnp.int32(cap), k))
            ts.append(time.perf_counter() - t0)
        out[str(int(cap))] = round(statistics.median(ts), 4)
    return out


def bench_resident_sharded(sizes=None, n_brackets=3,
                           max_budget=9, seed=0,
                           kde_fit_sizes=(1 << 14, 1 << 17, 1 << 20)):
    """``resident_100k``: the resident (scan-fused) incumbent-only sweep
    (``run_sharded_fused_sweep(resident=True)``) at growing config counts
    on the visible mesh — the whole multi-bracket schedule is ONE device
    dispatch whose host traffic is a 4-byte seed up and one incumbent
    down.

    The flat-d2h acceptance is a measured assertion, not prose: the
    per-sweep ``d2h_bytes``/``h2d_bytes``/``host_syncs`` (note_transfer
    deltas, published as the ``sweep_transfer_bytes`` gauges) must be
    IDENTICAL across every config count — host-sync count per sweep
    constant in config count. The default ladder is 8k/128k, plus a
    1M-config size off the CPU backend; explicit ``sizes`` run as given.

    Runs WITH the device metrics plane ON (ISSUE 13): the in-trace
    telemetry pytree (``ops/sweep.py`` ``DeviceMetrics``) rides the
    incumbent's d2h, so the flat-link assertion now also proves the
    telemetry bill is O(schedule), independent of config count — the
    decoded record's totals land in the tier dict as the evidence.

    Also carried: the truncnorm-KDE fit cost probe
    (:func:`measure_kde_fit_cost`) up to 1M observations, judged against
    this tier's own per-bracket execute seconds — ``fit_is_wall`` says
    whether an in-trace KDE refit would dominate a bracket at the
    largest size (the ``HPB_PALLAS_KDE_FIT`` lever's evidence).
    """
    import jax

    from hpbandster_tpu.parallel.mesh import config_mesh
    from hpbandster_tpu.parallel.multihost import run_sharded_fused_sweep
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    cs = branin_space(seed=seed)
    devices = jax.devices()
    n_dev = len(devices)
    mesh = config_mesh(devices)
    on_cpu = jax.default_backend() == "cpu"
    if sizes is None:
        sizes = (1 << 13, 1 << 17) + (() if on_cpu else (1 << 20,))
    sizes = tuple(int(s) for s in sizes)

    per_size = []
    bills = set()
    telemetry = None
    for n in sizes:
        # warmup compiles the size's program; the timed run measures it.
        # device_metrics=True: the flat-link assertion below must hold
        # WITH the telemetry plane on — that is the tier's ISSUE 13 bar.
        run_sharded_fused_sweep(
            branin_from_vector, cs, n_configs=n, min_budget=1,
            max_budget=max_budget, eta=3, mesh=mesh, seed=seed + 99,
            n_brackets=n_brackets, resident=True, device_metrics=True,
        )
        r = run_sharded_fused_sweep(
            branin_from_vector, cs, n_configs=n, min_budget=1,
            max_budget=max_budget, eta=3, mesh=mesh, seed=seed,
            n_brackets=n_brackets, resident=True, device_metrics=True,
        )
        bills.add((r["d2h_bytes"], r["h2d_bytes"], r["host_syncs"]))
        dt = r.get("device_telemetry") or {}
        telemetry = {
            "evaluations": dt.get("evaluations"),
            "crashes": dt.get("crashes"),
            "crash_rate": dt.get("crash_rate"),
            "rounds_completed": dt.get("rounds_completed"),
            "promotions": dt.get("promotions"),
        }
        per_size.append({
            "n_configs": n,
            "evaluations": r["evaluations"],
            "execute_fetch_s": r["execute_fetch_s"],
            "configs_per_s_per_chip": round(
                r["evaluations"] / r["execute_fetch_s"] / n_dev, 2
            ) if r["execute_fetch_s"] else None,
            "dispatches": len(r["chunks"]),
            "d2h_bytes": r["d2h_bytes"],
            "h2d_bytes": r["h2d_bytes"],
            "host_syncs": r["host_syncs"],
            "incumbent_loss": r["incumbent"]["loss"],
        })
    flat = len(bills) == 1
    if not flat:
        # the tier's acceptance bar: a scaling host-link bill is a
        # regression in the resident contract, and the artifact must
        # say so loudly (the _run_tier wrapper records it as an error)
        raise AssertionError(
            "resident host-link bill is NOT flat in config count: %r"
            % sorted(bills)
        )
    kde_fit = measure_kde_fit_cost(sizes=kde_fit_sizes)
    biggest = per_size[-1]
    per_bracket_s = (
        biggest["execute_fetch_s"] / n_brackets if n_brackets else None
    )
    fit_1m_s = kde_fit.get(str(1 << 20))
    fit_is_wall = (
        bool(fit_1m_s > 0.5 * per_bracket_s)
        if fit_1m_s is not None and per_bracket_s else None
    )
    return {
        "n_devices": n_dev,
        "n_brackets": n_brackets,
        "per_size": per_size,
        "d2h_flat": True,
        # the metrics plane was ON for every measured sweep: the flat
        # bill above INCLUDES the telemetry payload (O(schedule) bytes)
        "device_metrics_enabled": True,
        "device_telemetry": telemetry,
        "host_syncs_per_sweep": per_size[0]["host_syncs"],
        "transfer_gauges": {
            "sweep.transfer_bytes.d2h": per_size[0]["d2h_bytes"],
            "sweep.transfer_bytes.h2d": per_size[0]["h2d_bytes"],
            "sweep.host_syncs": per_size[0]["host_syncs"],
        },
        # the KDE-fit wall probe: seconds per fit by observation count,
        # vs this tier's own per-bracket device seconds. fit_is_wall=True
        # is the signal to flip HPB_PALLAS_KDE_FIT=1 (the Pallas moment
        # kernel, ops/pallas_kde.py) and re-baseline on the chip — on CPU
        # the number is directional only.
        "kde_fit_s": kde_fit,
        "per_bracket_execute_s": (
            round(per_bracket_s, 4) if per_bracket_s else None
        ),
        "fit_is_wall": fit_is_wall,
        "kde_fit_note": (
            "CPU-measured: directional; re-measure (and the Pallas fit "
            "twin) on the chip" if on_cpu else
            "accelerator-measured"
        ),
    }


def bench_ensemble_smoke(n_configs=256, n_brackets=2, max_budget=9,
                         repeats=3, seed=0, resident_sizes=(256, 512)):
    """``ensemble_smoke``: REAL-MODEL training under the fused sweep — the
    r02-era "workloads skipped on CPU" gap, closed. One device dispatch
    trains a whole rung of MLPs (``workloads/ensemble.py``: vmapped SGD,
    budget = cumulative steps, warm continuation across rungs), sized so
    a CPU correctness run finishes it in seconds.

    Two arms:

    - **unrolled**, via ``make_fused_sweep_fn(stateful_eval=...)``
      AOT-compiled (``lower().compile()``) so XLA's cost analysis lands in
      the compile ledger — then ``obs.profile.roofline_report`` must
      CLASSIFY the ensemble program (flops + intensity; bound when the
      device has a peak table entry, the CPU no-peak caveat otherwise).
      This is the first compute-heavy program through PR 7's roofline
      path: the surrogate sweeps it measured before are all bookkeeping.
    - **resident**, via ``run_sharded_fused_sweep(resident=True,
      stateful_eval=...)`` at two config counts — the per-sweep
      (d2h, h2d, host_syncs) bill must be IDENTICAL across sizes: live
      model state is bracket-local device scratch, so the flat host-link
      contract survives real training (asserted, not prose).

    Both arms train >= 256 configs in the first rung (the ISSUE 17
    acceptance bar) at default arguments; the per-lane memory formula
    (``ensemble_lane_bytes``) lands in the tier dict as the number HBM
    sizing starts from.
    """
    import jax
    import numpy as np

    from hpbandster_tpu.obs.profile import roofline_report
    from hpbandster_tpu.ops.bracket import mesh_aligned_plan
    from hpbandster_tpu.ops.sweep import build_space_codec, make_fused_sweep_fn
    from hpbandster_tpu.parallel.mesh import config_mesh, shard_count
    from hpbandster_tpu.parallel.multihost import run_sharded_fused_sweep
    from hpbandster_tpu.workloads.ensemble import (
        MLPConfig, ensemble_lane_bytes, make_mlp_ensemble,
    )
    from hpbandster_tpu.workloads.mlp import mlp_space

    cfg = MLPConfig(d_in=8, width=16, n_classes=4, n_train=128, n_val=64,
                    batch_size=32)
    se = make_mlp_ensemble(cfg, data_seed=seed)
    space = mlp_space(seed=seed)
    codec = build_space_codec(space)
    n_dev = len(jax.devices())

    # ---- unrolled arm: AOT compile -> cost analysis -> roofline row
    plan = mesh_aligned_plan(n_configs, 1.0, float(max_budget), 3.0, 1)
    assert plan.num_configs[0] >= 256, plan  # the ISSUE 17 rung-size bar
    fn = make_fused_sweep_fn(
        None, [plan] * n_brackets, codec, stateful_eval=se,
        # HyperBand mode (unreachable KDE gate): the tier measures the
        # training program, not proposal math
        min_points_in_model=2**30, incumbent_only=True,
        program_name="ensemble_sweep",
    )
    t0 = time.perf_counter()
    compiled = fn.lower(np.uint32(seed)).compile()
    compile_s = time.perf_counter() - t0
    jax.device_get(compiled(np.uint32(seed)))  # warmup execution
    times = []
    for i in range(repeats):
        t0 = time.perf_counter()
        inc = jax.device_get(compiled(np.uint32(seed + i)))
        times.append(time.perf_counter() - t0)
    execute_s = statistics.median(times)
    evals_per_sweep = n_brackets * sum(plan.num_configs)

    # roofline follow-through (ISSUE 17 satellite): the AOT path recorded
    # cost_analysis, so the report must carry a classified row for the
    # ensemble program — intensity always; bound when a peak table entry
    # exists, else the CPU no-peak caveat stands in
    report = roofline_report(
        seconds_by_program={"ensemble_sweep": execute_s}
    )
    rows = [r for r in report["programs"] if r["fn"] == "ensemble_sweep"]
    if not rows:
        raise AssertionError(
            "roofline_report has no 'ensemble_sweep' row — the AOT "
            "cost-analysis path regressed: %r"
            % [r["fn"] for r in report["programs"]]
        )
    roof = rows[-1]
    if not roof["flops"] or roof["intensity_flops_per_byte"] is None:
        raise AssertionError(
            "ensemble program not classified (flops=%r intensity=%r)"
            % (roof["flops"], roof["intensity_flops_per_byte"])
        )
    if roof["bound"] is None and not report["caveats"]:
        raise AssertionError(
            "no bound classification AND no no-peak caveat — the "
            "roofline contract lost its honesty clause"
        )

    # ---- resident arm: flat host-link bill with live model state
    mesh = config_mesh()
    n_shards = shard_count(mesh, "config")
    per_size, bills = [], set()
    for n in resident_sizes:
        run_sharded_fused_sweep(  # warmup: compile this size's program
            None, space, n_configs=n, min_budget=1, max_budget=max_budget,
            eta=3, mesh=mesh, seed=seed + 99, n_brackets=n_brackets,
            resident=True, device_metrics=True, stateful_eval=se,
            program_name="ensemble_sweep",
        )
        r = run_sharded_fused_sweep(
            None, space, n_configs=n, min_budget=1, max_budget=max_budget,
            eta=3, mesh=mesh, seed=seed, n_brackets=n_brackets,
            resident=True, device_metrics=True, stateful_eval=se,
            program_name="ensemble_sweep",
        )
        bills.add((r["d2h_bytes"], r["h2d_bytes"], r["host_syncs"]))
        per_size.append({
            "n_configs": n,
            "evaluations": r["evaluations"],
            "execute_fetch_s": r["execute_fetch_s"],
            "d2h_bytes": r["d2h_bytes"],
            "h2d_bytes": r["h2d_bytes"],
            "host_syncs": r["host_syncs"],
            "incumbent_loss": r["incumbent"]["loss"],
        })
    if len(bills) != 1:
        # the acceptance bar: live training state scaling the host link
        # is a regression in the resident contract — say so loudly
        raise AssertionError(
            "ensemble resident host-link bill is NOT flat in config "
            "count: %r" % sorted(bills)
        )

    lane_bytes = ensemble_lane_bytes(cfg)
    return {
        "model": "MLP %dx%dx%d, %d train samples, batch %d" % (
            cfg.d_in, cfg.width, cfg.n_classes, cfg.n_train,
            cfg.batch_size,
        ),
        "budget_semantics": "cumulative SGD steps, ladder 1..%d" % max_budget,
        "configs_per_rung": plan.num_configs[0],
        "unrolled": {
            "compile_s": round(compile_s, 3),
            "execute_s": round(execute_s, 4),
            "evaluations": evals_per_sweep,
            "configs_per_s_per_chip": round(
                evals_per_sweep / execute_s / n_dev, 2
            ) if execute_s else None,
            "incumbent_loss": float(np.asarray(inc.loss)),
        },
        "roofline": {
            "flops": roof["flops"],
            "bytes_accessed": roof["bytes_accessed"],
            "intensity_flops_per_byte": roof["intensity_flops_per_byte"],
            "bound": roof["bound"],
            "achieved_flops_per_s": roof.get("achieved_flops_per_s"),
            "utilization_vs_peak": roof.get("utilization_vs_peak"),
            "caveats": report["caveats"],
        },
        "resident": {
            "per_size": per_size,
            "d2h_flat": True,
            "host_syncs_per_sweep": per_size[0]["host_syncs"],
        },
        # HBM sizing input (docs/workloads.md memory formula): state bytes
        # per lane; a rung's ensemble costs n_configs x this, plus the
        # shared dataset
        "lane_state_bytes": lane_bytes,
        "rung_state_mb": round(
            plan.num_configs[0] * lane_bytes / 1e6, 3
        ),
    }


def bench_batched(n_iterations=5, repeats=5, seed=0):
    """Per-bracket batched tier: BatchedExecutor + VmapBackend, pb=3."""
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.parallel import BatchedExecutor, VmapBackend
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    mesh, _ = _mesh_or_none()

    def run(seed):
        cs = branin_space(seed=seed)
        executor = BatchedExecutor(
            VmapBackend(branin_from_vector, mesh=mesh), cs, parallel_brackets=3
        )
        opt = BOHB(
            configspace=cs, run_id=f"bench-b{seed}", executor=executor,
            min_budget=1, max_budget=81, eta=3, seed=seed,
        )
        t0 = time.perf_counter()
        res = opt.run(n_iterations=n_iterations)
        dt = time.perf_counter() - t0
        n = len([r for r in res.get_all_runs() if r.loss is not None])
        opt.shutdown()
        return n, dt

    run(seed=99)  # warmup
    rates = []
    for i in range(repeats):
        n, dt = run(seed + i)
        rates.append(n / dt)
    return rates


def bench_rpc_baseline(n_iterations=1, n_workers=1, repeats=5, seed=0):
    """Reference-architecture throughput on this host: one config per RPC."""
    from hpbandster_tpu.core.nameserver import NameServer
    from hpbandster_tpu.core.worker import Worker
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.workloads.toys import branin_dict, branin_space

    class BraninWorker(Worker):
        def compute(self, config_id, config, budget, working_directory):
            return {"loss": branin_dict(config, budget), "info": {}}

    rates = []
    for i in range(repeats):
        ns = NameServer(run_id=f"bench-rpc{i}", host="127.0.0.1", port=0)
        host, port = ns.start()
        for w in range(n_workers):
            BraninWorker(
                run_id=f"bench-rpc{i}", nameserver=host, nameserver_port=port, id=w
            ).run(background=True)
        opt = BOHB(
            configspace=branin_space(seed=seed + i), run_id=f"bench-rpc{i}",
            nameserver=host, nameserver_port=port,
            min_budget=1, max_budget=81, eta=3, seed=seed + i,
        )
        t0 = time.perf_counter()
        res = opt.run(n_iterations=n_iterations, min_n_workers=n_workers)
        dt = time.perf_counter() - t0
        n = len(res.get_all_runs())
        opt.shutdown(shutdown_workers=True)
        ns.shutdown()
        rates.append(n / dt)
    return rates


def _flops_summary(model_flops, wall_s, execute_s, device):
    """Achieved FLOP/s + MFU (vs peak bf16) over device-execute and wall.

    Pass ``execute_s=None`` when no device-time split exists (the batched
    teacher tier): the device-execute keys (``achieved_flops_per_s``,
    ``mfu``) are then OMITTED rather than silently filled with wall-clock
    numbers under the same name — a reader must not confuse the two."""
    from hpbandster_tpu.workloads.flops import peak_bf16_flops

    peak = peak_bf16_flops(device)
    out = {
        "model_flops": round(model_flops),
        "achieved_flops_per_s_incl_host": round(model_flops / wall_s),
        "peak_bf16_flops_per_s": peak,
    }
    if execute_s:
        out["achieved_flops_per_s"] = round(model_flops / execute_s)
        if peak:
            out["mfu"] = round(model_flops / execute_s / peak, 4)
    if peak:
        out["mfu_incl_host"] = round(model_flops / wall_s / peak, 4)
    return out


def _fused_sweep_metrics(opt, res, dt, step_flops, steps_per_budget_unit=1.0):
    """Shared reporting for fused training-workload sweeps: timing split
    from the driver's run_stats + analytic-FLOPs utilization."""
    import jax

    from hpbandster_tpu.workloads.flops import sweep_training_flops

    compile_s = sum(s["build_compile_s"] for s in opt.run_stats)
    execute_s = sum(s["execute_fetch_s"] for s in opt.run_stats)
    # include_failed: crashed configs' steps executed on device (ADVICE r3)
    model_flops = sweep_training_flops(
        res, step_flops, steps_per_budget_unit, include_failed=True
    )
    out = {
        "evaluations": opt.total_evaluated,
        "seconds_incl_compile": round(dt, 2),
        "device_compile_s": round(compile_s, 2),
        "device_execute_s": round(execute_s, 2),
        "configs_per_s_execute": round(opt.total_evaluated / execute_s, 2)
        if execute_s
        else None,
    }
    out.update(_flops_summary(model_flops, dt, execute_s, jax.devices()[0]))
    return out


def bench_cnn(seed=0, n_iterations=5):
    """CNN training sweep (budget = SGD steps): generalization target +
    MFU accounting (VERDICT r2 #1/#9). Loss = 1 - val_accuracy on the
    noise-ceiling dataset; the incumbent must clear the documented target."""
    from hpbandster_tpu.optimizers import FusedBOHB
    from hpbandster_tpu.workloads.cnn import (
        CNN_TARGET_VAL_ACCURACY,
        CNNConfig,
        cnn_space,
        make_cnn_error_fn,
    )
    from hpbandster_tpu.workloads.flops import cnn_step_flops

    mesh, _ = _mesh_or_none()
    cfg = CNNConfig()
    cs = cnn_space(seed=seed)
    opt = FusedBOHB(
        configspace=cs, eval_fn=make_cnn_error_fn(cfg, data_seed=0),
        run_id="bench-cnn", min_budget=3, max_budget=81, eta=3, seed=seed,
        mesh=mesh,
    )
    t0 = time.perf_counter()
    res = opt.run(n_iterations=n_iterations)
    dt = time.perf_counter() - t0
    traj = res.get_incumbent_trajectory()
    inc_acc = 1.0 - traj["losses"][-1]
    out = _fused_sweep_metrics(opt, res, dt, cnn_step_flops(cfg))
    losses = [r.loss for r in res.get_all_runs() if r.loss is not None]
    import math

    out.update(
        {
            # diverging configs (aggressive lr draws) are EXPECTED in HPO;
            # they are masked as crashed and never promoted
            "crashed_configs_masked": sum(
                1 for l in losses if not math.isfinite(l)
            ),
            "incumbent_val_accuracy": round(float(inc_acc), 4),
            "target_val_accuracy": CNN_TARGET_VAL_ACCURACY,
            "target_met": bool(inc_acc >= CNN_TARGET_VAL_ACCURACY),
        }
    )
    opt.shutdown()
    return out


def bench_resnet(seed=0, n_iterations=2):
    """ResNet-18 sweep rung (BASELINE rung 5): budget = SGD steps, GroupNorm
    ResNet on the same generalization dataset; MFU accounting as bench_cnn."""
    from hpbandster_tpu.optimizers import FusedBOHB
    from hpbandster_tpu.workloads.flops import resnet_step_flops
    from hpbandster_tpu.workloads.resnet import (
        ResNetConfig,
        make_resnet_eval_fn,
        resnet_space,
    )

    mesh, _ = _mesh_or_none()
    cfg = ResNetConfig()
    cs = resnet_space(seed=seed)
    opt = FusedBOHB(
        configspace=cs, eval_fn=make_resnet_eval_fn(cfg, data_seed=0),
        run_id="bench-resnet", min_budget=3, max_budget=27, eta=3, seed=seed,
        mesh=mesh,
    )
    t0 = time.perf_counter()
    res = opt.run(n_iterations=n_iterations)
    dt = time.perf_counter() - t0
    out = _fused_sweep_metrics(opt, res, dt, resnet_step_flops(cfg))
    inc_id = res.get_incumbent_id()
    out["incumbent_found"] = inc_id is not None
    opt.shutdown()
    return out


def bench_cnn_wide(seed=0):
    """MXU-saturation probe: the same CNN sweep at MXU-friendly shapes
    (width 128 -> 128/256-channel convs, batch 256). HPO semantics are
    unchanged (FusedHyperBand, one bracket); the question this answers is
    what fraction of peak the *framework* sustains when the model shape
    suits the systolic array — the compute-bound ceiling of the CNN rung."""
    from hpbandster_tpu.optimizers import FusedHyperBand
    from hpbandster_tpu.workloads.cnn import CNNConfig, cnn_space, make_cnn_error_fn
    from hpbandster_tpu.workloads.flops import cnn_step_flops

    mesh, _ = _mesh_or_none()
    cfg = CNNConfig(width=128, batch_size=256, n_train=1024, n_val=256)
    cs = cnn_space(seed=seed)
    opt = FusedHyperBand(
        configspace=cs, eval_fn=make_cnn_error_fn(cfg, data_seed=0),
        run_id="bench-cnn-wide", min_budget=9, max_budget=81, eta=3,
        seed=seed, mesh=mesh,
    )
    t0 = time.perf_counter()
    res = opt.run(n_iterations=1)
    dt = time.perf_counter() - t0
    out = _fused_sweep_metrics(opt, res, dt, cnn_step_flops(cfg))
    opt.shutdown()
    return out


def bench_transformer(seed=0, n_iterations=2):
    """Transformer (attention) sweep rung: the copy task whose second half
    is predictable only through the attention circuit; budget = SGD steps,
    MFU accounting as bench_cnn. The documented target is calibrated from
    a measured 12-draw probe (workloads/transformer.py)."""
    from hpbandster_tpu.optimizers import FusedBOHB
    from hpbandster_tpu.workloads.flops import transformer_step_flops
    from hpbandster_tpu.workloads.transformer import (
        TRANSFORMER_TARGET_VAL_ACCURACY,
        TransformerConfig,
        make_transformer_error_fn,
        transformer_space,
    )

    mesh, _ = _mesh_or_none()
    cfg = TransformerConfig()
    cs = transformer_space(seed=seed)
    opt = FusedBOHB(
        configspace=cs, eval_fn=make_transformer_error_fn(cfg, data_seed=0),
        run_id="bench-tfm", min_budget=3, max_budget=81, eta=3, seed=seed,
        mesh=mesh,
    )
    t0 = time.perf_counter()
    res = opt.run(n_iterations=n_iterations)
    dt = time.perf_counter() - t0
    out = _fused_sweep_metrics(opt, res, dt, transformer_step_flops(cfg))
    traj = res.get_incumbent_trajectory()
    inc_acc = 1.0 - traj["losses"][-1]
    out.update({
        "incumbent_val_accuracy": round(float(inc_acc), 4),
        "target_val_accuracy": TRANSFORMER_TARGET_VAL_ACCURACY,
        "target_met": bool(inc_acc >= TRANSFORMER_TARGET_VAL_ACCURACY),
    })
    opt.shutdown()
    return out


def bench_pallas_scorer(repeats=5):
    """Pallas acquisition scorer vs the XLA path at realistic shapes
    (VERDICT r2 #3): 128 proposals x 64 candidate samples, 256 observations
    per KDE side. Reports both medians and the speedup; FusedBOHB defaults
    follow the winner (see models/bohb_kde.py policy note)."""
    import jax
    import jax.numpy as jnp

    from hpbandster_tpu.ops.kde import KDE, normal_reference_bandwidths, propose
    from hpbandster_tpu.ops.pallas_kde import pallas_available, pallas_propose_batch

    n_obs, d, n_props, n_samples = 256, 6, 128, 64
    key = jax.random.key(0)
    vartypes = jnp.zeros(d, jnp.int32)
    cards = jnp.zeros(d, jnp.int32)

    def mk_kde(k):
        data = jax.random.uniform(k, (n_obs, d))
        mask = jnp.ones(n_obs, jnp.float32)
        bw = normal_reference_bandwidths(data, mask, cards, 1e-3)
        return KDE(data, mask, bw)

    kg, kb, kp = jax.random.split(key, 3)
    good, bad = mk_kde(kg), mk_kde(kb)

    pallas_fn = jax.jit(
        lambda k: pallas_propose_batch(
            k, good, bad, vartypes, cards, n_props, n_samples, 3.0, 1e-3,
            not pallas_available(),
        )
    )
    xla_fn = jax.jit(
        lambda k: jax.vmap(
            lambda kk: propose(kk, good, bad, vartypes, cards, n_samples,
                               3.0, 1e-3)[0]
        )(jax.random.split(k, n_props))
    )

    def timed(fn):
        fn(kp).block_until_ready()  # compile
        ts = []
        for i in range(repeats):
            k = jax.random.fold_in(kp, i)
            t0 = time.perf_counter()
            fn(k).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    t_xla = timed(xla_fn)
    t_pallas = timed(pallas_fn)
    return {
        "shape": f"{n_props} proposals x {n_samples} samples x {n_obs} obs, d={d}",
        "pallas_available": pallas_available(),
        "xla_median_s": round(t_xla, 5),
        "pallas_median_s": round(t_pallas, 5),
        "pallas_speedup": round(t_xla / t_pallas, 2),
    }


def bench_teacher(seed=0):
    """Teacher-student workload: wall-clock to the documented validation-
    accuracy target (budget = epochs; VERDICT r1 #8)."""
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.parallel import BatchedExecutor, VmapBackend
    from hpbandster_tpu.workloads.teacher import (
        TARGET_VAL_ACCURACY,
        make_teacher_eval_fn,
        teacher_space,
    )

    cs = teacher_space(seed=seed)
    executor = BatchedExecutor(VmapBackend(make_teacher_eval_fn()), cs)
    opt = BOHB(
        configspace=cs, run_id="bench-teacher", executor=executor,
        min_budget=1, max_budget=27, eta=3, seed=seed, min_points_in_model=5,
    )
    wall0 = time.time()
    t0 = time.perf_counter()
    res = opt.run(n_iterations=4)
    total = time.perf_counter() - t0
    opt.shutdown()
    traj = res.get_incumbent_trajectory()
    target_err = 1.0 - TARGET_VAL_ACCURACY
    time_to_target = None
    # times_finished are wall-clock job timestamps (reference schema)
    for t, loss in zip(traj["times_finished"], traj["losses"]):
        if loss <= target_err:
            time_to_target = round(t - wall0, 2)  # graftlint: disable=wallclock-duration — times_finished are Job's reference-schema wall timestamps; both ends are wall by API contract
            break
    best_acc = 1.0 - min(traj["losses"]) if traj["losses"] else 0.0
    import jax

    from hpbandster_tpu.workloads.flops import (
        sweep_training_flops,
        teacher_epoch_flops,
    )

    out = {
        "target_val_accuracy": TARGET_VAL_ACCURACY,
        "best_val_accuracy": round(float(best_acc), 4),
        "seconds_to_target_incl_compile": time_to_target,
        "sweep_seconds_total": round(total, 2),
        "evaluations": len(res.get_all_runs()),
    }
    # budget unit = epochs; the batched tier has no device-time split, so
    # utilization is reported against wall-clock only (this rung is an
    # MLP — it measures sweep overhead, not MXU saturation). execute_s=None
    # ⇒ only *_incl_host keys are emitted: no wall-clock number may wear
    # the device-execute MFU key.
    flops = sweep_training_flops(res, teacher_epoch_flops())
    out.update(_flops_summary(flops, total, None, jax.devices()[0]))
    return out


def bench_chunked_10k(seed=60, on_subresult=None):
    """Dynamic-count economics AT SCALE (VERDICT r4 next #5): the
    36-bracket 1..729 schedule — the fused10k program — run chunked
    (``chunk_brackets=6``), dynamic tier FIRST so a run that dies midway
    still keeps the number that has never existed: ``on_subresult`` fires
    the moment each sub-run finishes (collect() appends it to the partial
    trail), so the static comparison dying cannot take the finished
    dynamic dict with it. This is the workload the dynamic tier exists
    for: compile counts are the cache-independent claim, wall rides
    along."""
    return bench_chunked_compile(
        n_iterations=36, chunk=6, max_budget=729, seed=seed,
        dynamic_first=True, warmup=False, on_subresult=on_subresult,
    )


def bench_chunked_compile(n_iterations=9, chunk=3, max_budget=9, seed=70,
                          dynamic_first=False, warmup=True,
                          on_subresult=None):
    """Chunked-sweep compile economics: static tier (each chunk's
    observation counts burned into its trace -> one fresh compile per
    chunk) vs the dynamic-count tier (traced counts -> executable reuse
    across chunk boundaries; ``ops/sweep.py`` ``_fit_kde_pair_dynamic``).

    The structural claim is the FRESH-COMPILE COUNT for the same
    schedule; wall-clock is reported alongside but shrinks when the
    persistent XLA disk cache is warm from an earlier identical run
    (compile counts are cache-independent). Backend-independent — compile
    reuse is a program-structure property.
    """
    from hpbandster_tpu.optimizers import FusedBOHB
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    mesh, _ = _mesh_or_none()

    def run(dynamic):
        # fresh closure per timed invocation: the process-global executable
        # cache keys on eval_fn IDENTITY, so sharing the module-level
        # branin_from_vector would let any earlier same-schedule run (or a
        # second call to this bench) satisfy every lookup and report 0
        # fresh compiles for BOTH tiers (ADVICE r4)
        eval_fn = lambda v, b: branin_from_vector(v, b)  # noqa: E731
        opt = FusedBOHB(
            configspace=branin_space(seed=seed), eval_fn=eval_fn,
            run_id=f"bench-cc-{int(dynamic)}", min_budget=1,
            max_budget=max_budget, eta=3, seed=seed, mesh=mesh,
        )
        t0 = time.perf_counter()
        opt.run(n_iterations=n_iterations, chunk_brackets=chunk,
                dynamic_counts=dynamic)
        dt = time.perf_counter() - t0
        fresh = [
            s["build_compile_s"] for s in opt.run_stats
            if not s["compile_cache_hit"]
        ]
        out = {
            "first_run_wall_s": round(dt, 2),
            "chunks": len(opt.run_stats),
            "fresh_compiles": len(fresh),
            "compile_s_total": round(sum(fresh), 2),
        }
        opt.shutdown()
        if on_subresult is not None:
            # land each sub-run on disk the moment it exists: the OTHER
            # tier dying must not discard a finished measurement that
            # took tens of chip-minutes
            on_subresult("dynamic" if dynamic else "static", out)
        return out

    if warmup:
        # warmup: a throwaway 1-bracket run pays backend init and
        # first-ever XLA pipeline warmup WITHOUT warming the measured
        # executables (its program differs from both timed schedules), so
        # the first-measured ordering doesn't get billed process warmup
        warm = FusedBOHB(
            configspace=branin_space(seed=seed), eval_fn=branin_from_vector,
            run_id="bench-cc-warm", min_budget=1, max_budget=max_budget,
            eta=3, seed=seed, mesh=mesh,
        )
        warm.run(n_iterations=1)
        warm.shutdown()

    if dynamic_first:
        # at-scale variant: the dynamic number is the missing one — run
        # it first (and on_subresult lands it on disk immediately), so a
        # death during the static comparison cannot cost it
        dynamic = run(True)
        static = run(False)
    else:
        static = run(False)
        dynamic = run(True)
    wall = (
        round(static["first_run_wall_s"] / dynamic["first_run_wall_s"], 2)
        if dynamic["first_run_wall_s"] > 0 else None
    )
    return {
        "schedule": "%d brackets, chunk %d, budgets 1..%d"
                    % (n_iterations, chunk, max_budget),
        "static": static,
        "dynamic": dynamic,
        "fresh_compiles_static_vs_dynamic": [
            static["fresh_compiles"], dynamic["fresh_compiles"]
        ],
        "first_run_wall_speedup": wall,
    }


def bench_obs_overhead(repeats=3, n_iterations=3, inner=20, seed=0):
    """No-sink cost of the always-on obs instrumentation on the batched
    sweep path (BOHB + BatchedExecutor + VmapBackend on Branin, budgets
    1..9).

    Headline (``overhead_pct``) is COMPUTED, not raced: (per-call cost of
    a sinkless emit / counter inc, measured over long loops that average
    out scheduler noise) x (instrumented calls in one sweep, counted
    exactly by a counting sink + metric-snapshot delta) / (warm sweep
    wall). A direct A/B wall-clock comparison rides along as a
    cross-check (``ab_wall``), but on a shared host its noise floor
    (measured: adjacent identical blocks varying 2x) sits far above a
    sub-percent effect — the computed product is the citable number and
    the reproducible one. Acceptance bar (docs/observability.md): < 2%."""
    from hpbandster_tpu import obs
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.parallel import BatchedExecutor, VmapBackend
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    def run_once(s):
        cs = branin_space(seed=s)
        executor = BatchedExecutor(
            VmapBackend(branin_from_vector), cs, parallel_brackets=3
        )
        opt = BOHB(
            configspace=cs, run_id=f"bench-obs{s}", executor=executor,
            min_budget=1, max_budget=9, eta=3, seed=s,
        )
        res = opt.run(n_iterations=n_iterations)
        n = len(res.get_all_runs())
        opt.shutdown()
        return n

    # --- micro: per-call cost with no sink attached (long loops: the
    # per-op signal accumulates far above scheduler noise)
    bus = obs.EventBus()  # fresh sinkless bus
    n_micro = 200_000
    t0 = time.perf_counter()
    for _ in range(n_micro):
        bus.emit("job_submitted", config_id=(0, 0, 0), budget=1.0)
    emit_ns = (time.perf_counter() - t0) / n_micro * 1e9
    reg = obs.MetricsRegistry()
    counter = reg.counter("bench")
    t0 = time.perf_counter()
    for _ in range(n_micro):
        counter.inc()
    counter_ns = (time.perf_counter() - t0) / n_micro * 1e9
    # trace-envelope injection with no active trace — the cost every
    # RPCProxy.call pays since trace propagation landed (one ContextVar
    # read; must stay ~free for the <2% bar to hold on RPC-heavy tiers)
    t0 = time.perf_counter()
    for _ in range(n_micro):
        obs.current_wire()
    inject_ns = (time.perf_counter() - t0) / n_micro * 1e9
    # audit-record emit with no sink — what every add_configuration pays
    # per sample since the decision audit landed (the field-dict build is
    # behind the bus.active check, so this must stay ~one boolean check)
    t0 = time.perf_counter()
    for _ in range(n_micro):
        obs.emit_config_sampled(
            (0, 0, 0), 1.0, {"model_based_pick": False, "sample_reason": "no_model"}
        )
    audit_ns = (time.perf_counter() - t0) / n_micro * 1e9

    # --- exact instrumented-call census of one sweep
    events = []
    detach = obs.get_bus().subscribe(lambda ev: events.append(ev.name))
    try:
        snap0 = sum(obs.get_metrics().snapshot()["counters"].values())
        n_evals = run_once(seed + 7777)
        snap1 = sum(obs.get_metrics().snapshot()["counters"].values())
    finally:
        detach()
    n_emits = len(events)
    n_incs = int(snap1 - snap0)

    # --- A/B wall cross-check: paired blocks of pre-warmed sweeps,
    # alternating arm order
    def timed_block(enabled, seeds):
        obs.set_enabled(enabled)
        try:
            t0 = time.perf_counter()
            for s in seeds:
                run_once(s)
            return time.perf_counter() - t0
        finally:
            obs.set_enabled(True)

    run_once(99)  # process warmup (compile never timed)
    t_on_total = t_off_total = 0.0
    for r in range(repeats):
        seeds = [seed + r * inner + i for i in range(inner)]
        for s in seeds:
            run_once(s)
        order = (True, False) if r % 2 == 0 else (False, True)
        dt = {}
        for enabled in order:
            dt[enabled] = timed_block(enabled, seeds)
        t_on_total += dt[True]
        t_off_total += dt[False]

    sweep_s = t_off_total / max(repeats * inner, 1)
    per_sweep_cost_s = (n_emits * emit_ns + n_incs * counter_ns) / 1e9

    # --- device metrics plane (ISSUE 13): the in-trace accumulate cost
    # (same fused program with vs without the telemetry outputs, warm
    # medians) and the host decode cost per sweep — both judged under
    # the same <2% bar as the headline. HyperBand mode keeps the model
    # math out of the trace so the paired compile stays cheap and the
    # delta isolates the telemetry arithmetic.
    import statistics

    import jax as _jax
    import numpy as _np

    from hpbandster_tpu.obs.device_metrics import decode_device_metrics
    from hpbandster_tpu.ops.sweep import build_space_codec, make_fused_sweep_fn

    _cs = branin_space(seed=seed)
    _codec = build_space_codec(_cs)
    # a wide bracket so the sweep does real device work: a 9-config toy
    # schedule's wall is pure dispatch overhead and any delta reads as
    # tens of percent; the telemetry term is O(n) binning next to O(n)
    # evaluation, so the share must be measured where n dominates
    from hpbandster_tpu.ops.bracket import BracketPlan

    _plans = [
        BracketPlan((4096, 1365, 455), tuple(float(b) for b in (1, 3, 9)))
    ] * 2
    fn_off = make_fused_sweep_fn(
        branin_from_vector, _plans, _codec, min_points_in_model=2**30,
    )
    fn_on = make_fused_sweep_fn(
        branin_from_vector, _plans, _codec, min_points_in_model=2**30,
        device_metrics=True,
    )
    _jax.block_until_ready(fn_off(_np.uint32(seed)))  # warm compiles
    _jax.block_until_ready(fn_on(_np.uint32(seed)))

    def _one(fn, s):
        t0 = time.perf_counter()
        _jax.block_until_ready(fn(_np.uint32(s)))
        return time.perf_counter() - t0

    # INTERLEAVED pairs (off, on, off, on ...): shared-host wall drift
    # hits both arms of a pair equally, so the per-pair delta median is
    # far stabler than two separate medians subtracted
    pairs = [
        (_one(fn_off, seed + i), _one(fn_on, seed + i)) for i in range(15)
    ]
    t_plain = statistics.median(p[0] for p in pairs)
    delta_s = max(statistics.median(p[1] - p[0] for p in pairs), 0.0)
    micro_evals = sum(sum(p.num_configs) for p in _plans)
    accumulate_ns_per_eval = delta_s / micro_evals * 1e9
    _, dm = _jax.device_get(fn_on(_np.uint32(seed)))
    t0 = time.perf_counter()
    n_dec = 200
    for _ in range(n_dec):
        decode_device_metrics(dm, plans=_plans)
    decode_s = (time.perf_counter() - t0) / n_dec
    dm_bytes = int(sum(_np.asarray(l).nbytes for l in dm))
    # the gated number, same denominator discipline as the headline:
    # what the metrics plane would cost THIS tier's real sweep (its
    # eval census x the per-eval accumulate cost + one decode) over its
    # warm wall. The toy-objective share also rides along — branin is
    # ~one FLOP per eval, so that is the metrics plane's WORST case (on
    # any real objective the per-eval binning vanishes under training).
    device_metrics_pct = (
        round(
            100.0
            * (accumulate_ns_per_eval * n_evals / 1e9 + decode_s)
            / sweep_s,
            3,
        )
        if sweep_s else None
    )

    return {
        "path": "batched sweep (BOHB + BatchedExecutor, %d brackets, "
                "budgets 1..9)" % n_iterations,
        "evaluations_per_sweep": n_evals,
        "emit_no_sink_ns": round(emit_ns, 1),
        "counter_inc_ns": round(counter_ns, 1),
        "trace_inject_no_trace_ns": round(inject_ns, 1),
        "audit_emit_ns": round(audit_ns, 1),
        "instrumented_calls_per_sweep": {"emits": n_emits, "counter_incs": n_incs},
        "warm_sweep_s": round(sweep_s, 5),
        "overhead_pct": round(100.0 * per_sweep_cost_s / sweep_s, 3)
        if sweep_s else None,
        # the metrics plane's bill: in-trace accumulate (paired warm
        # medians of the SAME fused program with/without telemetry) +
        # host decode per sweep, as a share of the bare sweep — the
        # <2% acceptance bar applies to this number too
        "device_metrics": {
            "accumulate_ns_per_eval": round(accumulate_ns_per_eval, 1),
            "decode_s": round(decode_s, 6),
            "payload_bytes": dm_bytes,
            "overhead_pct": device_metrics_pct,
            "toy_share_pct": round(
                100.0 * delta_s / t_plain, 2
            ) if t_plain else None,
            "note": "overhead_pct projects the per-eval accumulate cost "
                    "+ one decode onto this tier's real sweep (same "
                    "denominator as the headline); toy_share_pct is the "
                    "worst case — branin is ~one FLOP per eval",
        },
        "ab_wall": {
            "enabled_no_sink_total_s": round(t_on_total, 4),
            "disabled_total_s": round(t_off_total, 4),
            "overhead_pct_of_totals": round(
                100.0 * (t_on_total - t_off_total) / t_off_total, 2
            ) if t_off_total else None,
            "note": "shared-host wall noise floor >> sub-percent effects; "
                    "cross-check only",
        },
    }


def bench_timeline_overhead(repeats=3, inner=8, seed=0, n_micro=100_000,
                            sizes=(512, 4096)):
    """Flight-recorder cost (obs/timeline.py) under the same <2% bar as
    obs_overhead, plus the timeline tier's two structural assertions.

    Headline (``overhead_pct``) is the RECORDER-OFF path, COMPUTED not
    raced (the obs_overhead method): per-call cost of the inactive
    timeline span API (no sink -> no clock reads, no Event) x the exact
    record census of one warm fused sweep (device metrics on) / the warm
    sweep wall — the cost every run pays now that the span API exists,
    gated < 2% (the byte-identical-off guarantee). The recorder-ON
    session cost rides along under ``recording``: per-record cost of an
    attached TimelineRecorder (~one list append on top of the Event
    construction EVERY sink pays) x the same census / the same wall.
    That share is a worst case by construction — the census sweep's
    objective is ~one FLOP per eval, so the wall is pure dispatch; on
    any real workload the µs-scale per-record cost vanishes (same
    framing as obs_overhead's ``toy_share_pct``). An interleaved A/B
    wall cross-check rides along (same caveat as obs_overhead:
    shared-host noise floor >> sub-percent effects).

    Structural assertions:

    * flat host link — the ``rung_seq`` stamp rides the O(schedule)
      telemetry pytree, so the device-metrics payload bytes must be
      IDENTICAL across config counts (``sizes``); growth means the stamp
      leaked an O(configs) term onto the resident d2h bill (hard error).
    * critical path — the analyzer runs over the recorded sweep journal;
      its machine-readable verdict lands in BUDGET_VERDICTS (persisted as
      detail.budgets.verdicts.timeline_critical_path, next to the
      compile/transfer verdicts). Recorded, not gated: a toy sweep's
      ms-scale wall makes the share noisy, and the e2e test pins the
      >=95% claim on a controlled journal.
    """
    import statistics

    from hpbandster_tpu.obs.timeline import (
        RUNG_COMPUTE,
        TimelineRecorder,
        critical_path,
        mark,
        phase_span,
        to_chrome_trace,
    )
    from hpbandster_tpu.optimizers import FusedBOHB
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    def run_once(s, n_iterations=3):
        cs = branin_space(seed=s)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector,
            run_id=f"bench-tl{s}", min_budget=1, max_budget=9, eta=3,
            seed=s,
        )
        opt.run(n_iterations=n_iterations, device_metrics=True)
        n = opt.total_evaluated
        opt.shutdown()
        return n

    # --- micro: the inactive span API (recorder off = global bus has no
    # sink in this process) and the per-record recorder-on cost
    t0 = time.perf_counter()
    for _ in range(n_micro):
        with phase_span("bench_timeline_probe", RUNG_COMPUTE):
            pass
    span_inactive_ns = (time.perf_counter() - t0) / n_micro * 1e9
    t0 = time.perf_counter()
    for _ in range(n_micro):
        mark("bench_timeline_probe", RUNG_COMPUTE)
    mark_inactive_ns = (time.perf_counter() - t0) / n_micro * 1e9
    with TimelineRecorder() as _probe_rec:
        t0 = time.perf_counter()
        for _ in range(n_micro):
            mark("bench_timeline_probe", RUNG_COMPUTE)
        record_ns = (time.perf_counter() - t0) / n_micro * 1e9
    del _probe_rec

    # --- exact record census of one warm sweep, recorder attached; the
    # recorded journal then feeds the critical-path analyzer and the
    # Chrome-trace assembly stats
    run_once(seed + 99)  # warmup (compile never timed)
    with TimelineRecorder() as rec:
        n_evals = run_once(seed + 7777)
    n_records = len(rec.records)
    cp = critical_path(rec.records)
    BUDGET_VERDICTS["timeline_critical_path"] = dict(cp["verdict"])
    chrome_stats = {
        k: v for k, v in to_chrome_trace(rec.records)["otherData"].items()
        if k != "generator"
    }

    # --- warm wall + interleaved A/B cross-check (recorder on vs off)
    def timed_block(recorder_on, seeds):
        t0 = time.perf_counter()
        if recorder_on:
            with TimelineRecorder():
                for s in seeds:
                    run_once(s)
        else:
            for s in seeds:
                run_once(s)
        return time.perf_counter() - t0

    t_on_total = t_off_total = 0.0
    sweep_walls = []
    for r in range(repeats):
        seeds = [seed + r * inner + i for i in range(inner)]
        for s in seeds:
            run_once(s)
        order = (True, False) if r % 2 == 0 else (False, True)
        dt = {}
        for recorder_on in order:
            dt[recorder_on] = timed_block(recorder_on, seeds)
        t_on_total += dt[True]
        t_off_total += dt[False]
        sweep_walls.append(dt[False] / max(len(seeds), 1))
    sweep_s = statistics.median(sweep_walls) if sweep_walls else 0.0

    # --- flat host-link assertion: same bracket geometry, growing config
    # counts — the telemetry payload (rung_seq stamp included) must not
    # move a byte
    import jax as _jax
    import numpy as _np

    from hpbandster_tpu.ops.bracket import BracketPlan
    from hpbandster_tpu.ops.sweep import build_space_codec, make_fused_sweep_fn

    _codec = build_space_codec(branin_space(seed=seed))
    payload_bytes = {}
    for n in sizes:
        _plans = [
            BracketPlan((n, n // 3, n // 9), (1.0, 3.0, 9.0))
        ] * 2
        fn = make_fused_sweep_fn(
            branin_from_vector, _plans, _codec,
            min_points_in_model=2**30, device_metrics=True,
        )
        _, dm = _jax.device_get(fn(_np.uint32(seed)))
        payload_bytes[str(n)] = int(sum(
            _np.asarray(l).nbytes
            for l in _jax.tree_util.tree_leaves(dm)
        ))
    if len(set(payload_bytes.values())) != 1:
        raise RuntimeError(
            "resident host-link bill is NOT flat: device-metrics payload "
            "bytes grew with config count: %r" % payload_bytes
        )

    per_sweep_recorder_s = n_records * record_ns / 1e9
    per_sweep_off_s = n_records * span_inactive_ns / 1e9
    return {
        "path": "fused sweep (FusedBOHB, 3 brackets, budgets 1..9, "
                "device metrics on)",
        "evaluations_per_sweep": n_evals,
        "records_per_sweep": n_records,
        "span_inactive_ns": round(span_inactive_ns, 1),
        "mark_inactive_ns": round(mark_inactive_ns, 1),
        "warm_sweep_s": round(sweep_s, 5),
        # the gated number: what the timeline span API costs with the
        # recorder OFF (no sink) — the path every run pays. Bar: < 2%.
        "overhead_pct": round(
            100.0 * per_sweep_off_s / sweep_s, 3
        ) if sweep_s else None,
        "recording": {
            "record_ns": round(record_ns, 1),
            "overhead_pct": round(
                100.0 * per_sweep_recorder_s / sweep_s, 3
            ) if sweep_s else None,
            "note": "opt-in recording-session cost: Event construction "
                    "(paid by ANY attached sink) + one list append, on "
                    "the worst-case denominator (branin is ~one FLOP "
                    "per eval, so the census sweep's wall is pure "
                    "dispatch)",
        },
        "host_link_flat": {"payload_bytes": payload_bytes, "flat": True},
        "critical_path": cp,
        "chrome_trace": chrome_stats,
        "ab_wall": {
            "recorder_total_s": round(t_on_total, 4),
            "bare_total_s": round(t_off_total, 4),
            "overhead_pct_of_totals": round(
                100.0 * (t_on_total - t_off_total) / t_off_total, 2
            ) if t_off_total else None,
            "note": "shared-host wall noise floor >> sub-percent effects; "
                    "cross-check only",
        },
    }


def bench_runtime_overhead(repeats=3, inner=100_000, seed=0):
    """Tracked-jit dispatch overhead (obs/runtime.py) under the <2% bar.

    Three numbers, all computed rather than raced (the obs_overhead
    method): warm per-call dispatch of the SAME tiny jitted function raw
    vs through ``tracked_jit`` (the delta is the signature hash + set
    lookup every steady-state call pays); the tracked-call census of one
    real batched sweep (counter delta) times that delta over the sweep
    wall — the headline ``overhead_pct``; and one DeviceSampler census
    pass (paid per sampling interval, not per dispatch). The sweep's own
    compile ledger delta rides along so the artifact separates compile
    time from steady-state throughput."""
    import numpy as np

    from hpbandster_tpu import obs
    from hpbandster_tpu.obs.runtime import DeviceSampler, tracked_jit
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.parallel import BatchedExecutor, VmapBackend
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    import jax

    def tiny(x):
        return x * 2.0 + 1.0

    raw = jax.jit(tiny)
    tracked = tracked_jit(tiny, name="bench_runtime_overhead_tiny")
    x = np.ones(8, np.float32)
    raw(x), tracked(x)  # warm both (compile + first tracked signature)

    def per_call_ns(fn):
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(inner):
                fn(x)
            dt = (time.perf_counter() - t0) / inner * 1e9
            best = dt if best is None else min(best, dt)
        return best

    # alternate arms so neither always pays the cache-warm position
    tracked_ns = per_call_ns(tracked)
    raw_ns = per_call_ns(raw)
    tracked_ns = min(tracked_ns, per_call_ns(tracked))
    raw_ns = min(raw_ns, per_call_ns(raw))
    overhead_ns = max(tracked_ns - raw_ns, 0.0)

    t0 = time.perf_counter()
    DeviceSampler().sample()
    sampler_pass_s = time.perf_counter() - t0

    # census + wall of one real warm sweep through the tracked ops
    def run_once(s):
        cs = branin_space(seed=s)
        executor = BatchedExecutor(
            VmapBackend(branin_from_vector), cs, parallel_brackets=3
        )
        opt = BOHB(
            configspace=cs, run_id=f"bench-rt{s}", executor=executor,
            min_budget=1, max_budget=9, eta=3, seed=s,
        )
        opt.run(n_iterations=3)
        opt.shutdown()

    run_once(seed + 91)  # warm (compiles excluded from the timed run)
    calls0 = obs.get_metrics().counter("runtime.tracked_calls").value
    led0 = obs.get_compile_tracker().snapshot()
    t0 = time.perf_counter()
    run_once(seed + 92)
    sweep_s = time.perf_counter() - t0
    led1 = obs.get_compile_tracker().snapshot()
    n_calls = obs.get_metrics().counter("runtime.tracked_calls").value - calls0

    per_sweep_cost_s = n_calls * overhead_ns / 1e9
    return {
        "raw_dispatch_ns": round(raw_ns, 1),
        "tracked_dispatch_ns": round(tracked_ns, 1),
        "tracked_overhead_ns": round(overhead_ns, 1),
        "sampler_pass_s": round(sampler_pass_s, 5),
        "tracked_calls_per_sweep": int(n_calls),
        "warm_sweep_s": round(sweep_s, 5),
        "overhead_pct": (
            round(100.0 * per_sweep_cost_s / sweep_s, 4) if sweep_s else None
        ),
        "sweep_compiles": {
            "count": led1["total_compiles"] - led0["total_compiles"],
            "seconds": round(
                led1["total_compile_s"] - led0["total_compile_s"], 3
            ),
        },
    }


def bench_collector_overhead(rounds=40, n_endpoints=3, interval_s=2.0,
                             seed=0):
    """Fleet-collector poll cost vs sweep wall under the <2% obs bar.

    Computed, not raced (the obs_overhead method): stand up
    ``n_endpoints`` REAL health endpoints (RPC servers in-process, the
    same ``obs_snapshot`` the fleet serves) and measure the median wall
    cost of one full ``FleetCollector.poll_once()`` round — N socket
    round-trips + derivation + one series line. The headline
    ``overhead_pct`` is the steady-state duty cycle, poll_round_s /
    interval_s: because the collector fires on a fixed interval, its
    share of ANY sweep's wall reduces to exactly that ratio (the
    per-sweep product cancels the sweep length by construction, unlike
    obs_overhead where a measured per-sweep call census makes the sweep
    load-bearing). One timed sweep rides along as context only —
    ``rounds_per_sweep`` says how many polls land inside a real sweep
    at this interval."""
    import tempfile

    from hpbandster_tpu import obs
    from hpbandster_tpu.obs.collector import FleetCollector
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.parallel import BatchedExecutor, VmapBackend
    from hpbandster_tpu.parallel.rpc import RPCServer
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    servers = []
    endpoints = {}
    for i in range(n_endpoints):
        srv = RPCServer("127.0.0.1", 0)
        obs.HealthEndpoint(
            component="worker" if i else "dispatcher",
        ).register(srv)
        srv.start()
        servers.append(srv)
        endpoints[f"ep{i}"] = srv.uri
    series = tempfile.NamedTemporaryFile(
        suffix=".jsonl", delete=False
    ).name
    collector = FleetCollector(
        endpoints=endpoints, interval_s=interval_s, series_path=series,
    )
    try:
        collector.poll_once()  # warm (connection setup, first derivation)
        times = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            collector.poll_once()
            times.append(time.perf_counter() - t0)
        times.sort()
        poll_round_s = times[len(times) // 2]
    finally:
        collector.stop()
        for srv in servers:
            srv.shutdown()
        try:
            os.unlink(series)
        except OSError:
            pass

    # one sweep wall, context only (the headline cancels it — docstring)
    def run_once(s):
        cs = branin_space(seed=s)
        executor = BatchedExecutor(
            VmapBackend(branin_from_vector), cs, parallel_brackets=3
        )
        opt = BOHB(
            configspace=cs, run_id=f"bench-coll{s}", executor=executor,
            min_budget=1, max_budget=9, eta=3, seed=s,
        )
        opt.run(n_iterations=3)
        opt.shutdown()

    t0 = time.perf_counter()
    run_once(seed + 32)
    sweep_s = time.perf_counter() - t0

    duty_cycle_pct = 100.0 * poll_round_s / interval_s
    return {
        "n_endpoints": n_endpoints,
        "poll_rounds_timed": rounds,
        "poll_round_s": round(poll_round_s, 6),
        "interval_s": interval_s,
        "duty_cycle_pct": round(duty_cycle_pct, 4),
        "sweep_s_context": round(sweep_s, 5),
        "rounds_per_sweep": round(sweep_s / interval_s, 2),
        # == duty_cycle_pct by construction; kept as the cross-tier
        # headline key every obs tier's <2% bar is read from
        "overhead_pct": round(duty_cycle_pct, 4),
    }


def bench_slo_overhead(micro_records=20_000, n_tenants=4, max_budget=9,
                       seed=0):
    """SLO evaluator + alert lifecycle cost under the <2% obs bar.

    Computed, not raced (the obs_overhead method): the per-record cost
    of one ``AlertManager.process()`` tick is measured over a synthetic
    mixed stream exercising every objective shape in the default pack
    (threshold, ratio, counter, staleness), then projected onto a REAL
    journaled ServePool churn running a LIVE manager:
    ``overhead_pct = slo-relevant record census x tick cost / warm churn
    wall``. The churn doubles as the acceptance run — its journal is
    re-evaluated offline (``scan_slo_records``, the ``obs slo`` path)
    and the live manager's transitions AND published gauge values must
    match **byte-identically**; the machine-readable verdict
    ``{firing, budget_remaining, ok, replay_identical}`` rides the tier
    dict (the gate is on overhead + replay — whether the tiny churn
    actually breaches an objective is load-dependent context).
    Budget-gated like every tier (TIER_BUDGETS['slo_overhead'], the
    serve-pool ceiling: the evaluator itself must add zero device work).
    """
    import tempfile
    import threading

    from hpbandster_tpu import obs
    from hpbandster_tpu.obs.alerts import AlertManager, scan_slo_records
    from hpbandster_tpu.obs.summarize import read_merged_ex
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.parallel import VmapBackend
    from hpbandster_tpu.serve import ServePool
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    # ---- micro: per-record manager tick over a synthetic mixed stream
    micro = AlertManager(bus=None)
    stream = []
    for i in range(micro_records):
        t = float(i) * 0.01
        k = i % 6
        if k == 0:
            stream.append({"event": "serve_admission", "t_wall": t,
                           "wait_s": 0.01})
        elif k == 1:
            stream.append({"event": "rpc_client_call", "t_wall": t,
                           "duration_s": 0.001})
        elif k == 2:
            stream.append({"event": "tenant_auth", "t_wall": t, "ok": True})
        elif k == 3:
            stream.append({"event": "serve_chunk", "t_wall": t,
                           "starved": 0})
        elif k == 4:
            stream.append({"event": "device_telemetry", "t_wall": t,
                           "evaluations": 8, "crashes": 0})
        else:
            stream.append({"event": "kde_refit", "t_wall": t})
    for r in stream[:256]:
        micro.process(r)  # warm (window allocation, first measures)
    t0 = time.perf_counter()
    for r in stream:
        micro.process(r)
    process_s = (time.perf_counter() - t0) / micro_records

    # ---- real churn: journaled ServePool run with a live manager
    journal_path = tempfile.NamedTemporaryFile(
        suffix=".jsonl", delete=False
    ).name
    handle = obs.configure(journal_path=journal_path, slo=True)

    def churn(s):
        pool = ServePool(
            VmapBackend(branin_from_vector), branin_space(seed=s),
            pack_window_s=0.02,
        )

        def drive(i):
            opt = BOHB(
                configspace=branin_space(seed=s + i),
                run_id=f"bench-slo{s}-{i}", tenant_id=f"tenant{i}",
                executor=pool.executor_for(f"tenant{i}"),
                min_budget=1, max_budget=max_budget, eta=3, seed=s + i,
            )
            opt.run(n_iterations=1)
            opt.shutdown()

        threads = [
            threading.Thread(target=drive, args=(i,), daemon=True)
            for i in range(n_tenants)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    try:
        churn(seed)  # warm: compiles + first admissions
        timed_from = time.time()
        warm_wall = churn(seed + 64)
        live_transitions = list(handle.slo.transitions)
        live_published = handle.slo.published()
        snap = handle.slo.snapshot()
    finally:
        handle.close()

    records, _skipped = read_merged_ex([journal_path])
    try:
        os.unlink(journal_path)
    except OSError:
        pass
    offline = scan_slo_records(records)
    replay_identical = bool(
        list(offline.transitions) == live_transitions
        and offline.published() == live_published
    )
    relevant = (
        "serve_admission", "serve_chunk", "tenant_auth",
        "device_telemetry", "rpc_client_call", "rpc_retry", "kde_refit",
        "sweep_chunk",
    )
    census = sum(
        1 for r in records
        if r.get("event") in relevant
        and isinstance(r.get("t_wall"), (int, float))
        and r["t_wall"] >= timed_from
    )
    overhead_pct = 100.0 * census * process_s / warm_wall
    budgets = [
        p["budget_remaining"] for p in live_published.values()
        if p.get("budget_remaining") is not None
    ]
    worst_budget = min(budgets) if budgets else None
    return {
        "micro_records": micro_records,
        "process_ns": round(process_s * 1e9, 1),
        "specs": len(offline.specs),
        "slo_records_per_churn": census,
        "warm_churn_s": round(warm_wall, 5),
        "overhead_pct": round(overhead_pct, 4),
        "replay": {
            "live_transitions": len(live_transitions),
            "identical": replay_identical,
        },
        # the obs slo verdict shape, riding the bench artifact
        "verdict": {
            "firing": snap["firing"],
            "budget_remaining": worst_budget,
            "ok": bool(
                snap["firing"] == 0
                and (worst_budget is None or worst_budget > 0.0)
                and replay_identical
            ),
            "replay_identical": replay_identical,
        },
    }


def bench_multitenant(n_tenants=16, repeats=3, max_budget=9, seed=0):
    """Multi-tenant serving tier: sustained configs/s + packing efficiency.

    ``n_tenants`` concurrent mixed-size BOHB sweeps (1-3 brackets each,
    round-robin — the ragged demand a serving tier actually sees) drive
    one shared ``ServePool``: fair-scheduled, cross-tenant megabatched
    (``hpbandster_tpu/serve``). The PAIRED baseline is one tenant pushing
    the SAME total bracket workload through an identical pool —
    ``packing_efficiency`` is multi-tenant configs/s over single-tenant
    configs/s, the number that says what cross-tenant packing recovers
    from ragged demand (>= ~1 means N tenants cost no throughput vs one).
    ``p95_queue_wait_s`` is each work item's enqueue->dispatch wait (the
    serving-tier proposal-latency proxy) read as a bucket-count DELTA of
    the ``serve.queue_wait_s`` histogram around the measured multi-tenant
    runs only — the warmup and single-tenant baselines feed the same
    process-global histogram and must not dilute it. Budget-gated
    like every tier (TIER_BUDGETS['multitenant']): the megabatch path
    must stay inside the bucketed compile counts the PR 6 layer
    established — a per-shape or per-tenant compile regression blows the
    ceiling immediately."""
    import threading

    from hpbandster_tpu import obs
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.parallel import VmapBackend
    from hpbandster_tpu.serve import ServePool
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    #: tenant i runs 1 + (i % 3) brackets — mixed sizes by construction
    def tenant_iters(i):
        return 1 + (i % 3)

    total_brackets = sum(tenant_iters(i) for i in range(n_tenants))

    def run_multi(s):
        pool = ServePool(
            VmapBackend(branin_from_vector), branin_space(seed=s),
            pack_window_s=0.02,
        )
        done = {}

        def drive(i):
            opt = BOHB(
                configspace=branin_space(seed=s + i),
                run_id=f"bench-mt{s}-{i}", tenant_id=f"tenant{i}",
                executor=pool.executor_for(f"tenant{i}"),
                min_budget=1, max_budget=max_budget, eta=3, seed=s + i,
            )
            res = opt.run(n_iterations=tenant_iters(i))
            opt.shutdown()
            done[i] = len(res.get_all_runs())

        threads = [
            threading.Thread(target=drive, args=(i,), daemon=True)
            for i in range(n_tenants)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        return sum(done.values()), dt

    def run_single(s):
        pool = ServePool(
            VmapBackend(branin_from_vector), branin_space(seed=s),
            pack_window_s=0.0,
        )
        opt = BOHB(
            configspace=branin_space(seed=s), run_id=f"bench-mt-solo{s}",
            tenant_id="solo", executor=pool.executor_for("solo"),
            min_budget=1, max_budget=max_budget, eta=3, seed=s,
        )
        t0 = time.perf_counter()
        res = opt.run(n_iterations=total_brackets)
        dt = time.perf_counter() - t0
        opt.shutdown()
        return len(res.get_all_runs()), dt

    def _serve_snapshot(reg):
        # the registry is process-global and cumulative: the warmup run
        # and the single-tenant baselines feed the SAME queue-wait
        # histogram and megabatch counters, so the reported numbers must
        # be deltas around the measured multi-tenant block only
        h = reg.histogram("serve.queue_wait_s")
        snap = reg.snapshot()
        hist = snap["histograms"].get(
            "serve.queue_wait_s",
            {"count": 0, "max": None, "buckets": {}},
        )
        return {
            "bounds": h.bounds,
            "count": hist["count"],
            "max": hist["max"],
            "buckets": dict(hist["buckets"]),
            "counters": {
                k: snap["counters"].get(k, 0)
                for k in ("serve.megabatch.dispatches",
                          "serve.megabatch.packed_brackets",
                          "serve.megabatch.pad_lanes")
            },
        }

    def _delta_p95(before, after):
        # Histogram.quantile's conservative upper-bound rule over the
        # delta bucket counts. The overflow bucket has no delta-able
        # bound: the cumulative max is only honest for this block when
        # the block itself set it — otherwise (a warmup-era max) fall
        # back to the largest finite bound, flagged as a floor.
        count = after["count"] - before["count"]
        if count <= 0:
            return None

        def overflow_bound():
            if before["max"] is None or after["max"] != before["max"]:
                return after["max"]
            return after["bounds"][-1]

        keys = [str(b) for b in after["bounds"]] + ["+inf"]
        rank = 0.95 * count
        acc = 0
        for i, k in enumerate(keys):
            c = after["buckets"].get(k, 0) - before["buckets"].get(k, 0)
            acc += c
            if acc >= rank and c:
                return (
                    after["bounds"][i] if i < len(after["bounds"])
                    else overflow_bound()
                )
        return overflow_bound()

    reg = obs.get_metrics()
    run_multi(seed + 99)  # warmup: bucket + megabatch programs compile
    before = _serve_snapshot(reg)
    multi_rates, single_rates = [], []
    for i in range(repeats):
        n, dt = run_multi(seed + i)
        multi_rates.append(n / dt)
    after = _serve_snapshot(reg)
    for i in range(repeats):
        n1, dt1 = run_single(seed + i)
        single_rates.append(n1 / dt1)

    p95_wait = _delta_p95(before, after)
    mega = {
        k.rsplit(".", 1)[-1]: after["counters"][k] - before["counters"][k]
        for k in after["counters"]
    }
    multi = _summary(multi_rates)
    single = _summary(single_rates)
    return {
        "n_tenants": n_tenants,
        "total_brackets": total_brackets,
        "median": multi["median"],
        "iqr": multi["iqr"],
        "runs_configs_per_s": multi["runs_configs_per_s"],
        "single_tenant": single,
        "packing_efficiency": round(multi["median"] / single["median"], 3)
        if single["median"] else None,
        "p95_queue_wait_s": p95_wait,
        "megabatch": mega,
    }


def bench_serve_continuous(n_tenants=8, lane_count=4, brackets_per_tenant=2,
                           repeats=3, max_budget=9, seed=0,
                           stagger_s=0.02):
    """Continuous-batching serving tier: steady tenant arrival/departure
    through the RESIDENT lane programs (``serve/continuous.py``) vs the
    SAME workload through the one-shot megabatch path.

    ``n_tenants`` concurrent BOHB tenants arrive staggered (``stagger_s``
    apart — the serving tier's steady-arrival shape) and depart as they
    finish; each runs ``brackets_per_tenant`` brackets (EQUAL demand, so
    the fairness yardstick is exact). Reported per arm:

    * ``median``/``iqr`` configs/s over ``repeats`` runs against ONE
      long-lived pool per arm (a serving pool lives for days — repeats
      against a fresh pool would re-measure compile, not serving);
    * ``p95_admission_to_first_result_s`` — per tenant, submission to
      its FIRST delivered result (the continuous-batching latency
      claim: a joining tenant boards the next chunk of a warm program
      instead of waiting out a cold dispatch);
    * ``compile_ledger`` — ``continuous_bracket`` compile delta across
      the WHOLE churning block, pinned <= len(bucket_set): however many
      tenants come and go, the lane programs never recompile;
    * ``lane_occupancy``/``lanes_starved``/``chunks`` from the lane
      gauges, and the fairness bar (no tenant below 80% of its
      deficit-fair served-cost share) under continuous allocation.

    Budget-gated like every tier (TIER_BUDGETS['serve_continuous']).
    """
    import threading

    from hpbandster_tpu import obs
    from hpbandster_tpu.obs.runtime import get_compile_tracker
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.parallel import VmapBackend
    from hpbandster_tpu.serve import ServePool
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    total_brackets = n_tenants * brackets_per_tenant

    def p95(xs):
        if not xs:
            return None
        xs = sorted(xs)
        return xs[min(int(math.ceil(0.95 * len(xs))) - 1, len(xs) - 1)]

    def run_fleet(pool, s):
        """One arrival/departure wave; returns (configs, wall_s,
        per-tenant submit->first-result latencies)."""
        done, first, submit = {}, {}, {}

        def drive(i):
            tenant = f"tenant{i}"
            ex = pool.executor_for(tenant)
            orig_finish = ex._finish

            def _finish(job, loss, _orig=orig_finish, t=tenant):
                if t not in first:
                    first[t] = time.perf_counter()
                _orig(job, loss)

            ex._finish = _finish
            submit[tenant] = time.perf_counter()
            opt = BOHB(
                configspace=branin_space(seed=s + i),
                run_id=f"bench-sc{s}-{i}", tenant_id=tenant,
                executor=ex, min_budget=1, max_budget=max_budget,
                eta=3, seed=s + i,
            )
            res = opt.run(n_iterations=brackets_per_tenant)
            opt.shutdown()
            done[i] = len(res.get_all_runs())

        threads = [
            threading.Thread(target=drive, args=(i,), daemon=True)
            for i in range(n_tenants)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
            time.sleep(stagger_s)  # steady arrival, not a thundering herd
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        lat = [
            first[t] - submit[t] for t in submit if t in first
        ]
        return sum(done.values()), dt, lat

    def run_arm(continuous, s):
        pool = ServePool(
            VmapBackend(branin_from_vector), branin_space(seed=s),
            pack_window_s=0.02, continuous=continuous,
            lane_count=lane_count,
        )
        rates, lats = [], []
        run_fleet(pool, s + 99)  # warmup: programs compile
        for i in range(repeats):
            n, dt, lat = run_fleet(pool, s + i)
            rates.append(n / dt)
            lats.extend(lat)
        shares = pool.scheduler.served_cost
        total_cost = sum(shares.values()) or 1.0
        fair = 1.0 / max(len(shares), 1)
        min_ratio = (
            min(c / total_cost for c in shares.values()) / fair
            if shares else None
        )
        return pool, rates, lats, min_ratio

    reg = obs.get_metrics()
    led0 = (
        get_compile_tracker().snapshot()["functions"]
        .get("continuous_bracket", {}).get("compiles", 0)
    )
    chunks0 = int(reg.counter("serve.continuous.chunks").value)
    pool_c, cont_rates, cont_lats, cont_min_ratio = run_arm(True, seed)
    led1 = (
        get_compile_tracker().snapshot()["functions"]
        .get("continuous_bracket", {}).get("compiles", 0)
    )
    _pool_o, shot_rates, shot_lats, _shot_ratio = run_arm(False, seed)

    snap = reg.snapshot()["gauges"]
    buckets = pool_c.snapshot()["buckets"]
    cont = _summary(cont_rates)
    shot = _summary(shot_rates)
    return {
        "n_tenants": n_tenants,
        "lane_count": lane_count,
        "total_brackets": total_brackets,
        "median": cont["median"],
        "iqr": cont["iqr"],
        "runs_configs_per_s": cont["runs_configs_per_s"],
        "one_shot": shot,
        "continuous_vs_one_shot": (
            round(cont["median"] / shot["median"], 3)
            if shot["median"] else None
        ),
        "p95_admission_to_first_result_s": {
            "continuous": round(p95(cont_lats), 4) if cont_lats else None,
            "one_shot": round(p95(shot_lats), 4) if shot_lats else None,
        },
        "lane_occupancy": snap.get("serve.lane_occupancy"),
        "lanes_starved": snap.get("serve.lanes.starved"),
        "chunks": (
            int(reg.counter("serve.continuous.chunks").value) - chunks0
        ),
        "compile_ledger": {
            "continuous_bracket_compiles": led1 - led0,
            "bucket_programs": buckets,
            "pinned": (led1 - led0) <= max(buckets, 1),
        },
        "fairness": {
            "min_share_ratio": (
                round(cont_min_ratio, 3)
                if cont_min_ratio is not None else None
            ),
            "ok": (
                cont_min_ratio is not None and cont_min_ratio >= 0.8
            ),
        },
    }


def bench_chaos(n_workers=4, n_iterations=3, seed=0, repeats=3,
                kill_fraction=0.1, tick_s=0.25, outage_s=0.25,
                compute_s_per_budget=0.02,
                delay_rate=0.05, partition_rate=0.05, duplicate_rate=0.1):
    """Elastic-fleet chaos tier: throughput retention and trajectory
    consistency under ~10% worker churn (docs/fault_tolerance.md).

    Paired seeded sweeps over the real host pool (nameserver +
    dispatcher + ``n_workers`` socket workers): one undisturbed, one
    with every worker behind a :class:`~hpbandster_tpu.parallel.chaos.
    ChaosProxy` carrying seeded rate faults (delays, partitions,
    duplicate deliveries — the exactly-once gate's diet) and a
    ChaosMonkey killing each alive worker with probability
    ``kill_fraction`` per ``tick_s`` with ``outage_s`` outages — the
    defaults hold ~10% of the pool dead at any instant
    ((0.1/0.25s)*0.25s). ``compute_s_per_budget`` paces the objective so
    sweeps span enough monkey ticks for kills to land mid-compute (the
    clean run pays the identical pacing, so retention stays a fair
    pairing). The numbers that matter:

    * ``throughput_retention`` — churn configs/s over clean configs/s
      (paired seeds, medians): what 10% churn actually costs end to end
      once requeues, backoff, and late-result joins are paid;
    * ``trajectory_consistent`` — every paired run produced the
      identical (config, budget, loss) set and incumbent (pure seeded
      sampling, so any divergence is lost or double-counted work);
    * the ``recovery.*`` counter deltas — how many requeues, duplicate
      drops, and replays the churn actually provoked (a zero row means
      the tier measured nothing).

    Host-side sockets + a python objective: no device compiles, so the
    tier measures on any backend like the obs tiers.
    """
    from hpbandster_tpu import obs
    from hpbandster_tpu.core.nameserver import NameServer
    from hpbandster_tpu.core.worker import Worker
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.parallel.chaos import (
        ChaosMonkey,
        ChaosProxy,
        ChaosSchedule,
    )
    from hpbandster_tpu.parallel.dispatcher import Dispatcher
    from hpbandster_tpu.workloads.toys import branin_dict, branin_space

    class ChurnWorker(Worker):
        def compute(self, config_id, config, budget, working_directory):
            # a budget-proportional cost so kills land mid-compute
            time.sleep(compute_s_per_budget * float(budget))
            return {"loss": branin_dict(config, budget), "info": {}}

    def run_once(s, churn):
        run_id = f"bench-chaos-{s}-{'churn' if churn else 'clean'}"
        ns = NameServer(run_id=run_id, host="127.0.0.1", port=0)
        host, port = ns.start()
        proxies = {}
        monkey = opt = None
        # one seeded decision stream shared by every proxy: the fault
        # sequence is a function of (s, call order), replayable like the
        # chaos tests
        schedule = ChaosSchedule(
            seed=s, delay_rate=delay_rate, partition_rate=partition_rate,
            duplicate_rate=duplicate_rate, delay_s=0.02,
        ) if churn else None
        try:
            for i in range(n_workers):
                w = ChurnWorker(
                    run_id=run_id, nameserver=host, nameserver_port=port,
                    id=i,
                )
                w.result_delivery_backoff = 0.02
                w.result_delivery_backoff_cap = 0.2
                w.run(background=True)
                if churn:
                    p = ChaosProxy(w._server.uri, schedule).start()
                    p.interpose(host, port, w.worker_id)
                    proxies[w.worker_id] = p
            d = Dispatcher(
                run_id=run_id, nameserver=host, nameserver_port=port,
                ping_interval=0.1, discover_interval=0.1,
                requeue_backoff=0.02, requeue_backoff_cap=0.2,
            )
            opt = BOHB(
                configspace=branin_space(seed=s), run_id=run_id,
                executor=d, min_budget=1, max_budget=9, eta=3, seed=s,
                # pure seeded sampling: the trajectory is a function of
                # the seed alone, so churn-vs-clean divergence can only
                # mean lost or double-counted work
                min_points_in_model=10_000,
            )
            if churn:
                monkey = ChaosMonkey(
                    proxies, seed=s, interval_s=tick_s,
                    kill_fraction=kill_fraction, outage_s=outage_s,
                    max_dead=n_workers - 1,
                ).start()
            t0 = time.perf_counter()
            res = opt.run(n_iterations=n_iterations, min_n_workers=n_workers)
            dt = time.perf_counter() - t0
            runs = {
                (r.config_id, r.budget): r.loss for r in res.get_all_runs()
            }
            kills = (
                len([e for e in monkey.log if e[2] == "kill"])
                if monkey is not None else 0
            )
            return runs, res.get_incumbent_id(), len(runs) / dt, kills
        finally:
            # cleanup runs on the FAILURE path too: a sweep that dies
            # under unlucky churn must not leak its monkey thread or its
            # worker pool into the remaining repeats' measurements
            if monkey is not None:
                monkey.stop()
            if opt is not None:
                opt.shutdown(shutdown_workers=True)
            for p in proxies.values():
                p.shutdown()
            ns.shutdown()

    reg = obs.get_metrics()
    recovery_keys = (
        "recovery.requeues", "recovery.duplicates_dropped",
        "recovery.replayed_results", "recovery.quarantines",
        "chaos.faults",
    )
    before = {k: reg.counter(k).value for k in recovery_keys}
    clean_rates, churn_rates, kills_per_run = [], [], []
    consistent = True
    for i in range(repeats):
        s = seed + i
        runs_c, inc_c, rate_c, _ = run_once(s, churn=False)
        runs_x, inc_x, rate_x, kills = run_once(s, churn=True)
        clean_rates.append(rate_c)
        churn_rates.append(rate_x)
        kills_per_run.append(kills)
        if runs_x != runs_c or inc_x != inc_c:
            consistent = False
    deltas = {
        k.split(".", 1)[-1]: reg.counter(k).value - before[k]
        for k in recovery_keys
    }
    clean = _summary(clean_rates)
    churn = _summary(churn_rates)
    return {
        "n_workers": n_workers,
        "n_iterations": n_iterations,
        "median": churn["median"],
        "iqr": churn["iqr"],
        "runs_configs_per_s": churn["runs_configs_per_s"],
        "clean": clean,
        "throughput_retention": round(churn["median"] / clean["median"], 3)
        if clean["median"] else None,
        "trajectory_consistent": consistent,
        "kills_per_run": kills_per_run,
        "recovery": deltas,
        "churn_knobs": {
            "kill_fraction_per_tick": kill_fraction,
            "tick_s": tick_s, "outage_s": outage_s,
            "expected_dead_fraction": round(
                kill_fraction / tick_s * outage_s, 3
            ),
        },
    }


def bench_async_straggler(n_workers=3, n_iterations=2, seed=0, repeats=3,
                          compute_s_per_budget=0.004, straggler_s=0.35,
                          straggler_min_samples=4):
    """Async-promotion tier: what the rung barrier costs under one
    straggler, and what ASHA buys back (docs/promotion.md).

    Paired seeded sweeps over the real host pool (nameserver +
    dispatcher + ``n_workers`` socket workers), one worker injected as a
    straggler: its compute sleeps ``straggler_s`` extra per evaluation —
    the one-host-quietly-10x-slower shape the anomaly detector's
    straggler rule flags. (The injection sits in compute, not on the
    RPC path: a chaos-proxy delay fault serializes through the
    dispatcher's single dispatch loop and would stall BOTH arms equally
    — head-of-line, not the barrier.) Each seed runs the same sweep
    twice: the paper's synchronous successive-halving barrier, then
    ``promotion_rule="asha"``. Both journal, and both pay the identical
    worker pacing, so the deltas isolate the promotion rule:

    * ``barrier_stall_s`` — max seconds a promoted config sat between
      its rung result and the decision that promoted it
      (``promote.replay.promotion_waits``): the barrier made
      measurable. Sync pays ~``straggler_s`` per stalled rung; ASHA's
      stays near zero;
    * ``utilization_delta`` — fleet busy-fraction (ASHA - sync) from
      the journals' run spans: what the idle wait cost the pool;
    * ``throughput_ratio`` — ASHA configs/s over sync configs/s
      (paired seeds, medians);
    * ``straggler_markers`` — ``straggler_observed`` entries on the
      recorded promotion decisions (the anomaly -> audit loop,
      threshold lowered to fire on bench-scale rungs).

    Host-side sockets + a python objective: no device compiles, so the
    tier measures on any backend like the obs tiers.
    """
    import tempfile

    from hpbandster_tpu import obs
    from hpbandster_tpu.core.nameserver import NameServer
    from hpbandster_tpu.core.worker import Worker
    from hpbandster_tpu.obs.anomaly import AnomalyRules
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.parallel.dispatcher import Dispatcher
    from hpbandster_tpu.promote.replay import (
        promotion_waits,
        worker_utilization,
    )
    from hpbandster_tpu.workloads.toys import branin_dict, branin_space

    class PacedWorker(Worker):
        straggle_s = 0.0

        def compute(self, config_id, config, budget, working_directory):
            time.sleep(compute_s_per_budget * float(budget) + self.straggle_s)
            return {"loss": branin_dict(config, budget), "info": {}}

    def run_once(s, rule):
        run_id = f"bench-straggler-{s}-{rule or 'sync'}"
        journal = os.path.join(
            tempfile.mkdtemp(prefix="bench_straggler_"), "journal.jsonl"
        )
        handle = obs.configure(
            journal_path=journal,
            anomaly=AnomalyRules(
                straggler_min_samples=straggler_min_samples,
                straggler_factor=2.0, cooldown_s=0.0,
            ),
        )
        ns = NameServer(run_id=run_id, host="127.0.0.1", port=0)
        host, port = ns.start()
        opt = None
        try:
            for i in range(n_workers):
                w = PacedWorker(
                    run_id=run_id, nameserver=host, nameserver_port=port,
                    id=i,
                )
                if i == 0:  # the injected straggler
                    w.straggle_s = straggler_s
                w.run(background=True)
            d = Dispatcher(
                run_id=run_id, nameserver=host, nameserver_port=port,
                ping_interval=0.1, discover_interval=0.1,
            )
            opt = BOHB(
                configspace=branin_space(seed=s), run_id=run_id,
                executor=d, min_budget=1, max_budget=9, eta=3, seed=s,
                min_points_in_model=10_000,  # pure seeded sampling
                promotion_rule=rule,
            )
            t0 = time.perf_counter()
            res = opt.run(n_iterations=n_iterations, min_n_workers=n_workers)
            dt = time.perf_counter() - t0
            n_runs = len(res.get_all_runs())
            incumbent = res.get_incumbent_id()
        finally:
            if opt is not None:
                opt.shutdown(shutdown_workers=True)
            ns.shutdown()
            handle.close()
        records = obs.read_journal(journal)
        waits = promotion_waits(records)
        util = worker_utilization(records)
        stragglers = sum(
            len(r.get("straggler_observed") or [])
            for r in records if r.get("event") == "promotion_decision"
        )
        return {
            "rate": n_runs / dt,
            "incumbent": incumbent,
            "stall_s": waits["max_wait_s"] or 0.0,
            "mean_wait_s": waits["mean_wait_s"] or 0.0,
            "busy_fraction": util["busy_fraction"],
            "straggler_markers": stragglers,
        }

    sync_rates, asha_rates = [], []
    sync_stalls, asha_stalls = [], []
    util_deltas, markers = [], 0
    for i in range(repeats):
        s = seed + i
        sync = run_once(s, None)
        asha = run_once(s, "asha")
        sync_rates.append(sync["rate"])
        asha_rates.append(asha["rate"])
        sync_stalls.append(sync["stall_s"])
        asha_stalls.append(asha["stall_s"])
        if (
            sync["busy_fraction"] is not None
            and asha["busy_fraction"] is not None
        ):
            util_deltas.append(asha["busy_fraction"] - sync["busy_fraction"])
        markers += sync["straggler_markers"] + asha["straggler_markers"]
    def summarize(rates):
        # the smoke lane runs a single pair; an IQR from < 3 runs would
        # masquerade as spread, so it reports median-only there
        if len(rates) >= 3:
            return _summary(rates)
        return {
            "median": round(statistics.median(rates), 2),
            "iqr": None,
            "runs_configs_per_s": [round(r, 2) for r in sorted(rates)],
        }

    sync_summary = summarize(sync_rates)
    asha_summary = summarize(asha_rates)
    return {
        "n_workers": n_workers,
        "n_iterations": n_iterations,
        "straggler_s": straggler_s,
        "median": asha_summary["median"],
        "iqr": asha_summary["iqr"],
        "runs_configs_per_s": asha_summary["runs_configs_per_s"],
        "sync": sync_summary,
        "throughput_ratio": (
            round(asha_summary["median"] / sync_summary["median"], 3)
            if sync_summary["median"] else None
        ),
        "barrier_stall_s": {
            "sync_median": round(statistics.median(sync_stalls), 4),
            "asha_median": round(statistics.median(asha_stalls), 4),
        },
        "utilization_delta": (
            round(sum(util_deltas) / len(util_deltas), 4)
            if util_deltas else None
        ),
        "straggler_markers": markers,
    }


def bench_report_100k(n_events=100_000, seed=0):
    """Report-CLI throughput over a synthetic ``n_events``-line journal.

    Synthesizes a journal shaped like a real sweep's (config_sampled /
    job_finished with losses / promotion_decision / kde_refit / worker
    churn), then times the full ``report`` path: rotated-set read, merge,
    ``build_report``, text render. Renders TWICE and compares bytes —
    the determinism acceptance bar rides the bench, not just the tests.
    Stdlib + obs only: measures on any backend.
    """
    import random as _random
    import tempfile

    from hpbandster_tpu.obs.report import build_report, format_report
    from hpbandster_tpu.obs.summarize import read_merged_ex

    rng = _random.Random(seed)
    t_wall = 1_700_000_000.0
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "synthetic.jsonl")
        n = 0
        t0 = time.perf_counter()
        with open(path, "w", encoding="utf-8") as fh:
            i = 0
            while n < n_events:
                cid = [i // 27, 0, i % 27]
                t_wall += rng.random() * 0.01
                model = i % 3 != 0
                recs = [
                    {"event": "config_sampled", "t_wall": t_wall,
                     "t_mono": n * 1e-3, "config_id": cid, "budget": 1.0,
                     "model_based_pick": model,
                     "sample_reason": "model" if model else "random_fraction",
                     "lg_score": round(rng.random() * 5, 6)},
                    {"event": "job_finished", "t_wall": t_wall + 0.005,
                     "t_mono": n * 1e-3 + 0.005, "config_id": cid,
                     "budget": 1.0, "worker": f"w{i % 7}",
                     "run_s": 0.004 + rng.random() * 0.002,
                     "loss": round(rng.random() * 100, 6)},
                ]
                if i % 27 == 26:
                    ids = [[i // 27, 0, k] for k in range(27)]
                    recs.append({
                        "event": "promotion_decision", "t_wall": t_wall,
                        "t_mono": n * 1e-3, "iteration": i // 27, "rung": 0,
                        "budget": 1.0, "next_budget": 3.0,
                        "rule": "successive_halving",
                        "config_ids": ids,
                        "losses": [round(rng.random() * 100, 6)
                                   for _ in ids],
                        "promoted": [k < 9 for k in range(27)],
                        "n_promoted": 9, "n_candidates": 27,
                        "cut_threshold": 33.0,
                        "survivor_losses": [1.0] * 9,
                    })
                if i % 100 == 99:
                    recs.append({
                        "event": "kde_refit", "t_wall": t_wall,
                        "t_mono": n * 1e-3, "budget": 1.0,
                        "n_obs": i, "duration_s": 0.001,
                    })
                for rec in recs:
                    fh.write(json.dumps(rec) + "\n")
                n += len(recs)
                i += 1
        synth_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        records, skipped = read_merged_ex([path])
        read_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        rep = build_report(records)
        text_a = format_report(rep)
        report_s = time.perf_counter() - t0
        text_b = format_report(build_report(records))
    total_s = read_s + report_s
    return {
        "n_events": n,
        "synthesize_s": round(synth_s, 3),
        "read_merge_s": round(read_s, 3),
        "build_render_s": round(report_s, 3),
        "events_per_s": round(n / total_s) if total_s > 0 else None,
        "skipped_lines": skipped,
        "deterministic": text_a == text_b,
        "alerts_found": rep["alerts"]["total"],
    }


def _append_partial(path, record, truncate=False):
    """One JSON line per finished tier, flushed + fsynced: the on-disk
    trail survives any way the process dies. ``truncate`` starts a fresh
    file for the run's ``_meta`` header line."""
    try:
        with open(path, "w" if truncate else "a") as f:
            f.write(json.dumps(record, default=str) + "\n")
            f.flush()
            os.fsync(f.fileno())
    except OSError as e:
        print("bench: partial write to %s failed: %s" % (path, e),
              file=sys.stderr)


#: per-tier compile ledger + transfer-counter deltas (obs/runtime.py),
#: filled by _run_tier and persisted as detail.compile_by_tier — the
#: numbers that let the trajectory separate compile time from
#: steady-state throughput, AND the observations the budget gate below
#: judges
COMPILE_BY_TIER = {}

#: Per-tier compile-count and transfer-byte BUDGETS — the enforcement arm
#: of the runtime telemetry (ISSUE 6 / ROADMAP "gate it: bench asserts
#: compile-count and transfer-byte budgets per tier so regressions FAIL,
#: not drift"). Exceeding a budget lands a loud ``budget:<tier>`` entry
#: in the artifact's error dict — which marks the whole artifact degraded
#: and makes the process exit non-zero — plus a stderr banner. Numbers are
#: structural ceilings with headroom, not measured medians: the fused
#: tiers compile ONE whole-sweep program (cache-shared across repeats),
#: the chunked tiers a handful (dynamic reuse + static per-chunk), the
#: batched tier a bucket set + stage kernels. A per-shape compile
#: regression (the tax this PR removed) blows 2-3x headroom immediately;
#: honest variance does not. ``max_transfer_mb`` bounds h2d+d2h bytes at
#: the repo's counted choke points — warm sweep state round-tripping
#: through the host per rung is exactly what it catches. Tiers not named
#: here are ungated (their cost is dominated by workload compiles that
#: scale with --smoke schedules).
#: ceilings re-baselined 2026-08-03 on a FULL-SCHEDULE explicit-CPU run
#: (PR 10; compile counts and transfer bytes are structural — program
#: count and choke-point bytes don't change with the backend, only wall
#: does): fused measured 1 compile / 0.16 MB (was budgeted 6/16),
#: chunked_compile 5 compiles / 0.013 MB (was 12/32). Ceilings now sit
#: at measured + honest headroom; tier dicts carry a platform stamp so
#: any stale comparison is self-describing. Not re-checked on the chip.
TIER_BUDGETS = {
    "fused":           {"max_compiles": 4,  "max_transfer_mb": 4},
    "fused10k":        {"max_compiles": 6,  "max_transfer_mb": 16},
    # mesh-sharded 100k/1M tiers: ONE sweep program per mesh shape — the
    # timed mesh program plus the single-chip reference program (compile
    # count <= len(bucket_set) per shape, +2 slack for workload warmup).
    # Transfers are structural: candidates sample ON DEVICE per shard, so
    # the host link carries one uint32 seed up and one incumbent down per
    # run — megabytes of headroom, not gigabytes of candidates (measured
    # CPU 8-device mesh: 2 compiles, <0.01 MB for fused_100k).
    "fused_100k":      {"max_compiles": 4,  "max_transfer_mb": 8},
    # resident tier: ONE scanned program per config-count size (2 sizes
    # on CPU, 3 with the accelerator 1M rung) plus the KDE-fit probe's
    # shape-polymorphic jit (one compile per observation-count shape).
    # Transfers are the whole point: a 4-byte seed up + one incumbent
    # down per sweep — the 8 MB ceiling is pure headroom for the warmup
    # runs' bills
    "resident_100k":   {"max_compiles": 10, "max_transfer_mb": 8},
    # real-model ensemble tier: one AOT unrolled program + one resident
    # program per config-count size (2 sizes) — the warmups reuse the
    # process-wide executable caches, so 8 is structural ceiling + slack.
    # Transfers stay incumbent-only (the live model state NEVER crosses
    # the host link — that is the tier's flat-bill assertion)
    "ensemble_smoke":  {"max_compiles": 8,  "max_transfer_mb": 8},
    "fused_1M":        {"max_compiles": 4,  "max_transfer_mb": 16},
    "chunked_compile": {"max_compiles": 8,  "max_transfer_mb": 16},
    "chunked10k":      {"max_compiles": 20, "max_transfer_mb": 128},
    "batched":         {"max_compiles": 24, "max_transfer_mb": 64},
    "rpc":             {"max_compiles": 8,  "max_transfer_mb": 16},
    # serving tier (hpbandster_tpu/serve): megabatch programs are capped
    # at one per bucket (<= len(bucket_set)); with the solo bucket twins,
    # the KDE propose kernels, and the cross-tenant stage batches the
    # structural ceiling sits near 20 — 16 mixed-size tenants of ragged
    # demand must NOT compile per tenant or per pack size, which is
    # exactly the regression a blown ceiling would catch
    "multitenant":     {"max_compiles": 32, "max_transfer_mb": 64},
    # continuous-batching tier (serve/continuous.py): the whole point is
    # ONE resident lane program per bucket family across an entire
    # churning workload — the continuous arm's ledger is pinned to
    # <= len(bucket_set) inside the tier dict itself; the ceiling here
    # additionally covers the one-shot comparison arm's megabatch/solo
    # programs and the KDE propose kernels
    "serve_continuous": {"max_compiles": 32, "max_transfer_mb": 64},
    # elastic/chaos tier: host sockets + a python objective — the
    # recovery machinery must cost (near) zero device work; a compile
    # appearing here means chaos plumbing leaked onto the device path
    "chaos":           {"max_compiles": 4,  "max_transfer_mb": 8},
    # async-promotion tier: same host-socket diet as chaos — promotion
    # bookkeeping is pure host work, so a compile here means a rule
    # implementation dragged device code into the master loop
    "async_straggler": {"max_compiles": 4,  "max_transfer_mb": 8},
    # SLO-evaluator tier: burn-rate windows are pure host record math
    # riding a real ServePool churn — the ceiling is the serve-pool
    # tier's; a compile beyond it means the evaluator leaked onto the
    # device path
    "slo_overhead":    {"max_compiles": 32, "max_transfer_mb": 64},
}


#: per-tier budget verdicts (filled by _check_tier_budget, persisted as
#: detail.budgets.verdicts)
BUDGET_VERDICTS = {}


def _runtime_totals():
    from hpbandster_tpu import obs
    from hpbandster_tpu.obs.runtime import get_compile_tracker

    led = get_compile_tracker().snapshot()
    reg = obs.get_metrics()
    return (
        led["total_compiles"],
        led["total_compile_s"],
        int(reg.counter("runtime.transfer_bytes_h2d").value),
        int(reg.counter("runtime.transfer_bytes_d2h").value),
    )


def _check_tier_budget(name, errors):
    """Judge one finished tier against its declared budget; a violation is
    recorded LOUDLY (stderr banner + error entry -> degraded artifact).
    Returns the verdict dict persisted under detail.budgets."""
    budget = TIER_BUDGETS.get(name)
    observed = COMPILE_BY_TIER.get(name)
    if budget is None or observed is None:
        return None
    transfer_mb = (
        (observed.get("h2d_bytes", 0) + observed.get("d2h_bytes", 0)) / 1e6
    )
    verdict = {
        "budget": dict(budget),
        "observed": {
            "compiles": observed["compiles"],
            "transfer_mb": round(transfer_mb, 3),
        },
        "ok": (
            observed["compiles"] <= budget["max_compiles"]
            and transfer_mb <= budget["max_transfer_mb"]
        ),
    }
    BUDGET_VERDICTS[name] = verdict
    if not verdict["ok"]:
        msg = (
            "compile/transfer budget EXCEEDED: %d compiles (budget %d), "
            "%.1f MB transferred (budget %d MB)"
            % (observed["compiles"], budget["max_compiles"], transfer_mb,
               budget["max_transfer_mb"])
        )
        errors["budget:" + name] = msg
        print("bench: tier %r %s" % (name, msg), file=sys.stderr, flush=True)
    return verdict


def _run_tier(errors, name, fn, *args, **kwargs):
    """Run one bench tier; a failure records the error and returns None
    so the remaining tiers still run (``main`` then exits non-zero: a
    tier that raised is a failed bench). Start/finish lines go to stderr so a
    killed-by-timeout run still shows WHICH tier ate the clock. The
    cumulative compile count/seconds and h2d/d2h transfer bytes the
    tier's tracked boundaries paid land in COMPILE_BY_TIER (and, for dict
    results, on the tier payload as ``"compile"``), then the tier is
    judged against TIER_BUDGETS."""
    print("bench: tier %r starting" % name, file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    c0, s0, h0, d0 = _runtime_totals()

    def _land():
        c1, s1, h1, d1 = _runtime_totals()
        COMPILE_BY_TIER[name] = {
            "compiles": c1 - c0, "compile_s": round(s1 - s0, 3),
            "h2d_bytes": h1 - h0, "d2h_bytes": d1 - d0,
        }
        return c1 - c0, s1 - s0

    try:
        out = fn(*args, **kwargs)
        compiles, compile_s = _land()
        print("bench: tier %r done in %.1fs (%d compiles, %.1fs compiling)"
              % (name, time.perf_counter() - t0, compiles, compile_s),
              file=sys.stderr, flush=True)
        _check_tier_budget(name, errors)
        return out
    except Exception as e:  # noqa: BLE001 — recorded; main() exits non-zero
        _land()
        errors[name] = "%s: %s" % (type(e).__name__, str(e)[:300])
        print("bench: tier %r failed after %.1fs: %s"
              % (name, time.perf_counter() - t0, errors[name]),
              file=sys.stderr, flush=True)
        return None


def collect(smoke=False, tiers=None, partial_path=None):
    import jax

    _enable_persistent_compile_cache()
    COMPILE_BY_TIER.clear()  # per-run ledger (tests call collect repeatedly)
    BUDGET_VERDICTS.clear()
    devices = jax.devices()
    n_chips = len(devices)
    errors = {}

    t_start = time.perf_counter()
    if partial_path:
        _append_partial(partial_path, {
            "tier": "_meta", "platform": str(devices[0].platform),
            "chip": str(devices[0].device_kind), "n_chips": n_chips,
            "smoke": smoke,
            "tiers_requested": "all" if tiers is None else sorted(tiers),
        }, truncate=True)

    def emit(name, value):
        """Record a finished tier on disk IMMEDIATELY (atomic append): a
        mid-run death — driver timeout, OOM — keeps every tier that
        completed. Every measured tier dict is also stamped with the
        platform it ACTUALLY ran on, so a stale budget comparison
        against it is self-describing instead of silently mixing chip
        and CPU numbers."""
        if isinstance(value, dict) and "skipped" not in value:
            value.setdefault("platform", str(devices[0].platform))
        if partial_path:
            _append_partial(partial_path, {
                "tier": name,
                "elapsed_total_s": round(time.perf_counter() - t_start, 1),
                "result": value,
                "error": errors.get(name),
                # what the tier paid in tracked-jit compiles (obs/runtime):
                # lets the trajectory separate compile time from
                # steady-state throughput, tier by tier
                "compile": COMPILE_BY_TIER.get(name),
            })
        return value

    selected = (lambda name: True) if tiers is None else tiers.__contains__
    NOT_SELECTED = {"skipped": "not selected (--tiers)"}

    def scaled_summary(rates):
        return _summary([r / n_chips for r in rates]) if rates else None

    repeats = 3 if smoke else RUNS_PER_TIER
    brackets = 4 if smoke else HEADLINE_BRACKETS
    max_budget = 9 if smoke else 81
    if smoke:
        # --smoke: exercise the full collect pipeline (error isolation /
        # JSON contract) in minutes, not the measurement (tiny ladders,
        # training rungs skipped); never citable
        fused_out = _run_tier(errors, "fused", bench_fused, brackets,
                              repeats=repeats, max_budget=max_budget)
        fused = emit("fused", scaled_summary(fused_out[0]) if fused_out
                     else None)
        fused10k = batched = cnn = cnn_wide = resnet = teacher = None
        chunked = chunked10k = transformer = None
        # smoke rung of the mesh-sharded tier: tiny config count, same
        # code path (sharded sampling, balance gauges, incumbent fetch)
        fused_100k = emit("fused_100k", _run_tier(
            errors, "fused_100k", bench_fused_sharded, n_configs=4096,
            repeats=repeats))
        # smoke rung of the resident tier: tiny sizes, same code path
        # (scan-fused schedule, flat-d2h assertion, KDE-fit probe)
        resident_100k = emit("resident_100k", _run_tier(
            errors, "resident_100k", bench_resident_sharded,
            sizes=(1024, 4096), kde_fit_sizes=(1 << 12, 1 << 14)))
        # smoke rung of the real-model tier: same code path (vmapped SGD
        # ensemble, warm continuation, roofline row, flat-bill assert)
        ensemble_smoke = emit("ensemble_smoke", _run_tier(
            errors, "ensemble_smoke", bench_ensemble_smoke,
            repeats=repeats))
        fused_1M = {"skipped": "--smoke: the 1M-config program is not a "
                               "smoke-size measurement"}
        rpc_rates = _run_tier(errors, "rpc", bench_rpc_baseline,
                              repeats=repeats)
        rpc = emit("rpc", _summary(rpc_rates) if rpc_rates else None)
        pallas = emit("pallas", _run_tier(errors, "pallas",
                                          bench_pallas_scorer,
                                          repeats=repeats))
        multitenant = emit("multitenant", _run_tier(
            errors, "multitenant", bench_multitenant,
            n_tenants=4, repeats=repeats))
        serve_continuous = emit("serve_continuous", _run_tier(
            errors, "serve_continuous", bench_serve_continuous,
            n_tenants=4, lane_count=2, repeats=repeats))
        chaos = emit("chaos", _run_tier(
            errors, "chaos", bench_chaos,
            n_workers=2, n_iterations=1, repeats=repeats))
        async_straggler = emit("async_straggler", _run_tier(
            errors, "async_straggler", bench_async_straggler,
            n_workers=2, n_iterations=1, repeats=1))
        obs_overhead = emit("obs_overhead", _run_tier(
            errors, "obs_overhead", bench_obs_overhead, repeats=repeats))
        timeline_overhead = emit("timeline_overhead", _run_tier(
            errors, "timeline_overhead", bench_timeline_overhead,
            repeats=repeats, inner=2, n_micro=20_000, sizes=(256, 512)))
        runtime_overhead = emit("runtime_overhead", _run_tier(
            errors, "runtime_overhead", bench_runtime_overhead,
            inner=5_000))
        collector_overhead = emit("collector_overhead", _run_tier(
            errors, "collector_overhead", bench_collector_overhead,
            rounds=10))
        slo_overhead = emit("slo_overhead", _run_tier(
            errors, "slo_overhead", bench_slo_overhead,
            micro_records=5_000, n_tenants=2))
        report_100k = emit("report_100k", _run_tier(
            errors, "report_100k", bench_report_100k, n_events=5_000))
    else:
        def tier(name, fn, *args, **kwargs):
            if not selected(name):
                return dict(NOT_SELECTED)
            return emit(name, _run_tier(errors, name, fn, *args, **kwargs))

        # execution order is TIER_ORDER: the tiers that have never
        # produced a chip number run FIRST, so a driver timeout mid-run
        # costs the least-missing numbers. Headline assembly below is
        # order-independent.
        cnn = tier("cnn", bench_cnn)
        cnn_wide = tier("cnn_wide", bench_cnn_wide)
        pallas = tier("pallas", bench_pallas_scorer)
        resnet = tier("resnet", bench_resnet)
        transformer = tier("transformer", bench_transformer)
        fused_1M = tier("fused_1M", bench_fused_sharded,
                        n_configs=1 << 20, repeats=repeats)
        fused_100k = tier("fused_100k", bench_fused_sharded,
                          n_configs=1 << 17, repeats=repeats)
        resident_100k = tier("resident_100k", bench_resident_sharded)
        ensemble_smoke = tier("ensemble_smoke", bench_ensemble_smoke)
        if not selected("fused10k"):
            fused10k = dict(NOT_SELECTED)
        else:
            fused10k_out = _run_tier(errors, "fused10k", bench_fused, 36,
                                     repeats=repeats, max_budget=729, seed=50)
            fused10k = scaled_summary(fused10k_out[0]) if fused10k_out else None
            if fused10k is not None:
                fused10k["total_configs_per_run"] = fused10k_out[1]
                fused10k["runs_timing_split"] = fused10k_out[2]
                # the per-repeat compile-vs-run split that ATTRIBUTES
                # this tier's historically-2.2x IQR
                fused10k["iqr_attribution"] = fused10k_out[3]
            emit("fused10k", fused10k)
        sub = (
            (lambda nm, v: _append_partial(partial_path, {
                "tier": "chunked10k.%s" % nm,
                "elapsed_total_s": round(
                    time.perf_counter() - t_start, 1),
                "result": v,
            }))
            if partial_path else None
        )
        chunked10k = tier("chunked10k", bench_chunked_10k, on_subresult=sub)
        chunked = tier("chunked_compile", bench_chunked_compile)
        if selected("fused"):
            fused_out = _run_tier(errors, "fused", bench_fused, brackets,
                                  repeats=repeats, max_budget=max_budget)
            fused = scaled_summary(fused_out[0]) if fused_out else None
            if fused is not None:
                fused["runs_timing_split"] = fused_out[2]
                fused["iqr_attribution"] = fused_out[3]
            emit("fused", fused)
        else:
            fused = dict(NOT_SELECTED)
        if selected("rpc"):
            rpc_rates = _run_tier(errors, "rpc", bench_rpc_baseline,
                                  repeats=repeats)
            rpc = emit("rpc", _summary(rpc_rates) if rpc_rates else None)
        else:
            rpc = dict(NOT_SELECTED)
        if selected("batched"):
            batched_rates = _run_tier(errors, "batched", bench_batched,
                                      repeats=repeats)
            batched = emit("batched", scaled_summary(batched_rates))
        else:
            batched = dict(NOT_SELECTED)
        teacher = tier("teacher", bench_teacher)
        # the serving, elastic-fleet and telemetry tiers below measure
        # host machinery (packing, sockets, record math), not the chip
        multitenant = tier("multitenant", bench_multitenant, repeats=repeats)
        serve_continuous = tier("serve_continuous", bench_serve_continuous,
                                repeats=repeats)
        chaos = tier("chaos", bench_chaos, repeats=repeats)
        async_straggler = tier("async_straggler", bench_async_straggler,
                               repeats=repeats)
        obs_overhead = tier("obs_overhead", bench_obs_overhead)
        timeline_overhead = tier("timeline_overhead",
                                 bench_timeline_overhead)
        runtime_overhead = tier("runtime_overhead", bench_runtime_overhead)
        collector_overhead = tier("collector_overhead",
                                  bench_collector_overhead)
        slo_overhead = tier("slo_overhead", bench_slo_overhead)
        report_100k = tier("report_100k", bench_report_100k)

    def median_of(tier):
        return tier.get("median") if isinstance(tier, dict) else None

    value = median_of(fused)
    rpc_median = median_of(rpc)
    vs_baseline = (
        round(value / rpc_median, 2)
        if value is not None and rpc_median else None
    )
    method = (
        "per-tier medians of paired same-process runs with IQR: "
        "%d runs for rpc/batched/fused/fused10k after a warmup run "
        "(compile excluded); vs_baseline = fused median / "
        "same-machine RPC median; training rungs report analytic "
        "model FLOPs (workloads/flops.py, XLA-cost-analysis-pinned) "
        "over device-execute seconds as achieved FLOP/s and MFU "
        "vs peak bf16; fused-rung FLOPs include crashed configs "
        "(their steps executed on device before masking). The "
        "archiving driver's top-level 'n' is its round counter, "
        "NOT a sample size." % repeats
    )
    result = {
        "metric": "configs evaluated/sec/chip (BOHB, Branin, eta=3, budgets 1..81)",
        "value": value,
        "unit": "configs/s/chip",
        "vs_baseline": vs_baseline,
        "detail": {
            "method": method,
            "runs_per_tier": repeats,
            "chip": str(devices[0].device_kind),
            "platform": str(devices[0].platform),
            "n_chips": n_chips,
            "tiers": {
                "rpc_pool_1worker": rpc,
                "batched_parallel_brackets3": batched,
                "fused_27_brackets": fused,
                "fused_10k_scale_36_brackets_1_729": fused10k,
            },
            "fused_1M_mesh_sharded": fused_1M,
            "fused_100k_mesh_sharded": fused_100k,
            "resident_100k_scan_fused": resident_100k,
            "ensemble_smoke_real_model": ensemble_smoke,
            "cnn_workload_budget_sgd_steps": cnn,
            "cnn_wide_mxu_saturation": cnn_wide,
            "resnet_workload_budget_sgd_steps": resnet,
            "transformer_workload_budget_sgd_steps": transformer,
            "teacher_workload_budget_epochs": teacher,
            "pallas_scorer_vs_xla": pallas,
            "chunked_compile_static_vs_dynamic": chunked,
            "chunked10k_at_scale_36_brackets_1_729": chunked10k,
            "multitenant_serving_16_tenants": multitenant,
            "serve_continuous_batching": serve_continuous,
            "chaos_churn_10pct": chaos,
            "async_straggler_promotion": async_straggler,
            "obs_overhead_no_sink": obs_overhead,
            "timeline_overhead_recorder": timeline_overhead,
            "runtime_overhead_tracked_jit": runtime_overhead,
            "collector_overhead_fleet_poll": collector_overhead,
            "slo_overhead_burn_alerting": slo_overhead,
            "report_100k_events": report_100k,
            "compile_by_tier": dict(sorted(COMPILE_BY_TIER.items())),
            # the budget gate's record: what each tier declared vs paid.
            # A failed verdict ALSO lands as error["budget:<tier>"], so
            # the artifact is degraded, not silently annotated.
            "budgets": {
                "declared": {
                    k: dict(v) for k, v in sorted(TIER_BUDGETS.items())
                },
                "verdicts": dict(sorted(BUDGET_VERDICTS.items())),
            },
        },
    }
    if smoke:
        result["smoke"] = True
        result["metric"] = (
            "configs evaluated/sec/chip (SMOKE: 4 brackets, budgets 1..9)"
        )
    if errors:
        result["error"] = errors
    return result


#: hard cap on the final printed line — the archiving driver captures a
#: 2000-char tail and parses its last line; an overrun lands
#: ``parsed: null`` despite a healthy run
COMPACT_LINE_MAX = 1900


def _short_error(errors, per_item=120, total=500):
    """Flatten collect()'s error dict into one bounded string for the
    compact line; the unabridged dict lives in the detail file."""
    if not isinstance(errors, dict):
        return str(errors)[:total]
    s = "; ".join("%s: %s" % (k, str(v)[:per_item])
                  for k, v in sorted(errors.items()))
    return s[:total]


def compact_line(result, detail_file):
    """The driver-facing summary: every headline field, a pointer to the
    full detail, and NOTHING unbounded. Guaranteed to fit the driver's
    tail capture whatever the run did (pinned in tests/test_bench.py)."""
    d = result.get("detail") or {}
    out = {
        "metric": result.get("metric"),
        "value": result.get("value"),
        "unit": result.get("unit"),
        "vs_baseline": result.get("vs_baseline"),
        "platform": d.get("platform"),
        "chip": d.get("chip"),
        "n_chips": d.get("n_chips"),
    }
    if detail_file:
        # only advertised when THIS run's detail actually landed on disk —
        # a pointer to a stale file from a previous run would let a
        # reader cite the wrong run's numbers
        out["detail_file"] = detail_file
    tiers = dict(d.get("tiers") or {})
    for k in ("cnn_workload_budget_sgd_steps", "cnn_wide_mxu_saturation",
              "resnet_workload_budget_sgd_steps",
              "transformer_workload_budget_sgd_steps",
              "teacher_workload_budget_epochs", "pallas_scorer_vs_xla",
              "chunked_compile_static_vs_dynamic",
              "chunked10k_at_scale_36_brackets_1_729",
              "obs_overhead_no_sink", "runtime_overhead_tracked_jit",
              "collector_overhead_fleet_poll"):
        tiers[k] = d.get(k)
    out["tiers_measured"] = sorted(
        k for k, v in tiers.items()
        if isinstance(v, dict) and "skipped" not in v
    )
    if result.get("smoke"):
        out["smoke"] = True
    if result.get("error"):
        out["error"] = _short_error(result["error"])
    line = json.dumps(out)
    if len(line) > COMPACT_LINE_MAX:  # belt over suspenders: drop verbose
        out["tiers_measured"] = len(out["tiers_measured"])  # fields first
        if "error" in out:
            out["error"] = _short_error(result.get("error"), 40, 150)
        line = json.dumps(out)
    # never byte-truncate (a sliced JSON string would land parsed: null —
    # the exact failure this function exists to prevent): drop whole
    # fields until a valid object fits. Detail-ish fields (the usual
    # overflow culprits, e.g. a long detail_file path) go FIRST; the
    # honesty labels (metric's SMOKE banner, error, smoke) go last, so a
    # degraded run cannot shed its degraded-ness before its pointer fields
    for k in ("detail_file", "chip", "n_chips", "platform",
              "tiers_measured", "metric", "error", "smoke"):
        if len(line) <= COMPACT_LINE_MAX:
            break
        out.pop(k, None)
        line = json.dumps(out)
    return line


def _write_detail(result, path):
    """Full result dict to disk, atomically (tmp + rename): the committed
    detail artifact is the citable record; the printed line only points
    at it."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _parse_args(argv=None):
    # allow_abbrev=False: with abbreviation on, an ambiguous prefix
    # SystemExits inside argparse BEFORE the final JSON line can print —
    # the parse_known_args contract below requires unknown-ish flags to
    # land in the ignored-leftovers path instead
    ap = argparse.ArgumentParser(
        description="hpbandster_tpu benchmark (see module docstring)",
        allow_abbrev=False)
    ap.add_argument("--smoke", action="store_true",
                    help="minutes-scale pipeline exercise; never citable")
    ap.add_argument("--tiers", metavar="A,B,...",
                    help="run only these tiers (in this fixed order): "
                         + ",".join(TIER_ORDER))
    ap.add_argument("--detail-out", default="BENCH_DETAIL.json",
                    help="full result dict destination (default: "
                         "%(default)s)")
    ap.add_argument("--partial-out", default="BENCH_PARTIAL.jsonl",
                    help="per-tier incremental JSONL (default: %(default)s; "
                         "'' disables)")
    # parse_known_args, not parse_args: an unknown flag must not be able
    # to kill the run before the final JSON line prints — the old
    # `in sys.argv` scanning ignored strangers, and the archiving driver's
    # invocation must never land parsed: null over a flag typo
    args, unknown_argv = ap.parse_known_args(argv)
    if unknown_argv:
        print("bench: ignoring unrecognized arguments: %s"
              % " ".join(unknown_argv), file=sys.stderr)
    if args.tiers is not None:
        names = {t.strip() for t in args.tiers.split(",") if t.strip()}
        unknown = names - set(TIER_ORDER)
        if unknown:
            ap.error("unknown tiers %s; valid: %s"
                     % (sorted(unknown), ",".join(TIER_ORDER)))
        if not names:
            ap.error("--tiers got no tier names; valid: %s"
                     % ",".join(TIER_ORDER))
        args.tiers = names
    if args.smoke and args.tiers is not None:
        # --smoke exercises the fixed pipeline; honoring a subset there
        # would silently change what the smoke run certifies
        print("bench: --tiers is ignored under --smoke (smoke runs its "
              "fixed tier set)", file=sys.stderr)
        args.tiers = None
    return args


def main(argv=None):
    args = _parse_args(argv)
    _require_backend()
    result = collect(
        smoke=args.smoke, tiers=args.tiers,
        partial_path=args.partial_out or None,
    )
    detail_file = args.detail_out
    try:
        _write_detail(result, args.detail_out)
    except OSError as e:
        print("bench: detail write to %s failed: %s" % (args.detail_out, e),
              file=sys.stderr)
        detail_file = None  # never point at a stale previous run's file
    # the LAST printed line is the compact driver-facing summary — the
    # full dict is in the detail file, never on stdout
    print(compact_line(result, detail_file))
    if result.get("error"):
        # a tier raised (or blew its compile/transfer budget): the
        # finished tiers' numbers are on disk, but the bench FAILED
        sys.exit(1)


if __name__ == "__main__":
    main()
