"""Multi-host (DCN-tier) support.

SURVEY.md §2 "Distributed communication backend" prescribes two tiers for
the rebuild: the ICI tier (sharded batched evaluation inside one jit — see
``backends.py``) and a DCN tier for multi-host pods. This module wires the
DCN tier the JAX-native way:

* :func:`initialize_multihost` — ``jax.distributed.initialize`` bootstrap;
  after it, ``jax.devices()`` spans the pod and a ``Mesh`` built from them
  makes the same ``VmapBackend`` code scale across hosts (XLA routes
  collectives over ICI within a slice and DCN between slices).
* :class:`MultiHostBatchedExecutor` — SPMD driver pattern: every host runs
  the same Master loop deterministically (same seeds), each jitted wave is
  a global computation over the pod-wide mesh, and only process 0 talks to
  result loggers — so there is no extra coordination protocol beyond XLA's.

The *elastic* worker pool (dynamic join/leave) intentionally stays on the
host RPC tier (``dispatcher.py``): JAX's SPMD model requires static mesh
membership per run (SURVEY.md §7 "Multi-host elasticity").
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional

from hpbandster_tpu.parallel.batched_executor import BatchedExecutor

logger = logging.getLogger("hpbandster_tpu.multihost")

#: process-wide sharded-sweep fn cache — one traced program per
#: (objective, chunk schedule, space, mesh, knobs), same policy as
#: ops.fused._FUSED_FN_CACHE / FusedBOHB._SWEEP_EXE_CACHE
from hpbandster_tpu.utils.lru import LRUCache as _LRUCache

_SHARDED_FN_CACHE: _LRUCache = _LRUCache(maxsize=16)

__all__ = [
    "initialize_multihost",
    "MultiHostBatchedExecutor",
    "is_primary_host",
    "run_sharded_fused_sweep",
    "publish_device_balance",
]


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> int:
    """Join the pod; returns this process's id. Safe to call when already
    initialized or in single-process mode (returns 0)."""
    import jax

    if num_processes is None or num_processes <= 1:
        return 0
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:  # already initialized
        logger.debug("jax.distributed.initialize: %s", e)
    return jax.process_index()


def is_primary_host() -> bool:
    import jax

    return jax.process_index() == 0


class MultiHostBatchedExecutor(BatchedExecutor):
    """BatchedExecutor for SPMD multi-host runs.

    Every host must construct the identical optimizer (same seeds/settings)
    and call ``run()`` — the Master's control flow is deterministic, so all
    hosts issue the same global computations in the same order. Side effects
    (result logging, checkpointing) fire only on process 0.
    """

    def __init__(self, backend, configspace, **kwargs):
        super().__init__(backend, configspace, **kwargs)
        import jax

        #: use this to gate side effects (result_logger, checkpoints):
        #: pass them to the Master only when primary is True
        self.primary = jax.process_index() == 0

    def run_sharded_sweep(self, n_configs: int, **kwargs) -> Dict[str, Any]:
        """Run one mesh-sharded fused sweep over the WHOLE pod.

        Every host calls this with identical arguments (the SPMD driver
        contract above); the sweep is a single global computation over a
        pod-wide 'config' mesh — losses reduce over ICI within a slice and
        DCN between slices, and only the final incumbent (a ``d``-vector +
        scalar loss, replicated to every rank) leaves the device loop.
        Per-device balance gauges are published for this process's local
        devices only; a fleet collector aggregates the rest.
        """
        eval_fn = kwargs.pop("eval_fn", None) or self.backend.eval_fn
        return run_sharded_fused_sweep(
            eval_fn, self.configspace, n_configs=n_configs, **kwargs
        )


def publish_device_balance(
    mesh,
    axis: str,
    per_shard_configs: List[int],
    per_shard_pad: List[int],
) -> Optional[float]:
    """Publish per-device config counts + compute-balance gauges.

    ``per_shard_configs[s]`` is the number of TRUE config rows shard ``s``
    evaluated this sweep; ``per_shard_pad[s]`` its padding rows (evaluated
    but never reported). Gauges land as ``sweep.device.<id>.configs`` /
    ``.pad_rows`` for this process's LOCAL devices (each pod rank owns its
    own), the Prometheus renderer re-expresses them as the
    ``sweep_device_*{device=}`` label family, and the fleet collector
    derives ``fleet.device_compute_skew`` — the compute-balance sibling of
    ``fleet.device_mem_skew``. On an SPMD mesh all devices step in
    lockstep, so the per-device row count IS the step-time balance: a
    nonzero skew means some device spends its steps on padding or an
    uneven shard. Returns the mesh-wide shard skew ((max-min)/max over
    ``per_shard_configs`` — identical on every rank, which is why every
    rank may publish the same ``sweep.balance_skew`` gauge; None if
    unmeasurable).
    """
    import jax

    from hpbandster_tpu.obs.metrics import get_metrics
    from hpbandster_tpu.parallel.mesh import shard_count

    n_shards = shard_count(mesh, axis)
    if len(per_shard_configs) != n_shards:
        raise ValueError(
            f"{len(per_shard_configs)} shard counts for a {n_shards}-shard "
            f"'{axis}' axis"
        )
    reg = get_metrics()
    # devices along the sharded axis, in axis order: shard s's rows live on
    # mesh.devices[... s ...] (a 1-D config mesh is the common case; on a
    # 2-D mesh each shard's rows replicate over the other axes, so every
    # device in the slice reports the shard's count)
    try:
        axis_index = list(mesh.axis_names).index(axis)
    except ValueError:
        return None
    import numpy as np

    devices = np.moveaxis(np.asarray(mesh.devices), axis_index, 0)
    devices = devices.reshape(n_shards, -1)
    proc = jax.process_index()
    for s in range(n_shards):
        for dev in devices[s]:
            if dev.process_index != proc:
                continue
            reg.gauge(f"sweep.device.{dev.id}.configs").set(
                float(per_shard_configs[s])
            )
            reg.gauge(f"sweep.device.{dev.id}.pad_rows").set(
                float(per_shard_pad[s])
            )
    hi = max(per_shard_configs) if per_shard_configs else 0
    skew = None if hi <= 0 else (hi - min(per_shard_configs)) / hi
    if skew is not None:
        reg.gauge("sweep.balance_skew").set(round(float(skew), 6))
    return skew


def run_sharded_fused_sweep(
    eval_fn,
    configspace,
    *,
    n_configs: int,
    n_brackets: int = 1,
    min_budget: float = 1.0,
    max_budget: float = 9.0,
    eta: float = 3.0,
    seed: int = 0,
    mesh=None,
    axis: str = "config",
    model: bool = False,
    num_samples: int = 64,
    chunk_brackets: Optional[int] = None,
    publish_gauges: bool = True,
    resident: bool = False,
    device_metrics: Optional[bool] = None,
    stateful_eval=None,
    program_name: Optional[str] = None,
) -> Dict[str, Any]:
    """Mesh-sharded fused successive halving at 100k-1M config scale.

    One deep bracket of ``n_configs`` (stage counts mesh-aligned,
    :func:`~hpbandster_tpu.ops.bracket.mesh_aligned_plan`) repeated
    ``n_brackets`` times, compiled as ONE sharded device program per chunk
    shape: per-shard on-device sampling (no candidate bytes cross the host
    link), per-stage sharding constraints over ``axis`` (rung promotions
    reduce across shards over ICI/DCN), and an ``incumbent_only`` payload —
    the winning vector + loss is the only thing fetched. ``model=True``
    turns the BOHB KDE on (observation buffers then shard over the config
    axis and, with ``chunk_brackets``, thread device-to-device between
    chunks under the PR-6 donation contract); the default is
    HyperBand-style random proposals, the honest mode at 1M configs where
    a KDE fit over the full observation set would dominate.

    ``resident=True`` fuses the whole multi-bracket OUTER loop in-trace
    (``ops/sweep.py`` ``resident=True``): the repeated bracket is traced
    once and a ``lax.scan`` drives all ``n_brackets`` rounds on device,
    so the sweep is ONE dispatch + ONE incumbent fetch however many
    brackets run — where the chunked path surfaces to host once per
    chunk. The per-sweep transfer gauges
    (``sweep.transfer_bytes.{h2d,d2h}`` / ``sweep.host_syncs``) are
    published and returned, and the incumbent payload is journaled as a
    ``sweep_incumbent`` audit record (``obs replay`` re-scores it) —
    the flat-d2h claim is measured, not asserted. Replaces
    ``chunk_brackets`` (passing both is an error).

    ``device_metrics`` (default: ``HPB_DEVICE_METRICS``) turns the
    in-trace metrics plane on (``ops/sweep.py`` ``device_metrics=True``):
    per-rung loss histograms and crash/promotion counts accumulate on
    device and ride the incumbent's d2h — an O(schedule) constant, so
    the flat-host-link bill stays flat in config count WITH telemetry
    enabled (the ``resident_100k`` bench tier measures exactly that).
    The decoded record is published as gauges, journaled as
    ``device_telemetry``, and returned under ``"device_telemetry"``.

    ``stateful_eval`` (exclusive with ``eval_fn``, pass ``eval_fn=None``)
    runs the sweep over a warm-continuation ensemble
    (``ops.fused.StatefulEval`` — e.g. ``workloads.ensemble``): every
    rung trains live models in-trace and promotions carry their weights.
    The ensemble state is bracket-local device scratch, so the flat
    host-link bill above is untouched. ``program_name`` labels the
    compiled program in the obs ledger (roofline attribution).

    Returns a stats dict (incumbent, per-device balance, chunk timings,
    and ``phase_s``: the call's seconds by span name, the
    ``obs.timeline.sweep_span`` names ``FusedBOHB.run`` uses for the
    phases this entry point has).
    SPMD multi-host: call on every rank with identical arguments over a
    pod-spanning mesh; the returned incumbent is identical on all ranks.
    """
    from hpbandster_tpu.obs.timeline import (
        ADMISSION,
        COMPILE,
        PROMOTION,
        RUNG_COMPUTE,
        TRANSFER,
        sweep_span,
    )

    phase_s: Dict[str, float] = {}
    with sweep_span("run", ADMISSION, phase_s):
        import jax
        import numpy as np

        from hpbandster_tpu.obs.runtime import note_transfer
        from hpbandster_tpu.ops.bracket import mesh_aligned_plan
        from hpbandster_tpu.ops.sweep import (
            build_space_codec,
            make_fused_sweep_fn,
            plan_additions,
            pow2_capacities,
        )
        from hpbandster_tpu.parallel.mesh import (
            batch_sharding,
            config_mesh,
            shard_count,
        )

        with sweep_span("sweep_planning", ADMISSION, phase_s):
            if mesh is None:
                mesh = config_mesh()
            n_shards = shard_count(mesh, axis)
            plan = mesh_aligned_plan(n_configs, min_budget, max_budget, eta, n_shards)
            plans = [plan] * max(int(n_brackets), 1)
            codec = build_space_codec(configspace)
            d = int(codec.kind.shape[0])
            rng = np.random.default_rng(seed)
            codec_sig = codec.signature

        with sweep_span("sweep_setup", ADMISSION, phase_s):
            if resident and chunk_brackets is not None:
                raise ValueError(
                    "resident=True replaces chunking (one scanned program for the "
                    "whole schedule) — drop chunk_brackets"
                )
            chunk = (
                len(plans)
                if (chunk_brackets is None or resident)
                else max(int(chunk_brackets), 1)
            )
            dynamic = resident or chunk_brackets is not None
            from hpbandster_tpu.obs.device_metrics import device_metrics_default

            use_dm = (
                device_metrics_default()
                if device_metrics is None else bool(device_metrics)
            )
            sweep_kwargs: Dict[str, Any] = dict(
                num_samples=num_samples,
                mesh=mesh,
                axis=axis,
                shard_sampling=True,
                incumbent_only=True,
                # HyperBand mode: an unreachable gate keeps the KDE out of the
                # trace entirely (any_trainable=False) — pure sample/eval/promote
                min_points_in_model=None if model else 2**30,
            )
            caps = None
            if dynamic:
                # one capacity map for the WHOLE schedule (pow2, floor 256): every
                # chunk shares buffer shapes, so the run is one executable and the
                # threaded state never re-uploads (ops/sweep.py return_state)
                caps = pow2_capacities(plan_additions(plans))

            def _empty_state_args():
                """Zero-observation warm buffers, built PER SHARD SLICE via
                ``make_array_from_callback`` — no host allocation ever holds a
                full capacity buffer (the bounded-RSS contract the bench tier's
                RSS probe checks). Returns ``(warm_v, warm_l, warm_n,
                host_bytes)`` — the bytes the host link actually carries, so the
                transfer ledger measures the warm upload instead of asserting it
                (same accounting as ``FusedBOHB._stream_warm_args``)."""
                from jax.sharding import NamedSharding, PartitionSpec

                shard = batch_sharding(mesh, axis)
                rep = NamedSharding(mesh, PartitionSpec())
                warm_v, warm_l, warm_n = {}, {}, {}
                host_bytes = 0
                for b, cap in caps.items():
                    sh = shard if cap % n_shards == 0 else rep
                    warm_v[b] = jax.make_array_from_callback(
                        (cap, d), sh,
                        lambda idx, cap=cap: np.zeros(
                            _slice_shape(idx, (cap, d)), np.float32
                        ),
                    )
                    warm_l[b] = jax.make_array_from_callback(
                        (cap,), sh,
                        lambda idx, cap=cap: np.full(
                            _slice_shape(idx, (cap,)), np.inf, np.float32
                        ),
                    )
                    warm_n[b] = np.int32(0)
                    host_bytes += cap * d * 4 + cap * 4 + 4
                return warm_v, warm_l, warm_n, host_bytes

            from hpbandster_tpu.obs.runtime import (
                publish_sweep_transfers,
                transfer_counters,
            )

            link0 = transfer_counters()
            fns: Dict[int, Any] = {}
            chunks: List[Dict[str, Any]] = []
            best: Optional[Dict[str, Any]] = None
            per_bracket_all: List[float] = []
            dm_parts: List[Any] = []
            dm_execute_s = 0.0
            state = None
            remaining = list(plans)
            bracket_base = 0
        while remaining:
            chunk_plans, remaining = remaining[:chunk], remaining[chunk:]
            # the jitted sweep, from the process-wide cache or built; this
            # entry point jits on first call, so a first dispatch also
            # traces and compiles
            with sweep_span("compile_lookup", COMPILE, phase_s):
                if len(chunk_plans) not in fns:
                    # process-wide reuse (same policy as the other fused tiers):
                    # bench repeats of the same (objective, schedule, mesh, knobs)
                    # must not retrace/recompile — the compile-count acceptance
                    # (<= one program per chunk shape) is per PROCESS, not per call
                    from hpbandster_tpu.ops.kde import _pallas_fit_requested

                    cache_key = (
                        # exactly one is non-None; the pair keys stateless and
                        # stateful (warm-continuation) executables apart
                        (eval_fn, stateful_eval),
                        tuple((p.num_configs, p.budgets) for p in chunk_plans),
                        codec_sig, mesh, axis, bool(model), int(num_samples),
                        dynamic, bool(resident),
                        None if caps is None else tuple(sorted(caps.items())),
                        # trace-time flag (ops/kde.py): an env flip must miss
                        # the cache, not serve the other fit path's executable
                        _pallas_fit_requested(),
                        # telemetry adds outputs to the traced program — the
                        # metrics-on executable must never serve a metrics-off
                        # call (or vice versa)
                        use_dm,
                        # the ledger label is part of what the caller asked for:
                        # a relabeled request must not serve a fn tracked under
                        # the old name (roofline attribution would lie)
                        program_name,
                    )
                    cached = _SHARDED_FN_CACHE.get(cache_key)
                    if cached is None:
                        cached = make_fused_sweep_fn(
                            eval_fn, chunk_plans, codec,
                            dynamic_counts=dynamic,
                            capacities=caps,
                            # resident runs the whole schedule in one dispatch:
                            # there is no next chunk to thread state into
                            return_state=dynamic and not resident,
                            resident=resident,
                            device_metrics=use_dm,
                            stateful_eval=stateful_eval,
                            program_name=program_name,
                            **sweep_kwargs,
                        )
                        _SHARDED_FN_CACHE[cache_key] = cached
                    fns[len(chunk_plans)] = cached
                fn = fns[len(chunk_plans)]
            with sweep_span("chunk_staging", TRANSFER, phase_s):
                seed_val = np.uint32(rng.integers(2**32, dtype=np.uint32))
                upload_bytes = int(seed_val.nbytes)
                if dynamic:
                    if state is not None:
                        # device-resident thread: nothing but the seed goes up
                        args = (seed_val,) + state
                    elif resident:
                        # cold resident sweep: with no warm inputs the dynamic
                        # init zeroes the observation buffers IN-TRACE
                        # (ops/sweep.py init_obs_state's absent-budget branch),
                        # so the whole upload is the 4-byte seed — h2d is flat
                        # in config count, like the incumbent-only d2h
                        args = (seed_val,)
                    else:
                        warm_v, warm_l, warm_n, host_bytes = _empty_state_args()
                        args = (seed_val, warm_v, warm_l, warm_n)
                        upload_bytes += host_bytes
                else:
                    args = (seed_val,)
                note_transfer("h2d", upload_bytes)
            t0 = time.perf_counter()
            with sweep_span("dispatch", TRANSFER, phase_s):
                out = fn(*args)
            dm_dev = None
            if dynamic and not resident:
                if use_dm:
                    inc, dm_dev, state = out
                else:
                    inc, state = out
            elif use_dm:
                inc, dm_dev = out
            else:
                inc = out
            with sweep_span("fetch", RUNG_COMPUTE, phase_s):
                inc = jax.device_get(inc)
                dm_host = jax.device_get(dm_dev) if dm_dev is not None else None
            execute_s = time.perf_counter() - t0
            with sweep_span("chunk_accounting", PROMOTION, phase_s):
                dm_leaves = (
                    list(jax.tree_util.tree_leaves(dm_host))
                    if dm_host is not None else []
                )
                if dm_host is not None:
                    dm_parts.append((
                        dm_host,
                        [(p.num_configs, p.budgets) for p in chunk_plans],
                    ))
                    dm_execute_s += execute_s
                note_transfer(
                    "d2h",
                    sum(int(np.asarray(l).nbytes) for l in inc)
                    + sum(int(np.asarray(l).nbytes) for l in dm_leaves),
                    buffers=len(inc) + len(dm_leaves),
                )
                loss = float(np.asarray(inc.loss))
                cand = {
                    "vector": np.asarray(inc.vector, np.float32).tolist(),
                    "loss": loss,
                    "bracket": bracket_base + int(np.asarray(inc.bracket)),
                }
                per_bracket_all.extend(
                    float(x) for x in np.asarray(inc.per_bracket_loss)
                )
                # NaN = every candidate crashed; never beats a real incumbent
                if best is None or (
                    not np.isnan(loss) and (
                        best["loss"] is None or np.isnan(best["loss"])
                        or loss < best["loss"]
                    )
                ):
                    best = cand
                chunks.append({
                    "brackets": len(chunk_plans),
                    "execute_fetch_s": round(execute_s, 4),
                    # 4 bytes (the seed) once the state threads device-to-device
                    "warm_upload_bytes": upload_bytes,
                })
                bracket_base += len(chunk_plans)

        with sweep_span("result", PROMOTION, phase_s):
            # geometry-derived balance: every stage splits its (mesh-aligned) rows
            # evenly, so shard s owns sum(widths)/S rows per bracket. Every row is
            # a REAL sampled config (the sweep path samples the full aligned
            # width — alignment surplus rows are extra exploration, not dead
            # padding), so pad_rows is 0 here and the surplus over the pure
            # eta-decay ladder is reported separately, uncounted in configs.
            pure = []
            for j in range(len(plan.num_configs)):
                pure.append(max(int(n_configs * float(eta) ** (-j)), 1))
            per_shard_rows = sum(plan.num_configs) // n_shards * len(plans)
            surplus_total = (sum(plan.num_configs) - sum(pure)) * len(plans)
            per_shard_configs = [per_shard_rows] * n_shards
            skew = None
            if publish_gauges:
                skew = publish_device_balance(
                    mesh, axis, per_shard_configs, [0] * n_shards
                )

            # per-sweep host-link bill: gauges for the scraper, deltas in the
            # stats dict, and — since the incumbent is this sweep's ONLY decision
            # payload — a sweep_incumbent audit record the replay harness can
            # re-score (per-rung decisions never left the device)
            link = publish_sweep_transfers(link0)
            host_syncs = link["transfers_h2d"] + link["transfers_d2h"]
            decoded_dm = None
            if dm_parts:
                # the metrics plane's host half: one decoded record per sweep —
                # gauges for the scraper, a device_telemetry journal record for
                # summarize/report and the anomaly rules (every rank publishes
                # its own copy, like the incumbent record: SPMD values are
                # identical on all ranks)
                from hpbandster_tpu.obs.device_metrics import (
                    decode_device_metrics,
                    emit_device_telemetry,
                    publish_device_metrics,
                )

                decoded_dm = decode_device_metrics(
                    dm_parts, execute_s=dm_execute_s
                )
                publish_device_metrics(decoded_dm)
                emit_device_telemetry(decoded_dm)
            if best is not None:
                from hpbandster_tpu.obs.audit import emit_sweep_incumbent

                emit_sweep_incumbent(
                    vector=best["vector"],
                    loss=best["loss"],
                    bracket=best["bracket"],
                    per_bracket_loss=per_bracket_all,
                    evaluations=int(sum(sum(p.num_configs) for p in plans)),
                    n_configs=int(n_configs),
                    d2h_bytes=link["transfer_bytes_d2h"],
                    h2d_bytes=link["transfer_bytes_h2d"],
                    host_syncs=host_syncs,
                )

        return {
            "incumbent": best,
            "evaluations": int(sum(sum(p.num_configs) for p in plans)),
            "requested_configs": int(n_configs),
            "aligned_stage_counts": list(plan.num_configs),
            "budgets": list(plan.budgets),
            "n_brackets": len(plans),
            "n_devices": int(np.asarray(mesh.devices).size),
            "n_shards": n_shards,
            "per_device_configs": per_shard_configs,
            # rows evaluated beyond the pure eta ladder due to mesh alignment
            # (whole schedule, all shards) — already included in
            # per_device_configs/evaluations, never add them together
            "alignment_surplus_rows": int(surplus_total),
            "balance_skew": 0.0 if skew is None else round(float(skew), 6),
            "chunks": chunks,
            "execute_fetch_s": round(
                sum(c["execute_fetch_s"] for c in chunks), 4
            ),
            "resident": bool(resident),
            "device_telemetry": decoded_dm,
            "per_bracket_loss": per_bracket_all,
            # measured host-link bill for THIS sweep (note_transfer deltas):
            # the resident tier's flat-d2h / constant-host-sync evidence
            "h2d_bytes": int(link["transfer_bytes_h2d"]),
            "d2h_bytes": int(link["transfer_bytes_d2h"]),
            "host_syncs": int(host_syncs),
            # the call's seconds by span name (FusedBOHB.run's names)
            "phase_s": phase_s,
        }


def _slice_shape(idx, shape) -> tuple:
    """Concrete shape of the shard slice ``make_array_from_callback``
    asks for — the per-shard allocation unit of the streamed uploads."""
    out = []
    for sl, n in zip(idx, shape):
        start, stop, _ = sl.indices(n)
        out.append(stop - start)
    return tuple(out)
