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
from typing import Any, Dict, List, Optional

from hpbandster_tpu.parallel.batched_executor import BatchedExecutor

logger = logging.getLogger("hpbandster_tpu.multihost")

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
    enabled (``tests/test_program_counts.py``
    ``test_resident_telemetry_rides_the_flat_link`` counts exactly that).
    The decoded record is published as gauges, journaled as
    ``device_telemetry``, and returned under ``"device_telemetry"``.

    ``stateful_eval`` (exclusive with ``eval_fn``, pass ``eval_fn=None``)
    runs the sweep over a warm-continuation ensemble
    (``ops.fused.StatefulEval`` — e.g. ``workloads.ensemble``): every
    rung trains live models in-trace and promotions carry their weights.
    The ensemble state is bracket-local device scratch, so the flat
    host-link bill above is untouched. ``program_name`` labels the
    compiled program in the obs ledger (roofline attribution).

    Every chunk's program is compiled ahead of time through the one
    driver and executable cache ``FusedBOHB`` uses (``ops/sweep_driver.py``).
    Returns a stats dict (incumbent, per-device balance, ``chunks`` — each
    with its ``build_compile_s``, 0.0 on a ``compile_cache_hit`` —,
    ``last_executable``, and ``phase_s``: the call's seconds by span name,
    the ``obs.timeline.sweep_span`` names ``FusedBOHB.run`` uses for the
    phases this entry point has).
    SPMD multi-host: call on every rank with identical arguments over a
    pod-spanning mesh; the returned incumbent is identical on all ranks.
    """
    from hpbandster_tpu.obs.timeline import ADMISSION, PROMOTION, sweep_span

    phase_s: Dict[str, float] = {}
    with sweep_span("run", ADMISSION, phase_s):
        import numpy as np

        from hpbandster_tpu.ops.bracket import mesh_aligned_plan
        from hpbandster_tpu.ops.sweep import build_space_codec
        from hpbandster_tpu.ops.sweep_driver import SweepDriver
        from hpbandster_tpu.parallel.mesh import config_mesh, shard_count

        with sweep_span("sweep_planning", ADMISSION, phase_s):
            if mesh is None:
                mesh = config_mesh()
            n_shards = shard_count(mesh, axis)
            plan = mesh_aligned_plan(n_configs, min_budget, max_budget, eta, n_shards)
            plans = [plan] * max(int(n_brackets), 1)
            evaluations = int(sum(sum(p.num_configs) for p in plans))
            codec = build_space_codec(configspace)
            rng = np.random.default_rng(seed)

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
            driver = SweepDriver(
                eval_fn, codec,
                dict(
                    stateful_eval=stateful_eval,
                    num_samples=num_samples,
                    mesh=mesh,
                    axis=axis,
                    shard_sampling=True,
                    # HyperBand mode: an unreachable gate keeps the KDE out of the
                    # trace entirely (any_trainable=False) — pure sample/eval/promote
                    min_points_in_model=None if model else 2**30,
                    program_name=program_name,
                ),
                dynamic=dynamic,
                resident=resident,
                incumbent_only=True,
                # resident runs the whole schedule in one dispatch: there is
                # no next chunk to thread state into
                thread_state=dynamic and not resident,
                device_metrics=device_metrics,
                # a cold resident sweep's whole upload is the 4-byte seed; a
                # chunked one streams its empty buffers per shard slice
                cold="seed" if resident else "stream_each",
            )
            chunks: List[Dict[str, Any]] = []
            best: Optional[Dict[str, Any]] = None
            per_bracket_all: List[float] = []
            remaining = list(plans)
            bracket_base = 0
        while remaining:
            chunk_plans, remaining = remaining[:chunk], remaining[chunk:]
            seed_val = np.uint32(rng.integers(2**32, dtype=np.uint32))
            # one capacity map for the WHOLE schedule: nothing of this
            # sweep's observations ever reaches the host
            inc, stat = driver.run_chunk(
                chunk_plans, seed_val, phase_s, first_bracket=bracket_base,
                sized_by=plans,
            )
            with sweep_span("chunk_accounting", PROMOTION, phase_s):
                driver.journal(stat, len(chunks), phase_s)
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
                # warm_upload_bytes: 4 bytes (the seed) once the state
                # threads device-to-device
                chunks.append(dict(stat, brackets=len(chunk_plans)))
                bracket_base += len(chunk_plans)

        with sweep_span("result", PROMOTION, phase_s):
            # geometry-derived balance: every stage splits its (mesh-aligned) rows
            # evenly, so shard s owns sum(widths)/S rows per bracket. Every row is
            # a REAL sampled config (the sweep path samples the full aligned
            # width — alignment surplus rows are extra exploration, not dead
            # padding), so pad_rows is 0 here and the surplus over the pure
            # eta-decay ladder is reported separately, uncounted in configs.
            pure = [max(int(n_configs * float(eta) ** (-j)), 1)
                    for j in range(len(plan.num_configs))]
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
            link, decoded_dm = driver.finish()
            host_syncs = link["transfers_h2d"] + link["transfers_d2h"]
            from hpbandster_tpu.obs.audit import emit_sweep_incumbent

            emit_sweep_incumbent(
                **best,  # vector, loss, bracket
                per_bracket_loss=per_bracket_all,
                evaluations=evaluations,
                n_configs=int(n_configs),
                d2h_bytes=link["transfer_bytes_d2h"],
                h2d_bytes=link["transfer_bytes_h2d"],
                host_syncs=host_syncs,
            )

        return {
            "incumbent": best,
            "evaluations": evaluations,
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
            # the AOT-compiled program of the last chunk (``.as_text()``,
            # ``.cost_analysis()``): FusedBOHB.last_executable's twin
            "last_executable": driver.last_executable,
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
