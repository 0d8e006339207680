"""Continuous-batching mesh serving: resident lane programs tenants join
and leave without going cold.

The serving tier's two halves finally fuse here. PR 8's megabatch packs
bucket-compatible tenant brackets into ONE-SHOT packed dispatches — every
megabatch is a fresh launch and the program between launches is cold.
PRs 10/12 keep a resident sharded sweep warm on the mesh with state
threaded device-to-device — but no tenant traffic ever reaches it. An
inference server solves the same problem with continuous batching:
requests join and leave a resident batch at step boundaries, and the
program never goes cold. Brackets are bucketable exactly like requests
are bucketable (the HyperBand ladder makes shapes finite — PAPERS.md),
so sweeps continuous-batch the same way:

* **one resident program per bucket family**, lane-packed over a FIXED
  lane count — the lane count is static, so the program AOT-compiles
  ONCE (through the ``_TrackedLowered`` ledger, name
  ``continuous_bracket``) and never recompiles on tenant churn: the
  compile ledger stays ``<= len(bucket_set)`` across an entire churning
  workload (test-pinned);
* the program runs rotation **chunks** in a loop: each chunk evaluates
  one bucketed bracket per occupied lane
  (:func:`~hpbandster_tpu.ops.buckets.
  fused_sh_bracket_bucketed_packed_carry` — per-lane results
  bit-identical to a solo dispatch), zero-count-masks empty lanes, and
  folds each lane's incumbent into a **device-resident carry**
  (:func:`~hpbandster_tpu.ops.sweep.init_lane_state`) threaded
  device-to-device between chunks the way the resident sweep threads its
  obs state — tenant churn re-uploads vectors, never state, never a
  program;
* tenants **join and leave at chunk boundaries**: the pool's
  deficit-fair scheduler picks which work items board, the
  :class:`LaneAllocator` maps items to lanes (sticky per tenant — a
  returning tenant lands on its warm lane and keeps its on-device
  incumbent; a stolen lane resets in-trace via the kernel's reset mask
  so no tenant ever reads another's carry), and freed lanes admit newly
  submitted sweeps between chunks;
* over a device mesh the program is **2-D lane x config sharded**
  (``Mesh(devices.reshape(lane, config), ("lane", "config"))`` — the
  SNIPPETS.md NamedSharding/PartitionSpec patterns): whole lanes shard
  over the ``lane`` axis, rows within a lane over the ``config`` axis,
  and the carry is pinned ``PartitionSpec("lane")`` on BOTH sides of the
  program so AOT state threading has stable in/out shardings by
  construction (the ``pin_state_shards`` trick).

Observability: ``serve.lanes.*`` gauges (occupancy, starved-lane count),
per-family ``serve.family.<f>.*`` gauges (program-warm age, chunks), and
``lane_assigned``/``lane_released`` events — rendered by ``obs top``'s
lane line and ``watch --snapshot``'s per-row lanes part
(docs/serving.md "Continuous batching").
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from hpbandster_tpu.obs import events as E
from hpbandster_tpu.obs.metrics import get_metrics
from hpbandster_tpu.ops.buckets import (
    BucketPlan,
    fused_sh_bracket_bucketed_packed_carry,
    member_counts_for,
    member_telemetry_record,
    slice_member_stages,
)
from hpbandster_tpu.serve.megabatch import PackEntry

__all__ = ["ContinuousRunner", "LaneAllocator", "make_lane_mesh"]


def make_lane_mesh(lane_shards: int, devices=None):
    """The 2-D ``lane x config`` mesh of a continuous-batching program:
    ``lane_shards`` rows of whole lanes, the remaining devices splitting
    each lane's config rows (the SNIPPETS.md device-reshape pattern).
    ``lane_shards`` must divide the device count."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    lane_shards = int(lane_shards)
    if lane_shards < 1 or n % lane_shards:
        raise ValueError(
            f"lane_shards={lane_shards} must divide the {n} devices"
        )
    grid = np.array(devices, dtype=object).reshape(
        lane_shards, n // lane_shards
    )
    return Mesh(grid, ("lane", "config"))


class LaneAllocator:
    """Sticky per-tenant lane bookkeeping for one resident program.

    Pure host logic, called under the runner lock. Policy per chunk:
    a boarding entry prefers a free lane its tenant already owns (warm —
    the on-device incumbent carry survives), then an unowned lane, then
    steals the least-recently-used lane of an absent tenant (the steal
    marks the lane dirty: its carry resets IN-TRACE before the chunk
    folds, so no tenant ever reads another's state). Ownership persists
    across chunks until stolen or released — that is the warm-lane
    contract churn tests pin.
    """

    def __init__(self, lane_count: int):
        if int(lane_count) < 1:
            raise ValueError("lane_count must be >= 1")
        self.lane_count = int(lane_count)
        self.owners: List[Optional[str]] = [None] * self.lane_count
        #: lanes whose carry must reset before the next fold (stolen or
        #: released ownership — the previous tenant's incumbent must die)
        self.dirty: set = set(range(self.lane_count))
        #: lane -> last chunk index it was actively used (LRU steal key)
        self._last_used: Dict[int, int] = {}
        self._chunks = 0

    def assign(
        self, tenants: Sequence[str]
    ) -> List[Tuple[int, bool]]:
        """Map one chunk's boarding entries to lanes.

        Returns ``[(lane, warm), ...]`` per entry (warm = the tenant kept
        a lane it already owned). Two passes: warm placements FIRST (every
        boarding tenant that owns a lane keeps one — a steal can never
        evict a lane its owner is boarding this very chunk), then
        newcomers take unowned lanes, then steal the LRU lane of an
        ABSENT tenant; only when every untaken lane belongs to a boarding
        tenant that needs more lanes than it owns does the steal fall back
        to the plain LRU. Raises when more entries than lanes — callers
        chunk to capacity first."""
        if len(tenants) > self.lane_count:
            raise ValueError(
                f"{len(tenants)} entries do not fit {self.lane_count} lanes"
            )
        self._chunks += 1
        boarding = set(tenants)
        taken: set = set()
        placements: List[Optional[Tuple[int, bool]]] = [None] * len(tenants)
        owned: Dict[str, List[int]] = {}
        for lane, owner in enumerate(self.owners):
            if owner is not None:
                owned.setdefault(owner, []).append(lane)
        # pass 1: warm lanes — sticky ownership wins before any stealing
        for i, tenant in enumerate(tenants):
            mine = [x for x in owned.get(tenant, []) if x not in taken]
            if mine:
                taken.add(mine[0])
                placements[i] = (mine[0], True)
        # pass 2: unowned lanes, then absent tenants' lanes (LRU)
        unowned = [
            lane for lane, o in enumerate(self.owners) if o is None
        ]
        for i, tenant in enumerate(tenants):
            if placements[i] is not None:
                continue
            free = [x for x in unowned if x not in taken]
            if free:
                lane = free[0]
            else:
                victims = [
                    x for x in range(self.lane_count)
                    if x not in taken
                    and self.owners[x] not in boarding
                ] or [
                    x for x in range(self.lane_count) if x not in taken
                ]
                lane = min(
                    victims, key=lambda x: self._last_used.get(x, -1)
                )
                self.dirty.add(lane)
            taken.add(lane)
            self.owners[lane] = tenant
            placements[i] = (lane, False)
        for lane in taken:
            self._last_used[lane] = self._chunks
        return placements

    def release_tenant(self, tenant: str) -> List[int]:
        """Free every lane ``tenant`` owns; returns the freed lanes
        (their carries are dirty — reset before any future fold)."""
        freed = []
        for lane, owner in enumerate(self.owners):
            if owner == tenant:
                self.owners[lane] = None
                self.dirty.add(lane)
                freed.append(lane)
        return freed

    def occupied(self) -> int:
        return sum(1 for o in self.owners if o is not None)


class ContinuousRunner:
    """One bucket family's RESIDENT lane-packed program.

    The continuous-batching sibling of ``serve.megabatch.MegaRunner``:
    same AOT ``lower().compile()`` tracked-ledger contract (compiled
    exactly ONCE per family — lane count and bucket shape are static, so
    tenant churn can never recompile), plus the device-resident per-lane
    incumbent carry and the lane allocator. ``run_chunk`` is one loop
    iteration: occupied lanes evaluate their brackets, empty lanes are
    zero-count-masked (their carries pass through), and the carry output
    feeds the next chunk without ever touching the host.
    """

    def __init__(
        self,
        eval_fn,
        bucket: BucketPlan,
        lane_count: int = 8,
        mesh=None,
        lane_axis: str = "lane",
        config_axis: str = "config",
        family: int = 0,
        device_metrics: Optional[bool] = None,
    ):
        from hpbandster_tpu.obs.device_metrics import device_metrics_default
        from hpbandster_tpu.obs.runtime import tracked_jit
        from hpbandster_tpu.ops.sweep import sweep_donation_safe

        self.bucket = bucket
        self.lane_count = int(lane_count)
        self.mesh = mesh
        self.lane_axis = lane_axis
        self.config_axis = config_axis
        self.family = int(family)
        self.lanes = LaneAllocator(self.lane_count)
        self._lock = threading.Lock()
        self._compiled = None
        self._dim: Optional[int] = None
        self._carry = None
        self._compiled_mono: Optional[float] = None
        self.chunks_run = 0
        #: masked lanes of the LAST chunk while same-family items waited
        #: for a later chunk — 0 by construction; the starvation proof
        self._last_starved = 0
        #: in-trace telemetry (obs/device_metrics.py) riding the chunk
        #: dispatch: each occupied lane's decoded record emits on fetch,
        #: so continuous serving feeds the device metrics plane exactly
        #: like the one-shot paths. Resolved at construction — the flag
        #: changes the compiled program.
        self.device_metrics = (
            device_metrics_default() if device_metrics is None
            else bool(device_metrics)
        )
        dm_edges = None
        if self.device_metrics:
            from hpbandster_tpu.obs.device_metrics import bin_edges

            dm_edges = bin_edges().astype(np.float32)

        def chunk_fn(vectors, counts, carry, reset):
            return fused_sh_bracket_bucketed_packed_carry(
                eval_fn, vectors, counts, carry, reset, bucket,
                telemetry_edges=dm_edges,
            )

        # the carry is the device-resident state thread: donate it so the
        # update aliases in place on accelerator backends; gated OFF on
        # CPU by the shared gate (ops/sweep.py sweep_donation_safe). The
        # vectors/counts/reset
        # inputs are fresh uploads each chunk and their shapes never match
        # an output: donation declined for them explicitly
        # (docs/perf_notes.md "Buffer donation contract").
        jit_kwargs: Dict[str, Any] = {
            "donate_argnums": (2,) if sweep_donation_safe() else (),
        }
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            axes = dict(mesh.shape)
            lane_size = int(axes.get(lane_axis, 1))
            if lane_size > 1 and self.lane_count % lane_size:
                raise ValueError(
                    f"lane_count {self.lane_count} must be a multiple of "
                    f"the {lane_axis!r} mesh axis ({lane_size})"
                )
            cfg_size = int(axes.get(config_axis, 1))
            if cfg_size > 1 and any(w % cfg_size for w in bucket.widths):
                raise ValueError(
                    f"bucket widths {bucket.widths} must be multiples of "
                    f"the {config_axis!r} mesh axis ({cfg_size}) — build "
                    "the bucket set with mesh_size set to it"
                )
            vec_s = NamedSharding(mesh, PartitionSpec(lane_axis, config_axis))
            lane_s = NamedSharding(mesh, PartitionSpec(lane_axis))
            jit_kwargs["in_shardings"] = (vec_s, lane_s, lane_s, lane_s)
            # the carry's OUT sharding is pinned to its IN sharding, so
            # the AOT executable's state thread has stable boundary
            # shardings by construction (the pin_state_shards contract)
            out_s = ((lane_s, lane_s), lane_s)
            if self.device_metrics:
                out_s = out_s + ((lane_s, lane_s),)
            jit_kwargs["out_shardings"] = out_s
        self._wrapper = tracked_jit(
            chunk_fn, name="continuous_bracket", **jit_kwargs
        )

    # ------------------------------------------------------------- compile
    def ensure_compiled(self, d: int):
        """AOT-compile the family's ONE program (idempotent, thread-safe;
        the warm-age clock starts here)."""
        with self._lock:
            return self._ensure_compiled_locked(d)

    def _ensure_compiled_locked(self, d: int):
        if self._compiled is not None:
            if self._dim != int(d):
                raise ValueError(
                    f"continuous program compiled for d={self._dim}, "
                    f"asked for d={d}"
                )
            return self._compiled
        import jax
        import jax.numpy as jnp

        specs = (
            jax.ShapeDtypeStruct(
                (self.lane_count, self.bucket.widths[0], int(d)),
                jnp.float32,
            ),
            jax.ShapeDtypeStruct(
                (self.lane_count, self.bucket.depth), jnp.int32
            ),
            jax.ShapeDtypeStruct((self.lane_count,), jnp.float32),
            jax.ShapeDtypeStruct((self.lane_count,), jnp.bool_),
        )
        self._compiled = self._wrapper.lower(*specs).compile()
        self._dim = int(d)
        self._compiled_mono = time.monotonic()
        return self._compiled

    def warm_age_s(self) -> Optional[float]:
        """Seconds since this family's program compiled (None = cold)."""
        with self._lock:
            if self._compiled_mono is None:
                return None
            return time.monotonic() - self._compiled_mono

    # -------------------------------------------------------------- device
    def _device_carry(self):
        """The resident carry, minted on first use (rank-space +inf —
        every lane has observed nothing). Caller holds ``self._lock``
        (run_chunk is the only caller)."""
        from hpbandster_tpu.ops.sweep import init_lane_state

        if self._carry is not None:  # graftlint: disable=lock-coverage — run_chunk calls this under self._lock
            return self._carry  # graftlint: disable=lock-coverage — see above
        fresh = init_lane_state(self.lane_count)
        if self.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec

            fresh = jax.device_put(
                np.asarray(fresh),
                NamedSharding(self.mesh, PartitionSpec(self.lane_axis)),
            )
        self._carry = fresh  # graftlint: disable=lock-coverage — run_chunk calls this under self._lock
        return self._carry  # graftlint: disable=lock-coverage — see above

    def _shard_inputs(self, vectors, counts, reset):
        if self.mesh is None:
            return vectors, counts, reset
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        vec_s = NamedSharding(
            self.mesh, PartitionSpec(self.lane_axis, self.config_axis)
        )
        lane_s = NamedSharding(self.mesh, PartitionSpec(self.lane_axis))
        v_host, c_host, r_host = vectors, counts, reset
        return (
            jax.make_array_from_callback(
                v_host.shape, vec_s, lambda i: v_host[i]
            ),
            jax.make_array_from_callback(
                c_host.shape, lane_s, lambda i: c_host[i]
            ),
            jax.make_array_from_callback(
                r_host.shape, lane_s, lambda i: r_host[i]
            ),
        )

    # --------------------------------------------------------------- chunk
    def dispatch_chunk(
        self, entries: Sequence[PackEntry], d: int,
        waiting: int = 0,
    ):
        """Launch one loop iteration of the resident program; returns a
        FETCH callable (blocking d2h + demux).

        ``entries`` board lanes (at most ``lane_count`` — the pool chunks
        by capacity), the rest of the lanes are zero-count-masked, and
        the carry threads device-to-device — so the NEXT chunk (same
        family or another) can launch immediately after this returns,
        overlapping its device work with this chunk's fetch (the same
        launch-all-then-fetch discipline as the one-shot round).
        ``waiting`` is the same-family backlog that could NOT board this
        chunk; it feeds the starved-lane gauge (a masked lane while items
        wait would be a scheduling bug — the gauge proves there is none).
        """
        import jax

        from hpbandster_tpu.obs.runtime import note_transfer

        if not entries:
            return lambda: []
        m = get_metrics()
        with self._lock:
            compiled = self._ensure_compiled_locked(int(d))
            placements = self.lanes.assign([e.tenant for e in entries])
            w0 = self.bucket.widths[0]
            vectors = np.zeros((self.lane_count, w0, int(d)), np.float32)
            counts = np.zeros(
                (self.lane_count, self.bucket.depth), np.int32
            )
            # EVERY dirty lane resets this chunk (assigned or not): a
            # released lane's stale carry dies at the first opportunity,
            # not at its eventual reassignment
            reset = np.zeros(self.lane_count, bool)
            for lane in self.lanes.dirty:
                reset[lane] = True
            bus_on = E.get_bus().active
            for e, (lane, warm) in zip(entries, placements):
                rows = np.asarray(e.vectors, np.float32)
                if rows.shape[0] > w0 or rows.shape[1] != int(d):
                    raise ValueError(
                        f"member rows {rows.shape} do not fit bucket "
                        f"(W0={w0}, d={d})"
                    )
                vectors[lane, : rows.shape[0]] = rows
                counts[lane] = member_counts_for(
                    self.bucket, e.plan, e.entry
                )
                if not warm:
                    # ownership changed: the lane lifecycle event (warm
                    # re-boardings are silent — assignment is sticky, so
                    # re-emitting every chunk would only journal noise)
                    if bus_on:
                        E.emit(
                            E.LANE_ASSIGNED, lane=lane,
                            family=self.family, tenant=e.tenant,
                        )
                    m.counter("serve.continuous.joins").inc()
            carry = self._device_carry()
            h2d = vectors.nbytes + counts.nbytes + reset.nbytes
            v_dev, c_dev, r_dev = self._shard_inputs(
                vectors, counts, reset
            )
            out_dev = compiled(v_dev, c_dev, carry, r_dev)
            if self.device_metrics:
                (idx_lanes, loss_lanes), new_carry, telemetry = out_dev
            else:
                (idx_lanes, loss_lanes), new_carry = out_dev
                telemetry = None
            # carry threads device-to-device: the old buffer is replaced
            # (and donated to the launch on accelerator backends), never
            # fetched — tenant churn costs vectors, not state
            self._carry = new_carry
            note_transfer("h2d", h2d, buffers=3)
            self.lanes.dirty -= {i for i, on in enumerate(reset) if on}
            self.chunks_run += 1
            occupied = len(entries)
            masked = self.lane_count - occupied
            m.counter("serve.continuous.chunks").inc()
            m.counter("serve.continuous.masked_lanes").inc(masked)
            m.gauge(f"serve.family.{self.family}.chunks").set(
                self.chunks_run
            )
            if self._compiled_mono is not None:
                m.gauge(f"serve.family.{self.family}.warm_age_s").set(
                    round(time.monotonic() - self._compiled_mono, 3)
                )
            m.gauge(f"serve.family.{self.family}.lanes_occupied").set(
                occupied
            )
            # starved = lanes sitting masked while same-family work
            # waited for a later chunk: 0 by construction (chunks fill
            # before a second chunk runs) — the gauge is the proof
            self._last_starved = masked if waiting > 0 else 0
            m.gauge(f"serve.family.{self.family}.lanes_starved").set(
                self._last_starved
            )
            starved = self._last_starved
            chunk_seq = self.chunks_run - 1
            t_dispatch = time.monotonic()

        def fetch():
            fetched = jax.device_get(
                (idx_lanes, loss_lanes) + (
                    tuple(telemetry) if telemetry is not None else ()
                )
            )
            note_transfer(
                "d2h", sum(int(a.nbytes) for a in fetched),
                buffers=len(fetched),
            )
            idx_h, loss_h = fetched[0], fetched[1]
            tel_h = fetched[2:] if telemetry is not None else None
            out = []
            for e, (lane, _warm) in zip(entries, placements):
                stages, off = [], 0
                for w in self.bucket.widths:
                    stages.append((
                        idx_h[lane, off:off + w],
                        loss_h[lane, off:off + w],
                    ))
                    off += w
                member = slice_member_stages(stages, e.plan, e.entry)
                out.append(member)
                if tel_h is not None:
                    from hpbandster_tpu.obs.device_metrics import (
                        emit_device_telemetry,
                        publish_device_metrics,
                    )

                    rec = member_telemetry_record(
                        tel_h[0][lane], tel_h[1][lane],
                        member_counts_for(self.bucket, e.plan, e.entry),
                        self.bucket.budgets, member,
                    )
                    if rec is not None:
                        publish_device_metrics(rec)
                        emit_device_telemetry(rec)
            if E.get_bus().active:
                # span-shaped chunk record (dispatch -> fetch landed):
                # the flight recorder's rung_compute slice for a resident
                # serving round, one per chunk like the sweep tier's
                # sweep_chunk
                E.emit(
                    "serve_chunk",
                    duration_s=round(time.monotonic() - t_dispatch, 6),
                    family=self.family,
                    lanes=occupied,
                    # the lane_starvation SLO reads this off the chunk
                    # record (good when 0) — the gauge above is the live
                    # twin, this is the journaled/replayable one
                    starved=starved,
                    seq=chunk_seq,
                )
            return out

        return fetch

    def run_chunk(
        self, entries: Sequence[PackEntry], d: int,
        waiting: int = 0,
    ) -> List[List[Tuple[np.ndarray, np.ndarray]]]:
        """Dispatch + fetch one chunk (the synchronous convenience;
        the pool uses :meth:`dispatch_chunk` to overlap chunks). Each
        entry's TRUE-shape per-stage ``(indices, losses)`` come back
        demuxed in entry order — bit-identical to a solo dispatch
        (test-pinned)."""
        return self.dispatch_chunk(entries, d, waiting=waiting)()

    # ------------------------------------------------------------- tenants
    def release_tenant(self, tenant: str) -> None:
        """A tenant left the pool: free (and dirty) its lanes so the next
        chunk admits newcomers into them."""
        m = get_metrics()
        with self._lock:
            freed = self.lanes.release_tenant(tenant)
            if freed and E.get_bus().active:
                for lane in freed:
                    E.emit(
                        E.LANE_RELEASED, lane=lane, family=self.family,
                        tenant=tenant,
                    )
            if freed:
                m.counter("serve.continuous.leaves").inc(len(freed))

    def lane_incumbents(self) -> List[Optional[float]]:
        """Host decode of the resident carry: per lane, the running
        incumbent loss (None = nothing observed, NaN = crashed-only).
        An inspection surface — fetching it is the ONLY d2h the carry
        ever pays, and nothing on the serving path calls it."""
        from hpbandster_tpu.ops.sweep import decode_lane_state

        import jax

        with self._lock:
            if self._carry is None:
                return [None] * self.lane_count
            # snapshot the carry reference only: device_get blocks until
            # the in-flight chunk producing it finishes on device, and
            # holding the lock through that stalls every join/leave/submit
            # on this runner behind an inspection call. Fetching outside
            # is safe — carries are immutable; a racing chunk swaps the
            # reference, it never mutates the fetched one.
            carry = self._carry
        return decode_lane_state(jax.device_get(carry))

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "family": self.family,
                "lane_count": self.lane_count,
                "occupied": self.lanes.occupied(),
                "owners": list(self.lanes.owners),
                "chunks": self.chunks_run,
                "starved": self._last_starved,
                "warm_age_s": (
                    round(time.monotonic() - self._compiled_mono, 3)
                    if self._compiled_mono is not None else None
                ),
            }
