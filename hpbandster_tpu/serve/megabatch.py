"""Cross-tenant megabatching: many small brackets, one fused dispatch.

Ragged multi-tenant demand is the device-utilization killer: sixteen
tenants each dispatching a 27-row bracket wave leaves the accelerator
idle between sixteen small launches. BOHB/HyperBand brackets are
independent SH ladders (nothing in the analysis couples them — PAPERS.md),
so bucket-compatible brackets from DIFFERENT tenants can share one
program launch: :func:`~hpbandster_tpu.ops.buckets.
fused_sh_bracket_bucketed_packed` runs ``P`` lanes of the same bucket
program under ``vmap``, and this module owns the packing (member brackets
-> lanes, zero-padding the remainder) and the demux (lanes -> per-member
true-shape stage results).

Program-count contract (the acceptance bar ``tests/test_serve.py`` pins
against the compile ledger): the lane capacity ``pack_width`` is STATIC
per runner, so the packed path compiles at most ONE program per bucket —
``<= len(bucket_set)`` programs however many tenants come and go. Fewer
ready brackets than lanes means zero-count padding lanes (evaluated,
never reported — the same bounded-waste trade bucket padding already
made); more means several dispatches of the same executable.

Bit-parity contract: a member bracket's ``(indices, losses)`` from a
packed dispatch are identical to dispatching it alone through the solo
:class:`~hpbandster_tpu.ops.buckets._BucketRunner` — lanes cannot
interact under ``vmap``, and the test suite pins exact equality.

Runners are process-cached and AOT-compiled through the tracked
``lower().compile()`` proxy exactly like the solo bucket runners, so the
compile ledger, the ceilings of ``tests/test_program_counts.py`` and the
roofline report see the megabatch programs as first-class citizens.
"""

from __future__ import annotations

import threading
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from hpbandster_tpu.obs.metrics import get_metrics
from hpbandster_tpu.ops.bracket import BracketPlan
from hpbandster_tpu.ops.buckets import (
    BucketPlan,
    fused_sh_bracket_bucketed_packed,
    member_counts_for,
    member_telemetry_record,
    slice_member_stages,
)
from hpbandster_tpu.utils.lru import LRUCache

__all__ = ["PackEntry", "MegaRunner", "make_mega_runner", "pack_members"]


class PackEntry(NamedTuple):
    """One member bracket heading into a packed dispatch."""

    #: who this bracket belongs to (demuxed results return per entry)
    tenant: str
    #: f32[n0, d] member stage-0 rows (true shape; lane-padded here)
    vectors: np.ndarray
    #: the member's true bracket shape
    plan: BracketPlan
    #: entry stage inside the bucket (ops/buckets.py assignment)
    entry: int


def pack_members(
    entries: Sequence[PackEntry], bucket: BucketPlan, pack_width: int, d: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Member brackets -> ``(f32[P, W0, d], i32[P, depth])`` lane arrays.

    Lanes beyond ``len(entries)`` are zero padding: zero vectors and
    all-zero counts (every stage pre-entry — the kernel carries the
    identity slice and nobody reads the lane back).
    """
    if len(entries) > pack_width:
        raise ValueError(
            f"{len(entries)} members do not fit pack_width {pack_width}"
        )
    w0 = bucket.widths[0]
    vectors = np.zeros((pack_width, w0, d), np.float32)
    counts = np.zeros((pack_width, bucket.depth), np.int32)
    for lane, e in enumerate(entries):
        rows = np.asarray(e.vectors, np.float32)
        if rows.shape[0] > w0 or rows.shape[1] != d:
            raise ValueError(
                f"member rows {rows.shape} do not fit bucket "
                f"(W0={w0}, d={d})"
            )
        vectors[lane, : rows.shape[0]] = rows
        for s, k in enumerate(e.plan.num_configs):
            counts[lane, e.entry + s] = int(k)
    return vectors, counts


class MegaRunner:
    """One bucket's PACKED program: ``pack_width`` lanes per dispatch.

    The lane-packed sibling of ``ops.buckets._BucketRunner``: same AOT
    ``lower().compile()`` tracked-ledger contract, same
    compile-exactly-once lock discipline, plus the pack/demux plumbing.
    """

    def __init__(
        self,
        eval_fn,
        bucket: BucketPlan,
        pack_width: int = 8,
        mesh=None,
        axis: str = "config",
        device_metrics: Optional[bool] = None,
    ):
        from hpbandster_tpu.obs.device_metrics import device_metrics_default
        from hpbandster_tpu.obs.runtime import tracked_jit

        if pack_width < 1:
            raise ValueError("pack_width must be >= 1")
        self.bucket = bucket
        self.pack_width = int(pack_width)
        self.mesh = mesh
        self.axis = axis
        #: in-trace telemetry per lane (obs/device_metrics.py): demux
        #: then emits one decoded device_telemetry record per member —
        #: the megabatch tier's join onto the device metrics plane.
        #: Resolved here because the flag changes the compiled program.
        self.device_metrics = (
            device_metrics_default() if device_metrics is None
            else bool(device_metrics)
        )
        self._lock = threading.Lock()
        self._compiled = None
        self._dim: Optional[int] = None
        # the bin schema is a host constant burned into the trace —
        # resolved OUTSIDE the traced closure (obs-emit-in-jit contract)
        edges = None
        if self.device_metrics:
            from hpbandster_tpu.obs.device_metrics import bin_edges

            edges = bin_edges().astype(np.float32)

        def packed_bracket(vectors, counts):
            return fused_sh_bracket_bucketed_packed(
                eval_fn, vectors, counts, bucket, telemetry_edges=edges
            )

        jit_kwargs: Dict = {
            # donation declined explicitly (docs/perf_notes.md "Buffer
            # donation contract"): the packed (idx, loss) outputs cannot
            # alias the [P, W0, d] vectors input — wrong shape and dtype
            "donate_argnums": (),
        }
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            # shard over the LANE axis: each device runs whole lanes, so
            # the per-lane promotion logic never crosses a shard boundary
            shard = NamedSharding(mesh, PartitionSpec(axis))
            rep = NamedSharding(mesh, PartitionSpec())
            jit_kwargs["in_shardings"] = (shard, rep)
            mesh_size = int(dict(mesh.shape).get(axis, 1))
            if mesh_size > 1 and self.pack_width % mesh_size:
                raise ValueError(
                    f"pack_width {self.pack_width} must be a multiple of "
                    f"the {axis!r} mesh axis ({mesh_size}) to lane-shard"
                )
        self._wrapper = tracked_jit(
            packed_bracket, name="megabatch_bracket", **jit_kwargs
        )

    # ------------------------------------------------------------- compile
    def ensure_compiled(self, d: int):
        """AOT-compile the packed program (idempotent, thread-safe —
        precompile and a dispatching pool round may race here)."""
        with self._lock:
            if self._compiled is not None:
                if self._dim != int(d):
                    raise ValueError(
                        f"megabatch program compiled for d={self._dim}, "
                        f"asked for d={d}"
                    )
                return self._compiled
            import jax
            import jax.numpy as jnp

            specs = (
                jax.ShapeDtypeStruct(
                    (self.pack_width, self.bucket.widths[0], int(d)),
                    jnp.float32,
                ),
                jax.ShapeDtypeStruct(
                    (self.pack_width, self.bucket.depth), jnp.int32
                ),
            )
            self._compiled = self._wrapper.lower(*specs).compile()
            self._dim = int(d)
            return self._compiled

    # ------------------------------------------------------------ dispatch
    def dispatch(self, entries: Sequence[PackEntry], d: int):
        """Launch one packed dispatch of up to ``pack_width`` members;
        returns the packed DEVICE pair without blocking (pools overlap
        several dispatches before fetching)."""
        from hpbandster_tpu.obs.runtime import note_transfer

        vectors, counts = pack_members(
            entries, self.bucket, self.pack_width, int(d)
        )
        compiled = self.ensure_compiled(d)
        h2d_bytes = int(vectors.nbytes) + int(counts.nbytes)
        if self.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec

            shard = NamedSharding(self.mesh, PartitionSpec(self.axis))
            rep = NamedSharding(self.mesh, PartitionSpec())
            vecs_host, counts_host = vectors, counts
            vectors = jax.make_array_from_callback(
                vecs_host.shape, shard, lambda idx: vecs_host[idx]
            )
            counts = jax.make_array_from_callback(
                counts_host.shape, rep, lambda idx: counts_host[idx]
            )
        out = compiled(vectors, counts)
        # count AFTER launch: a dispatch that failed to upload or enqueue
        # (device OOM, callback error) must not read as packed throughput
        note_transfer("h2d", h2d_bytes, buffers=2)
        m = get_metrics()
        m.counter("serve.megabatch.dispatches").inc()
        m.counter("serve.megabatch.packed_brackets").inc(len(entries))
        m.counter("serve.megabatch.pad_lanes").inc(
            self.pack_width - len(entries)
        )
        return out

    def demux(
        self, packed, entries: Sequence[PackEntry]
    ) -> List[List[Tuple[np.ndarray, np.ndarray]]]:
        """Blocking fetch of one dispatch, cut back into each member's
        TRUE-shape per-stage ``(indices, losses)`` — the per-tenant view,
        in ``entries`` order. Telemetry-carrying dispatches
        (``device_metrics=True``) additionally emit one decoded
        ``device_telemetry`` record per member lane."""
        import jax

        from hpbandster_tpu.obs.runtime import note_transfer

        fetched = jax.device_get(tuple(packed))
        note_transfer(
            "d2h", sum(int(a.nbytes) for a in fetched), buffers=len(fetched)
        )
        idx_lanes, loss_lanes = fetched[0], fetched[1]
        telemetry = fetched[2:] if len(fetched) == 4 else None
        out: List[List[Tuple[np.ndarray, np.ndarray]]] = []
        for lane, e in enumerate(entries):
            stages, off = [], 0
            for w in self.bucket.widths:
                stages.append((
                    idx_lanes[lane, off:off + w],
                    loss_lanes[lane, off:off + w],
                ))
                off += w
            out.append(slice_member_stages(stages, e.plan, e.entry))
            if telemetry is not None:
                from hpbandster_tpu.obs.device_metrics import (
                    emit_device_telemetry,
                    publish_device_metrics,
                )

                rec = member_telemetry_record(
                    telemetry[0][lane], telemetry[1][lane],
                    member_counts_for(self.bucket, e.plan, e.entry),
                    self.bucket.budgets, stages,
                )
                if rec is not None:
                    publish_device_metrics(rec)
                    emit_device_telemetry(rec)
        return out

    def run_packed(
        self, entries: Sequence[PackEntry], d: int
    ) -> List[List[Tuple[np.ndarray, np.ndarray]]]:
        """Dispatch + demux in one call (the pool's synchronous path)."""
        return self.demux(self.dispatch(entries, d), entries)


#: process-wide packed-program cache — same policy as the solo
#: _BUCKET_FN_CACHE: an (objective, bucket, width, mesh, telemetry-flag)
#: combination compiles once per process, bounded so throwaway pools
#: cannot pin executables forever
_MEGA_FN_CACHE: LRUCache = LRUCache(maxsize=64)


def make_mega_runner(
    eval_fn,
    bucket: BucketPlan,
    pack_width: int = 8,
    mesh=None,
    axis: str = "config",
    device_metrics: Optional[bool] = None,
) -> MegaRunner:
    """The (process-cached) packed runner for one bucket program. The
    telemetry flag resolves BEFORE the cache key (the
    ``make_bucketed_bracket_fn`` contract): a mid-process
    ``HPB_DEVICE_METRICS`` flip misses the cache, never serves the other
    program."""
    from hpbandster_tpu.obs.device_metrics import device_metrics_default

    if device_metrics is None:
        device_metrics = device_metrics_default()
    key = (eval_fn, bucket, int(pack_width), mesh, axis, bool(device_metrics))
    runner = _MEGA_FN_CACHE.get(key)
    if runner is None:
        runner = MegaRunner(
            eval_fn, bucket, pack_width=pack_width, mesh=mesh, axis=axis,
            device_metrics=device_metrics,
        )
        _MEGA_FN_CACHE[key] = runner
    return runner
