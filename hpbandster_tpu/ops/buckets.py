"""Shape-bucketed fused brackets: a handful of programs for a whole sweep.

The compile ledger (``obs/runtime.py``) proved what the fused per-bracket
tier pays: ``make_fused_bracket_fn`` burns every bracket shape into its
trace, so a multi-bracket HyperBand sweep compiles one XLA program per
distinct ``(num_configs, budgets)`` — seven programs for the 36-bracket
1..729 rotation, each tens of seconds on a cold cache. This module spends
those ledger numbers: bracket shapes are padded up to a small GEOMETRIC
bucket set, and per-stage survivor counts become *traced* inputs, so every
bracket in a bucket shares ONE compiled program.

Bucket geometry (:func:`build_bucket_set`):

* **depths pair up**: adjacent present depths ``(d, d-1)`` share a bucket
  aligned at the ladder TAIL (their budgets are suffixes of each other in
  a HyperBand schedule). The shallower member enters at stage 1 and wastes
  only the bucket's cheapest leading rung — a bounded ~1/depth overhead —
  while halving the program count. Deeper merges are geometrically worse
  (HyperBand rungs cost roughly equal device time), so pairing is the
  default and the knob stops there.
* **widths round up to powers of two** (floor 8) of the widest member at
  each aligned rung, so one width profile covers the pair and future
  schedules reusing the shapes hit the same executables.

The bucketed kernel (:func:`fused_sh_bracket_bucketed`) reproduces
``fused_sh_bracket``'s promotion semantics exactly — NaN (crashed) rows
rank behind every clean loss and ahead of padding, ties break
index-stably, survivors keep their original order — but the top-k widths
are traced counts: promotion is a rank mask (the same double-argsort as
``sh_promotion_mask``) followed by a static-width gather, not a static
``top_k``. Rows beyond a stage's traced count are padding: evaluated
(bounded waste, see above) but never promoted and never reported.

Programs are AOT-compiled through ``tracked_jit``'s ``lower().compile()``
proxy (:func:`precompile_buckets`), optionally on a background thread so
the compile overlaps stage-0 sampling, and every compile lands in the
process-wide ledger — the budget tests in ``tests/test_buckets.py`` and
the ceilings of ``tests/test_program_counts.py`` read it back.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from hpbandster_tpu.ops.bracket import BracketPlan
from hpbandster_tpu.utils.lru import LRUCache

__all__ = [
    "BucketPlan",
    "BucketSet",
    "build_bucket_set",
    "bucketed_stage_telemetry",
    "fused_sh_bracket_bucketed",
    "fused_sh_bracket_bucketed_packed",
    "fused_sh_bracket_bucketed_packed_carry",
    "make_bucketed_bracket_fn",
    "member_counts_for",
    "member_telemetry_record",
    "precompile_buckets",
    "slice_member_stages",
]

#: crashed (NaN) losses rank here: behind any real loss, ahead of the +inf
#: padding rows — the same constant (and therefore the same ordering) as
#: ops.fused._CRASH_RANK / the host sh_promotion_mask twin
_CRASH_RANK = np.float32(3.0e38)


class BucketPlan(NamedTuple):
    """One compiled bucket: static per-stage WIDTHS + static budgets."""

    #: padded row capacity at each stage (non-increasing, pow2, floor 8)
    widths: Tuple[int, ...]
    #: concrete budget per stage (a ladder suffix; eval fns may use it as
    #: a static trip count, exactly like the unbucketed fused bracket)
    budgets: Tuple[float, ...]

    @property
    def depth(self) -> int:
        return len(self.widths)


class BucketSet(NamedTuple):
    """The bucket programs for a schedule + each shape's placement."""

    buckets: Tuple[BucketPlan, ...]
    #: (num_configs, budgets) -> (bucket_index, entry_stage)
    assignment: Dict[Tuple, Tuple[int, int]]

    def lookup(self, num_configs, budgets) -> Optional[Tuple[int, int]]:
        key = (
            tuple(int(n) for n in num_configs),
            tuple(float(b) for b in budgets),
        )
        return self.assignment.get(key)


def _pow2(n: int, floor: int = 8) -> int:
    size = floor
    while size < n:
        size *= 2
    return size


def build_bucket_set(
    plans: Sequence[BracketPlan],
    *,
    min_width: int = 8,
    mesh_size: int = 1,
) -> BucketSet:
    """Group a schedule's bracket shapes into a small geometric bucket set.

    Shapes group by depth, adjacent present depths pairing up (deepest
    first); within a bucket, shapes align at the ladder TAIL (final budgets
    coincide), the bucket's budgets are the deepest member's, and each
    rung's width is the widest aligned member count rounded up to a power
    of two (stage 0 additionally to a multiple of ``mesh_size``). A shape
    whose budgets are not a suffix of its group's deepest member — plans
    from a different ladder — falls back to its own singleton bucket
    rather than mis-aligning.

    Single-stage plans are excluded (nothing to fuse, nothing to compile).
    """
    shapes = sorted(
        {
            (
                tuple(int(n) for n in p.num_configs),
                tuple(float(b) for b in p.budgets),
            )
            for p in plans
            if len(p.num_configs) >= 2
        },
        key=lambda s: (-len(s[1]), s[1], s[0]),
    )
    by_depth: Dict[int, List[Tuple]] = {}
    for shape in shapes:
        by_depth.setdefault(len(shape[1]), []).append(shape)

    buckets: List[BucketPlan] = []
    assignment: Dict[Tuple, Tuple[int, int]] = {}
    depths = sorted(by_depth, reverse=True)
    used: set = set()
    for d in depths:
        if d in used:
            continue
        group_depths = [d]
        if (d - 1) in by_depth and (d - 1) not in used:
            group_depths.append(d - 1)
        used.update(group_depths)

        # the bucket's budgets come from the deepest member; members whose
        # budgets are not a suffix of them get singleton buckets instead
        bucket_budgets = by_depth[d][0][1]
        members: List[Tuple[Tuple, int]] = []  # (shape, entry)
        for gd in group_depths:
            for shape in by_depth[gd]:
                entry = len(bucket_budgets) - len(shape[1])
                if shape[1] == bucket_budgets[entry:]:
                    members.append((shape, entry))
                else:
                    singleton = BucketPlan(
                        widths=tuple(
                            _pow2(int(n), min_width) for n in shape[0]
                        ),
                        budgets=shape[1],
                    )
                    singleton = _mesh_pad(singleton, mesh_size)
                    assignment[shape] = (len(buckets), 0)
                    buckets.append(singleton)

        if not members:
            continue
        widths = [0] * len(bucket_budgets)
        for shape, entry in members:
            for s, n in enumerate(shape[0]):
                widths[entry + s] = max(widths[entry + s], int(n))
        # pow2 roundup of an (aligned-max) non-increasing profile stays
        # non-increasing; the running max from the right guards the
        # invariant against degenerate inputs anyway
        widths = [_pow2(w, min_width) for w in widths]
        for j in range(len(widths) - 2, -1, -1):
            widths[j] = max(widths[j], widths[j + 1])
        bucket = _mesh_pad(
            BucketPlan(widths=tuple(widths), budgets=bucket_budgets),
            mesh_size,
        )
        idx = len(buckets)
        buckets.append(bucket)
        for shape, entry in members:
            assignment[shape] = (idx, entry)
    return BucketSet(buckets=tuple(buckets), assignment=assignment)


def _mesh_pad(bucket: BucketPlan, mesh_size: int) -> BucketPlan:
    """EVERY stage width padded to a mesh multiple, so each rung of the
    ladder stays evenly shardable over the config axis (the per-stage
    :func:`~hpbandster_tpu.ops.fused.shard_rows` constraints only apply to
    divisible widths).

    The waste is amortized by the pow2 bucket geometry: widths are already
    powers of two (floor 8), so on a pow2 mesh any width >= mesh_size is a
    multiple for free and only tail rungs narrower than the mesh pad up to
    one row per shard. Per-stage relative waste is bounded by
    ``(ceil(w/m)*m - w)/w <= (m-1)/w`` — exactly zero on pow2 meshes with
    ``w >= m`` (docs/perf_notes.md "Mesh sharding")."""
    m = max(int(mesh_size), 1)
    if m == 1 or all(w % m == 0 for w in bucket.widths):
        return bucket
    widths = [((w + m - 1) // m) * m for w in bucket.widths]
    # mesh roundup of a non-increasing profile stays non-increasing, but
    # guard the invariant like build_bucket_set does
    for j in range(len(widths) - 2, -1, -1):
        widths[j] = max(widths[j], widths[j + 1])
    return BucketPlan(widths=tuple(widths), budgets=bucket.budgets)


def fused_sh_bracket_bucketed(
    eval_fn: Callable,
    vectors,
    counts,
    bucket: BucketPlan,
    mesh=None,
    axis: str = "config",
):
    """One bucketed bracket, traceable under ``jit``.

    ``vectors`` is ``f32[widths[0], d]`` (member rows first, zero-padded);
    ``counts`` is ``i32[depth]`` — the member's TRUE per-stage config
    counts, 0 for stages before its entry. Returns per-stage
    ``(indices, losses)`` at bucket widths; rows past ``counts[t]`` are
    padding (see :func:`slice_member_stages`).

    Promotion reproduces ``fused_sh_bracket`` / ``sh_promotion_mask``
    exactly (crash rank, index-stable ties, original-order survivors) with
    the top-k width a traced count: rank < k masks survivors, a stable
    index-keyed argsort packs them first, a static slice narrows to the
    next stage's width. While a stage's count is 0 (pre-entry) the carry
    is the identity head slice, so entering rows survive untouched.

    ``mesh``/``axis`` keep each stage's rows sharded over the config axis
    (``ops.fused.shard_rows``) — the rank mask then reduces across shards
    on-device (ICI collectives) and no stage is ever gathered to one
    device. Values are bit-identical with or without the mesh.
    """
    import jax
    import jax.numpy as jnp

    from hpbandster_tpu.ops.fused import eval_lanes, shard_rows

    widths = bucket.widths
    budgets = bucket.budgets
    depth = len(widths)
    counts = jnp.asarray(counts, jnp.int32)

    cur_vecs = shard_rows(vectors, mesh, axis)
    cur_idx = jnp.arange(widths[0], dtype=jnp.int32)
    out = []
    for t in range(depth):
        losses_t = eval_lanes(eval_fn, cur_vecs, float(budgets[t]), mesh)
        out.append((cur_idx, losses_t))
        if t + 1 == depth:
            break
        w, w_next = widths[t], widths[t + 1]
        rows = jnp.arange(w, dtype=jnp.int32)
        valid = rows < counts[t]
        key = jnp.where(jnp.isnan(losses_t), _CRASH_RANK, losses_t)
        key = jnp.where(valid, key, jnp.inf)
        # double argsort = value rank with index-stable ties, the same
        # selection top_k makes (and sh_promotion_mask_np replays host-side)
        ranks = jnp.argsort(jnp.argsort(key, stable=True), stable=True)
        promote = (ranks < counts[t + 1]) & valid
        # survivors first, original order among them — then the rest, so a
        # static head slice is the gather (matches fused's sorted top_k)
        order = jnp.argsort(jnp.where(promote, rows, w + rows), stable=True)
        sel_ranked = order[:w_next]
        sel_identity = jnp.arange(w_next, dtype=jnp.int32)
        sel = jnp.where(counts[t] > 0, sel_ranked, sel_identity)
        cur_vecs = shard_rows(cur_vecs[sel], mesh, axis)
        cur_idx = cur_idx[sel]
    return out


def _lane_stages(eval_fn: Callable, bucket: BucketPlan):
    """ONE definition of a packed program's lane body: run the bucketed
    bracket and flat-concatenate its stages — shared by the uncarried
    and carried packed kernels (and their telemetry variants), so a
    future change to the lane semantics cannot diverge between the
    compiled programs."""
    import jax.numpy as jnp

    def run(vecs, cnts):
        stages = fused_sh_bracket_bucketed(eval_fn, vecs, cnts, bucket)
        return (
            stages,
            jnp.concatenate([s[0] for s in stages]),
            jnp.concatenate([s[1] for s in stages]),
        )

    return run


def _lane_telemetry(stages, cnts, edges):
    """Per-lane telemetry stack: ``(i32[depth, n_bins], i32[depth])``
    from :func:`bucketed_stage_telemetry` (padding-masked)."""
    import jax.numpy as jnp

    tel = bucketed_stage_telemetry(stages, cnts, edges)
    return (
        jnp.stack([h for h, _ in tel]),
        jnp.stack([c for _, c in tel]),
    )


def fused_sh_bracket_bucketed_packed(
    eval_fn: Callable,
    vectors,
    counts,
    bucket: BucketPlan,
    telemetry_edges=None,
):
    """A LANE-PACKED stack of bucketed brackets, traceable under ``jit``.

    ``vectors`` is ``f32[P, widths[0], d]`` and ``counts`` ``i32[P, depth]``
    — ``P`` independent member brackets of the SAME bucket, one per lane
    (the serving tier's cross-tenant megabatch, ``serve/megabatch.py``).
    Each lane runs :func:`fused_sh_bracket_bucketed` under ``vmap``;
    brackets are independent SH ladders, so lanes never interact and each
    lane's promotions are BIT-IDENTICAL to dispatching that bracket alone
    (pinned by ``tests/test_serve.py``). Returns the packed per-lane
    ``(i32[P, sum(widths)], f32[P, sum(widths)])`` pair — the same
    flat-concatenated layout the solo ``_BucketRunner`` ships, with a
    leading lane axis. With ``telemetry_edges`` (the device-metrics bin
    schema) the return gains per-lane ``(hist i32[P, depth, n_bins],
    crashes i32[P, depth])`` from :func:`bucketed_stage_telemetry`.

    A lane whose counts are all zero is pure padding: every stage carries
    the identity slice and its rows are evaluated (bounded waste, exactly
    the bucket-padding trade) but never reported to anyone.
    """
    import jax
    import jax.numpy as jnp

    body = _lane_stages(eval_fn, bucket)

    def one_lane(vecs, cnts):
        stages, idx, loss = body(vecs, cnts)
        if telemetry_edges is None:
            return idx, loss
        hist, crashes = _lane_telemetry(stages, cnts, telemetry_edges)
        return idx, loss, hist, crashes

    return jax.vmap(one_lane)(vectors, jnp.asarray(counts, jnp.int32))


def fused_sh_bracket_bucketed_packed_carry(
    eval_fn: Callable,
    vectors,
    counts,
    carry,
    reset,
    bucket: BucketPlan,
    telemetry_edges=None,
):
    """The CARRIED lane-packed kernel — the continuous-batching tier's
    device program (``serve/continuous.py``).

    Identical lane semantics to :func:`fused_sh_bracket_bucketed_packed`
    (each lane's promotions are bit-identical to a solo dispatch,
    pinned), plus a per-lane incumbent state threaded device-to-device
    across chunk dispatches the way the resident sweep threads its obs
    state (``ops/sweep.py``):

    * ``carry`` is ``f32[P]`` in RANK space
      (:func:`~hpbandster_tpu.ops.sweep.init_lane_state`): a real loss
      is itself, crashed-only is the shared crash-rank constant, and
      ``+inf`` means the lane has observed nothing;
    * ``reset`` is ``bool[P]``: True re-initializes the lane's carry
      BEFORE this chunk folds in (a lane whose owner changed at the
      chunk boundary must not leak the previous tenant's incumbent);
    * each lane folds ``min(carry, best final-stage loss)`` where NaN
      rows rank at the crash constant and rows past the lane's traced
      final count are ``+inf`` — a zero-count (masked-empty) lane folds
      ``+inf`` and its carry passes through untouched.

    Returns ``((i32[P, sum(widths)], f32[P, sum(widths)]), f32[P])`` —
    the packed per-lane stage pair and the updated carry, which the
    caller keeps ON DEVICE between chunks (the whole point: tenant churn
    never re-uploads or re-compiles, and the incumbent trail needs no
    per-chunk d2h). With ``telemetry_edges`` the return gains a third
    element: per-lane ``(hist i32[P, depth, n_bins],
    crashes i32[P, depth])`` — the device metrics plane riding the same
    dispatch (padding lanes mask to zero).
    """
    import jax
    import jax.numpy as jnp

    counts = jnp.asarray(counts, jnp.int32)
    carry = jnp.asarray(carry, jnp.float32)
    reset = jnp.asarray(reset, jnp.bool_)
    body = _lane_stages(eval_fn, bucket)

    def one_lane(vecs, cnts, c_in, rst):
        stages, idx, loss = body(vecs, cnts)
        _f_idx, f_loss = stages[-1]
        w_last = bucket.widths[-1]
        valid = jnp.arange(w_last, dtype=jnp.int32) < cnts[-1]
        rank = jnp.where(jnp.isnan(f_loss), jnp.float32(_CRASH_RANK), f_loss)
        rank = jnp.where(valid, rank, jnp.inf)
        base = jnp.where(rst, jnp.inf, c_in)
        new_c = jnp.minimum(base, jnp.min(rank))
        if telemetry_edges is None:
            return idx, loss, new_c
        hist, crashes = _lane_telemetry(stages, cnts, telemetry_edges)
        return idx, loss, new_c, hist, crashes

    out = jax.vmap(one_lane)(vectors, counts, carry, reset)
    if telemetry_edges is None:
        idx, loss, new_carry = out
        return (idx, loss), new_carry
    idx, loss, new_carry, hist, crashes = out
    return (idx, loss), new_carry, (hist, crashes)


def bucketed_stage_telemetry(stages, counts, edges):
    """Jittable device-metrics accumulation over one BUCKETED bracket's
    stages: per-stage ``(histogram i32[n_bins], crash_count i32[])`` in
    exactly the schema the fused-sweep accumulator emits
    (``ops.fused.stage_telemetry`` over ``obs/device_metrics.py`` bin
    edges) — the seam through which the bucketed/megabatch executor tier
    joins the device metrics plane.

    A bucketed stage's rows past its traced ``counts[t]`` are padding:
    evaluated but never reported, so they are masked out of BOTH the
    histogram and the crash count here (a padding row's garbage loss —
    or NaN — must not read as telemetry). Output shapes are fixed by the
    bucket depth and bin count alone.
    """
    import jax.numpy as jnp

    from hpbandster_tpu.ops.fused import stage_telemetry

    counts = jnp.asarray(counts, jnp.int32)
    out = []
    for t, (_idx_t, losses_t) in enumerate(stages):
        live = jnp.arange(losses_t.shape[0], dtype=jnp.int32) < counts[t]
        # padding rows become NaN for the histogram mask, then their
        # (artificial) crash contribution is subtracted back out
        masked = jnp.where(live, losses_t, jnp.nan)
        hist, crashes = stage_telemetry(masked, edges)
        crashes = crashes - jnp.sum(~live).astype(jnp.int32)
        out.append((hist, crashes))
    return out


def slice_member_stages(
    stages: List[Tuple], plan: BracketPlan, entry: int
) -> List[Tuple]:
    """Cut a bucket dispatch's stage list down to one member bracket's
    results: bucket stage ``entry + s`` holds member stage ``s`` in its
    first ``plan.num_configs[s]`` rows."""
    out = []
    for s, k in enumerate(plan.num_configs):
        idx, losses = stages[entry + s]
        out.append((idx[: int(k)], losses[: int(k)]))
    return out


def member_counts_for(
    bucket: BucketPlan, plan: BracketPlan, entry: int
) -> np.ndarray:
    """One member bracket's entry-aligned traced-count vector
    (``i32[bucket.depth]``, zeros for pre-entry stages) — the ONE
    definition of the counts layout every dispatcher builds."""
    counts = np.zeros(bucket.depth, np.int32)
    for s, k in enumerate(plan.num_configs):
        counts[entry + s] = int(k)
    return counts


def member_telemetry_record(hist, crashes, counts, budgets, stages):
    """One member bracket's fetched in-trace telemetry -> the decoded
    ``device_telemetry`` record (``obs/device_metrics.py`` schema).

    ``hist``/``crashes`` are the :func:`bucketed_stage_telemetry` outputs
    for this member's dispatch (or its lane of a packed dispatch),
    bucket-depth rows; ``counts`` the member's entry-aligned traced
    counts; ``budgets`` the bucket's budgets; ``stages`` the member's
    TRUE-shape per-stage ``(idx, losses)`` (for the best-final fold —
    already fetched, no extra device work). Returns None for an all-zero
    (padding) lane. The record shape matches what the fused drivers
    journal, so ``summarize``/``report``/anomaly readers see one schema
    whichever executor produced it.
    """
    from types import SimpleNamespace

    from hpbandster_tpu.obs.device_metrics import decode_device_metrics

    counts = np.asarray(counts, np.int64)
    nonzero = np.nonzero(counts)[0]
    if nonzero.size == 0:
        return None
    entry = int(nonzero[0])
    member_counts = tuple(int(c) for c in counts[entry:])
    member_budgets = tuple(float(b) for b in budgets[entry:])
    n_stages = len(member_counts)
    hist_m = np.asarray(hist)[entry:entry + n_stages]
    crash_m = np.asarray(crashes)[entry:entry + n_stages]
    # SH promotions are exactly the next stage's traced count (the rank
    # mask always fills it: counts are non-increasing and crashed rows
    # still rank); the final rung promotes nobody
    promos = np.array(list(member_counts[1:]) + [0], np.int64)
    final_losses = np.asarray(stages[-1][1], np.float32)[: member_counts[-1]]
    finite = final_losses[~np.isnan(final_losses)]
    best = float(finite.min()) if finite.size else float("nan")
    metrics = SimpleNamespace(
        loss_hist=hist_m[None, :, :],
        evals=np.array([member_counts], np.int64),
        crashes=crash_m[None, :],
        promotions=promos[None, :],
        model_fits=np.zeros((1,), np.int64),
        best_final=np.array([best], np.float32),
    )
    return decode_device_metrics(
        metrics, plans=[(member_counts, member_budgets)]
    )


class _TelemetryPacked(NamedTuple):
    """A telemetry-carrying dispatch handle: the compiled output tuple
    plus the host counts the decode needs (callers treat dispatch
    results as opaque, so the handle rides through their fetch
    plumbing untouched)."""

    out: Tuple
    counts: np.ndarray


def _publish_member_telemetry(hist, crashes, counts, budgets, stages) -> None:
    """Decode one member's fetched telemetry and hand it to the obs
    pipeline (gauges + ``device_telemetry`` journal record) — the shared
    tail of the solo and packed unpack paths."""
    from hpbandster_tpu.obs.device_metrics import (
        emit_device_telemetry,
        publish_device_metrics,
    )

    rec = member_telemetry_record(hist, crashes, counts, budgets, stages)
    if rec is not None:
        publish_device_metrics(rec)
        emit_device_telemetry(rec)


#: process-wide compiled-bucket cache — same policy as ops.fused's
#: _FUSED_FN_CACHE: a (objective, bucket, mesh, telemetry-flag)
#: combination compiles once per process, bounded so throwaway closures
#: cannot pin executables
_BUCKET_FN_CACHE: LRUCache = LRUCache(maxsize=64)


class _BucketRunner:
    """One bucket's compiled program + dispatch/unpack plumbing.

    The executable is built exactly once (lazily on first dispatch, or
    ahead of time via :meth:`ensure_compiled` / :func:`precompile_buckets`)
    through the tracked ``lower().compile()`` proxy, so the compile ledger
    sees exactly one compile per bucket — the number the budget tests
    assert on. Dispatches always run the AOT executable;
    the jit wrapper itself is never called (that would compile a second,
    untracked-by-AOT cache entry).
    """

    def __init__(self, eval_fn, bucket: BucketPlan, mesh=None, axis="config",
                 device_metrics: Optional[bool] = None):
        from hpbandster_tpu.obs.device_metrics import device_metrics_default
        from hpbandster_tpu.obs.runtime import tracked_jit

        self.bucket = bucket
        self.mesh = mesh
        self.axis = axis
        #: in-trace telemetry (obs/device_metrics.py): the compiled
        #: program additionally returns per-stage histograms + crash
        #: counts (bucketed_stage_telemetry) and every unpack emits the
        #: decoded device_telemetry record — the bucketed/megabatch
        #: executors' join onto the device metrics plane. Resolved HERE
        #: (not at dispatch) because the flag changes the program.
        self.device_metrics = (
            device_metrics_default() if device_metrics is None
            else bool(device_metrics)
        )
        self._lock = threading.Lock()
        self._compiled = None
        self._dim: Optional[int] = None
        # the bin schema is a host constant burned into the trace —
        # resolved OUTSIDE the traced closure (obs-emit-in-jit contract)
        dm_edges = None
        if self.device_metrics:
            from hpbandster_tpu.obs.device_metrics import bin_edges

            dm_edges = bin_edges().astype(np.float32)

        def bracket(vectors, counts):
            stages = fused_sh_bracket_bucketed(
                eval_fn, vectors, counts, bucket, mesh=mesh, axis=axis
            )
            import jax.numpy as jnp

            out = (
                jnp.concatenate([s[0] for s in stages]),
                jnp.concatenate([s[1] for s in stages]),
            )
            if dm_edges is None:
                return out
            tel = bucketed_stage_telemetry(stages, counts, dm_edges)
            return out + (
                jnp.stack([h for h, _ in tel]),
                jnp.stack([c for _, c in tel]),
            )

        jit_kwargs: Dict = {
            # donation declined explicitly (docs/perf_notes.md): the
            # packed (idx, loss) outputs cannot alias the [W0, d] vectors
            # input, so donating it would only emit a per-compile warning
            "donate_argnums": (),
        }
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            shard = NamedSharding(mesh, PartitionSpec(axis))
            rep = NamedSharding(mesh, PartitionSpec())
            jit_kwargs["in_shardings"] = (shard, rep)
        self._wrapper = tracked_jit(
            bracket, name="fused_bucket", **jit_kwargs
        )

    # ------------------------------------------------------------- compile
    def ensure_compiled(self, d: int):
        """AOT-compile the bucket program for ``d``-dim vectors (idempotent,
        thread-safe — the background precompiler and a dispatching executor
        may race here)."""
        with self._lock:
            if self._compiled is not None:
                if self._dim != int(d):
                    raise ValueError(
                        f"bucket program compiled for d={self._dim}, "
                        f"asked for d={d}"
                    )
                return self._compiled
            import jax
            import jax.numpy as jnp

            specs = (
                jax.ShapeDtypeStruct((self.bucket.widths[0], int(d)), jnp.float32),
                jax.ShapeDtypeStruct((self.bucket.depth,), jnp.int32),
            )
            self._compiled = self._wrapper.lower(*specs).compile()
            self._dim = int(d)
            return self._compiled

    # ------------------------------------------------------------ dispatch
    def dispatch(self, vectors: np.ndarray, counts: Sequence[int]):
        """Launch one member bracket; returns packed DEVICE arrays without
        blocking (callers overlap several brackets before fetching).

        ``vectors`` is ``f32[n0, d]`` member rows (padded up here);
        ``counts`` the member's true per-stage counts, entry-aligned
        (length = bucket depth, leading zeros for pre-entry stages).
        """
        from hpbandster_tpu.obs.runtime import note_transfer

        vectors = np.asarray(vectors, np.float32)
        w0 = self.bucket.widths[0]
        if vectors.shape[0] > w0:
            raise ValueError(
                f"{vectors.shape[0]} rows do not fit bucket width {w0}"
            )
        if vectors.shape[0] < w0:
            vectors = np.concatenate(
                [vectors, np.zeros((w0 - vectors.shape[0], vectors.shape[1]),
                                   np.float32)]
            )
        counts = np.asarray(counts, np.int32)
        if counts.shape != (self.bucket.depth,):
            raise ValueError(
                f"counts must be i32[{self.bucket.depth}], got {counts.shape}"
            )
        compiled = self.ensure_compiled(vectors.shape[1])
        note_transfer("h2d", vectors.nbytes + counts.nbytes, buffers=2)
        counts_host = np.asarray(counts)
        if self.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec

            shard = NamedSharding(self.mesh, PartitionSpec(self.axis))
            rep = NamedSharding(self.mesh, PartitionSpec())
            vecs_host = vectors
            vectors = jax.make_array_from_callback(
                vecs_host.shape, shard, lambda idx: vecs_host[idx]
            )
            counts = jax.make_array_from_callback(
                counts_host.shape, rep, lambda idx: counts_host[idx]
            )
        out = compiled(vectors, counts)
        if self.device_metrics:
            # the counts ride the handle so unpack can decode the
            # telemetry against the member's true rung layout
            return _TelemetryPacked(out, counts_host)
        return out

    def unpack(self, packed) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Blocking fetch of a dispatch's packed pair, cut back into
        per-stage (idx, losses) at bucket widths. A telemetry-carrying
        dispatch (``device_metrics=True``) additionally decodes the
        in-trace histograms/crash counts into a ``device_telemetry``
        record, publishes the gauges, and journals the event — the
        bucketed executor tier's join onto the device metrics plane."""
        import jax

        from hpbandster_tpu.obs.runtime import note_transfer

        counts_host = None
        if isinstance(packed, _TelemetryPacked):
            packed, counts_host = packed
        fetched = jax.device_get(tuple(packed))
        note_transfer(
            "d2h", sum(int(a.nbytes) for a in fetched), buffers=len(fetched)
        )
        idx_flat, loss_flat = fetched[0], fetched[1]
        out, off = [], 0
        for w in self.bucket.widths:
            out.append((idx_flat[off:off + w], loss_flat[off:off + w]))
            off += w
        if counts_host is not None and len(fetched) == 4:
            _publish_member_telemetry(
                fetched[2], fetched[3], counts_host, self.bucket.budgets, out
            )
        return out

    def run_member(self, vectors: np.ndarray, plan: BracketPlan, entry: int):
        """Dispatch + fetch one member bracket, returning its TRUE-shape
        per-stage ``(indices, losses)`` — the drop-in equivalent of a
        ``make_fused_bracket_fn`` runner call."""
        counts = member_counts_for(self.bucket, plan, entry)
        packed = self.dispatch(np.asarray(vectors, np.float32), counts)
        return slice_member_stages(self.unpack(packed), plan, entry)


def make_bucketed_bracket_fn(
    eval_fn: Callable,
    bucket: BucketPlan,
    mesh=None,
    axis: str = "config",
    device_metrics: Optional[bool] = None,
) -> _BucketRunner:
    """The (process-cached) runner for one bucket program. The telemetry
    flag resolves BEFORE the cache key (like the fused drivers'
    ``_sweep_key``): a mid-process ``HPB_DEVICE_METRICS`` flip misses the
    cache instead of silently serving the other program."""
    from hpbandster_tpu.obs.device_metrics import device_metrics_default

    if device_metrics is None:
        device_metrics = device_metrics_default()
    key = (eval_fn, bucket, mesh, axis, bool(device_metrics))
    runner = _BUCKET_FN_CACHE.get(key)
    if runner is None:
        runner = _BucketRunner(
            eval_fn, bucket, mesh=mesh, axis=axis,
            device_metrics=device_metrics,
        )
        _BUCKET_FN_CACHE[key] = runner
    return runner


class _Precompile:
    """Handle over a (possibly background) bucket-set compilation."""

    def __init__(self, runners: List[_BucketRunner], d: int):
        self._runners = runners
        self._d = int(d)
        self._done = threading.Event()
        self.errors: List[Exception] = []

    def _work(self) -> None:
        try:
            for r in self._runners:
                try:
                    r.ensure_compiled(self._d)
                except Exception as e:  # noqa: BLE001 — reported via wait()
                    self.errors.append(e)
        finally:
            self._done.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until every bucket is compiled; True when finished."""
        return self._done.wait(timeout)


def precompile_buckets(
    eval_fn: Callable,
    bucket_set: BucketSet,
    d: int,
    mesh=None,
    axis: str = "config",
    background: bool = True,
) -> _Precompile:
    """AOT-compile every bucket program through the tracked
    ``lower().compile()`` proxy — in a daemon thread by default, so the
    compile overlaps the optimizer's stage-0 sampling instead of
    serializing in front of the first dispatch. Returns a handle whose
    ``wait()`` blocks until the set is ready (dispatching earlier is safe:
    the runner's own lock serializes on the in-flight compile)."""
    runners = [
        make_bucketed_bracket_fn(eval_fn, b, mesh=mesh, axis=axis)
        for b in bucket_set.buckets
    ]
    handle = _Precompile(runners, d)
    if background:
        threading.Thread(
            target=handle._work, daemon=True, name="bucket-precompile"
        ).start()
    else:
        handle._work()
    return handle
