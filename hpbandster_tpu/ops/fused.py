"""Fused successive-halving bracket: all stages in ONE device computation.

The north-star capability (SURVEY.md §0, BASELINE.json): "per-bracket
allocation decided on-device". Stage evaluations, the top-k promotion
decision, and the gather of surviving configs all happen inside a single
jitted function — zero host round-trips between stages, so a whole bracket
is one dispatch regardless of depth.

Shapes are fully static: ``num_configs``/``budgets`` are Python tuples
closed over at trace time, each stage's survivor batch has its statically
known size, and budget-dependent training loops see a *concrete* budget
(enabling static trip counts). Crashed configs surface as NaN losses and
rank behind every clean loss in the on-device promotion (but ahead of
mesh-padding rows), index-stably — matching ``sh_promotion_mask``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hpbandster_tpu.obs.runtime import note_transfer, tracked_jit

__all__ = ["LaneFacts", "StatefulEval", "eval_lanes", "fused_sh_bracket",
           "init_draws", "lanes_at_once", "make_fused_bracket_fn", "shard_rows",
           "stage_telemetry"]

#: crashed (NaN) losses map here for ranking: behind any real loss, ahead of
#: the +inf padding rows, ties broken index-stably by top_k — the same
#: ordering sh_promotion_mask's argsort produces host-side. numpy, NOT a
#: jnp scalar: module-level device-array creation would initialize the jax
#: backend at import time (see workloads/toys.py).
_CRASH_RANK = np.float32(3.0e38)


def shard_rows(x: jax.Array, mesh, axis: str = "config") -> jax.Array:
    """Constrain a leading batch dim to stay sharded over ``axis``.

    Identity on values (a sharding constraint never changes bits) and a
    no-op without a mesh or when the row count does not divide evenly —
    XLA is then free to choose its own layout for that (small) stage.
    Inserted between the stages of a sharded fused bracket so the config
    axis stays distributed for the whole rung ladder: survivor gathers and
    the rank reduction become ICI collectives instead of XLA deciding to
    home the batch on one device.
    """
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    from hpbandster_tpu.parallel.mesh import is_multiprocess_mesh, shard_count

    m = shard_count(mesh, axis)
    if m <= 1 or x.shape[0] % m != 0:
        return x
    if is_multiprocess_mesh(mesh) and jax.default_backend() == "cpu":
        # CPU PJRT does not implement multiprocess computations at all
        # ("Multiprocess computations aren't implemented on the CPU
        # backend"), so forcing cross-process layouts here can only add
        # failure modes — the DCN-on-CPU test pods keep XLA's own layout
        # choice, the pre-constraint behavior. Real pods (TPU/GPU) keep
        # the constraints: that is where the ICI/DCN reduction lives.
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(axis))
    )


def stage_telemetry(
    losses: jax.Array, edges
) -> Tuple[jax.Array, jax.Array]:
    """Jittable one-stage telemetry: ``(histogram i32[len(edges)+1],
    crash_count i32[])`` over one rung's losses — the device half of the
    metrics plane (``obs/device_metrics.py`` owns the schema; ``edges``
    are its ``bin_edges()``, the ONE definition host and device bin
    against).

    NaN (crashed) losses are excluded from the histogram and counted in
    the crash counter; +/-inf are finite-for-binning (they land in the
    overflow/underflow bins — a diverged loss is still a loss). A loss
    equal to a bin's upper bound lands IN that bin (<= against the upper
    bound, matching ``obs.metrics.Histogram``'s ``bisect_left``).

    Deliberately scatter-free: the histogram is a cumulative
    ``count(loss <= edge)`` compare-matrix reduced over the loss axis,
    then adjacent-differenced — XLA lowers it to vectorized partial sums
    (and, when the losses are sharded over the config axis, to per-shard
    partials + one tiny cross-shard reduction), where a scatter-add
    lowers to a serial loop (measured ~2x slower on CPU and hostile to
    sharding). Output shape is fixed by the bin count alone, so
    accumulating this per rung keeps the telemetry payload independent
    of the config count — the resident tier's flat-host-link contract.
    """
    edges = jnp.asarray(edges, jnp.float32)
    losses = losses.astype(jnp.float32)
    crashed = jnp.isnan(losses)
    w = jnp.where(crashed, 0, 1).astype(jnp.int32)
    # NaN compares false against every edge, but the weight mask is the
    # authoritative exclusion (it also keeps the total-count arithmetic
    # honest for the overflow bin)
    le = (losses[:, None] <= edges[None, :]).astype(jnp.int32) * w[:, None]
    cum = jnp.sum(le, axis=0)  # finite losses at or below each edge
    total = jnp.sum(w)
    hist = jnp.concatenate(
        [cum[:1], jnp.diff(cum), (total - cum[-1])[None]]
    )
    return hist, jnp.sum(crashed).astype(jnp.int32)


class StatefulEval(NamedTuple):
    """Stateful-evaluation seam beside ``eval_fn``: real-model training
    whose live state (weight/optimizer pytrees) threads through the rung
    ladder so promoted configs CONTINUE training instead of restarting.

    ``init_fn(vectors f32[n, d]) -> state`` builds the rung's ensemble:
    a pytree whose every leaf carries a leading config axis of size ``n``
    (one lane per config row, padding rows included).

    ``step_fn(state, vectors f32[k, d], budget, prev_budget) ->
    (state, losses f32[k])`` advances each lane from cumulative budget
    ``prev_budget`` to ``budget`` (both CONCRETE floats — static trip
    counts for the inner ``lax.scan``) and returns the lanes' current
    validation losses. Lane ``i`` of the state corresponds to row ``i``
    of ``vectors``; a crashed (diverged) lane reports NaN and must not
    influence any other lane — the bracket ranks it with the shared
    crash key, exactly like the stateless path.

    The bracket gathers surviving state leaves with the SAME top-k
    indices the rung ranked by (``jax.tree.map(lambda l: l[top], state)``),
    so promotion selects among live training states — warm continuation.
    Evicted lanes simply drop out of the gather; the next bracket's
    ``init_fn`` re-creates fresh lanes in-trace. See
    ``workloads/ensemble.py`` for the vmapped-SGD reference
    implementation and ``docs/workloads.md`` for the protocol contract.
    """

    init_fn: Callable[[jax.Array], Any]
    step_fn: Callable[[Any, jax.Array, float, float], Tuple[Any, jax.Array]]


class LaneFacts(NamedTuple):
    """What a workload's maker states about one lane of its stateless
    ``eval_fn``, attached as ``eval_fn.lane_facts``: facts the sweep cannot
    see from outside the function and acts on (:func:`eval_lanes`) or
    accounts with (``FusedBOHB.run_stats``). An ``eval_fn`` without the
    attribute is evaluated and accounted exactly as before there was one."""

    #: device bytes one lane needs while it is evaluated: parameters,
    #: optimizer state, gradients and the peak of its activations
    bytes: int
    #: tokens one unit of budget trains on (0: budget is not in tokens)
    tokens_per_step: int = 0
    #: names of the numbers an evaluation counts on the device beside its
    #: loss, and ``with_counters(vec, budget) -> (loss, f32[len(counters)])``
    counters: Tuple[str, ...] = ()
    with_counters: Any = None
    #: the evaluation takes its budget as a traced value too (no Python
    #: loop over it): lanes in turn can then share one trace of the lane
    #: over all the rungs of a bracket (:func:`_sh_bracket_in_turn`)
    traced_budget: bool = False
    #: ``shared() -> tree``: what of a lane no configuration changes and
    #: every evaluation would make again (the unit draw of its initial
    #: weights). ``eval_fn`` and ``with_counters`` take the tree as a third
    #: argument; a loop that takes the evaluations in turn (a bracket's, or
    #: a rung's) makes it once, before the loop, where it fits the device
    #: beside the lanes (:func:`_holds_shared`)
    shared: Any = None


def _device_memory_bytes():
    """What one device of the default backend can hold, or ``None`` where
    the backend does not say (the CPU)."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def lanes_at_once(eval_fn, n_lanes: int) -> int:
    """How many of a rung's ``n_lanes`` lanes are evaluated side by side:
    all of them, unless the maker stated a footprint
    (``eval_fn.lane_facts.bytes``) under which they do not fit the
    device's memory together; then as many as do, and at least one."""
    facts = getattr(eval_fn, "lane_facts", None)
    memory = _device_memory_bytes() if facts is not None else None
    if memory is None or n_lanes * facts.bytes <= memory:
        return n_lanes
    return max(int(memory // facts.bytes), 1)


@functools.lru_cache(maxsize=64)
def _shared_bytes(shared) -> int:
    """The bytes of ``shared()``, from its shapes alone: read once a lane,
    not once a chunk's accounting."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(jax.eval_shape(shared)))


def _holds_shared(facts, at_once: int) -> bool:
    """Whether a loop that takes a lane's evaluations in turn, ``at_once`` of
    them side by side, makes what they share (``facts.shared``) once and
    holds it: the lane states it, and its bytes fit the device beside the
    lanes'. A backend that does not say its memory (the CPU) holds it."""
    if facts is None or facts.shared is None:
        return False
    memory = _device_memory_bytes()
    return memory is None or at_once * facts.bytes + _shared_bytes(facts.shared) <= memory


def _evaluation(eval_fn, facts, counted: bool, at_once: int):
    """``one(vec, budget)`` of a loop that takes a lane's evaluations in
    turn: its loss, or ``(loss, counters)`` where ``counted``. Called before
    the loop: what the evaluations share and the device can hold
    (:func:`_holds_shared`) is made here, once, and every evaluation is
    handed it; else every evaluation makes it, as before there was any."""
    one = facts.with_counters if counted else eval_fn
    if not _holds_shared(facts, at_once):
        return one
    shared = facts.shared()
    return lambda vec, budget: one(vec, budget, shared)


def _in_turn(eval_fn, n_rows: int, depth: int, mesh=None) -> bool:
    """Whether a bracket of ``depth`` rungs, the widest of ``n_rows`` lanes,
    is one loop over its evaluations (:func:`_sh_bracket_in_turn`)."""
    facts = getattr(eval_fn, "lane_facts", None)
    return (facts is not None and facts.traced_budget and mesh is None
            and depth > 1 and lanes_at_once(eval_fn, n_rows) == 1)


def init_draws(eval_fn, num_configs: Sequence[int]) -> int:
    """How often the program of one bracket of ``num_configs`` lanes a rung
    makes what a lane's evaluations share (the draw of its initial weights),
    by the two rules the program itself was built by (:func:`_in_turn`,
    :func:`_holds_shared`): a loop makes it once a turn, and once in all
    where it was handed it or takes one turn. The loops: the bracket's,
    where its lanes run in turn in one; else a rung's, whose turn is as
    many lanes as fit side by side (a rung that fits is one turn, a
    ``vmap``: one trace of the evaluation). A mesh changes nothing here:
    lanes that do not fit side by side are not sharded yet."""
    facts = eval_fn.lane_facts
    if _in_turn(eval_fn, int(num_configs[0]), len(num_configs)):
        loops = [(int(sum(num_configs)), 1)]
    else:
        at_once = [lanes_at_once(eval_fn, int(n)) for n in num_configs]
        loops = [(-(-int(n) // a), a) for n, a in zip(num_configs, at_once)]
    return sum(1 if turns == 1 or _holds_shared(facts, a) else turns for turns, a in loops)


def eval_lanes(eval_fn, vecs: jax.Array, budget: float, mesh=None,
               counters: Optional[list] = None) -> jax.Array:
    """A rung's losses ``f32[n]`` from its vectors ``f32[n, d]``: the one
    definition of how a rung evaluates the lanes of a stateless
    ``eval_fn``. Lanes that fit the device side by side are one ``vmap``;
    lanes that do not (:func:`lanes_at_once`) are taken in turn by
    ``jax.lax.map``, as many at once as fit; where that is one, and in a
    rung of one lane, the lane is traced unbatched. With ``counters`` a
    list and an ``eval_fn`` that counts on the device
    (``lane_facts.counters``), the rung's ``f32[n, len(counters)]`` is
    appended to it. Where the lanes are taken in turn, what they share
    (``lane_facts.shared``) is made once, before the loop
    (:func:`_evaluation`)."""
    facts = getattr(eval_fn, "lane_facts", None)
    counted = counters is not None and facts is not None and bool(facts.counters)
    one = ((lambda v: facts.with_counters(v, budget)) if counted
           else (lambda v: eval_fn(v, budget)))
    n = vecs.shape[0]
    at_once = lanes_at_once(eval_fn, n)
    if at_once < n and mesh is None:
        # lanes in turn: the loop would make what they share once a turn
        evaluation = _evaluation(eval_fn, facts, counted, at_once)
        one = lambda v: evaluation(v, budget)
    if facts is None or 1 < n <= at_once:
        out = jax.vmap(one)(vecs)
    elif mesh is not None and at_once < n:
        raise NotImplementedError(
            f"{n} lanes of {facts.bytes} bytes do not fit one device side by "
            "side, and lanes in turn are not sharded over a mesh yet")
    elif at_once == 1 or n == 1:
        # a lane that states its footprint is traced unbatched where it
        # runs alone, the single lane of a last rung too
        out = jax.lax.map(one, vecs)
    else:
        out = jax.lax.map(one, vecs, batch_size=at_once)
    if counted:
        out, counts = out
        counters.append(counts.astype(jnp.float32))
    return out.astype(jnp.float32)


def _sh_bracket_in_turn(eval_fn, vectors, num_configs, budgets, rank_key,
                        scores_for, lane_counters):
    """:func:`fused_sh_bracket` for lanes that run one at a time and take a
    traced budget: ONE loop over the bracket's evaluations, every rung's,
    so the program holds the lane once and not once a rung (a lane of a
    published block takes a minute to compile). Slot ``i`` evaluates row
    ``i`` of a queue of vectors at its rung's budget; after a rung's last
    slot the promotion of :func:`fused_sh_bracket`, the same arithmetic on
    the same arrays, fills the next rung's rows of the queue. What the
    evaluations share (``lane_facts.shared``) is made once, before the loop
    (:func:`_evaluation`): the loop's body reads it as an operand that no
    slot changes."""
    n0, n_rows = int(num_configs[0]), vectors.shape[0]
    widths = [n_rows] + [int(k) for k in num_configs[1:]]
    starts = np.concatenate([[0], np.cumsum(widths)])
    total, depth = int(starts[-1]), len(widths)
    rows = [slice(int(starts[s]), int(starts[s + 1])) for s in range(depth)]
    facts = eval_fn.lane_facts
    counted = lane_counters is not None and bool(facts.counters)
    stage_of = np.repeat(np.arange(depth), widths)
    # after slot i: nothing (0), or the promotion that follows rung s (s + 1)
    then = np.zeros(total, np.int32)
    then[starts[1:-1] - 1] = np.arange(1, depth)
    with jax.named_scope("hpb.train"):
        evaluation = _evaluation(eval_fn, facts, counted, 1)

    def promote(s, carry):
        """What follows rung ``s``: rank its survivors by their history
        and write rung ``s + 1``'s vectors, indices and local top-k."""
        queue, losses, counts, idx, tops = carry
        with jax.named_scope("hpb.promote"):
            history = []
            for r in range(s + 1):
                col = losses[rows[r]]
                for later in range(r + 1, s + 1):
                    col = col[tops[later - 1]]
                history.append(col)
            cur_idx = (jnp.arange(n_rows, dtype=jnp.int32) if s == 0
                       else idx[s - 1])
            is_pad = (cur_idx >= n0 if s == 0
                      else jnp.zeros_like(cur_idx, dtype=bool))
            _, top = jax.lax.top_k(
                -rank_key(scores_for(history, s), is_pad), widths[s + 1])
            top = jnp.sort(top)
            sel_idx = cur_idx[top]
            queue = jax.lax.dynamic_update_slice_in_dim(
                queue, vectors[sel_idx], rows[s + 1].start, axis=0)
            idx = idx[:s] + (sel_idx,) + idx[s + 1:]
            tops = tops[:s] + (top,) + tops[s + 1:]
        return queue, losses, counts, idx, tops

    def slot(i, carry):
        queue, losses, counts, idx, tops = carry
        budget = jnp.asarray(budgets, jnp.float32)[jnp.asarray(stage_of)[i]]
        with jax.named_scope("hpb.train"):
            loss = evaluation(queue[i], budget)
            if counted:
                loss, count = loss
                counts = counts.at[i].set(count.astype(jnp.float32))
        losses = losses.at[i].set(loss.astype(jnp.float32))
        carry = (queue, losses, counts, idx, tops)
        return jax.lax.switch(
            jnp.asarray(then)[i],
            [lambda c: c] + [lambda c, s=s: promote(s, c) for s in range(depth - 1)],
            carry)

    later = tuple(jnp.zeros((k,), jnp.int32) for k in widths[1:])
    queue = jnp.zeros((total, vectors.shape[1]), vectors.dtype).at[:n_rows].set(vectors)
    _, losses, counts, idx, _ = jax.lax.fori_loop(0, total, slot, (
        queue, jnp.zeros((total,), jnp.float32),
        jnp.zeros((total, len(facts.counters) if counted else 0), jnp.float32),
        later, later))
    if counted:
        lane_counters.extend(counts[r] for r in rows)
    return ([(jnp.arange(n0, dtype=jnp.int32), losses[:n0])]
            + [(idx[s - 1], losses[rows[s]]) for s in range(1, depth)])


def _shard_state(state, mesh, axis: str):
    """Naive per-leaf sharding of an ensemble state: every leaf's leading
    config axis stays distributed over ``axis`` (the SNIPPETS
    ``shard_params`` path — shard when divisible, else leave XLA free).
    A 2-D model x config layout via ``match_partition_rules``-style regex
    trees is deliberately NOT wired here yet (reserved for a real
    model-parallel mesh); one axis is the honest current scope."""
    if mesh is None:
        return state
    return jax.tree.map(lambda leaf: shard_rows(leaf, mesh, axis), state)


def fused_sh_bracket(
    eval_fn: Callable[[jax.Array, float], jax.Array],
    vectors: jax.Array,
    num_configs: Sequence[int],
    budgets: Sequence[float],
    rank_fn: Callable[[jax.Array, jax.Array, float], jax.Array] = None,
    mesh=None,
    axis: str = "config",
    stateful: "StatefulEval" = None,
    return_final_state: bool = False,
    lane_counters: Optional[list] = None,
) -> List[Tuple[jax.Array, jax.Array]]:
    """Trace one whole bracket. Returns per-stage ``(indices, losses)``
    where ``indices`` index the original (unpadded) stage-0 rows.

    ``vectors`` may carry extra padding rows beyond ``num_configs[0]`` (for
    mesh divisibility); they are evaluated but can never be promoted. Must
    run under ``jit`` (see :func:`make_fused_bracket_fn`).

    ``rank_fn(budgets_so_far f32[s+1], history f32[n_cur, s+1],
    final_budget) -> scores f32[n_cur]`` overrides the promotion scores
    (lower = better; NaN = never promote). Default: the current stage's raw
    losses — plain successive halving. ``FusedH2BO`` passes the power-law
    learning-curve extrapolation here.

    ``mesh``/``axis`` pin each stage's survivor batch to stay sharded over
    the config axis (:func:`shard_rows`) — bit-identical results (a
    constraint never changes values; a 1-device mesh is the unsharded
    program), but the rung reduction and survivor gather lower to ICI
    collectives instead of a single-device round-trip.

    ``stateful`` (a :class:`StatefulEval`, exclusive with ``eval_fn``)
    switches every stage to the warm-continuation protocol: stage 0 runs
    ``init_fn`` then ``step_fn(state, vecs, budgets[0], 0.0)``; stage ``s``
    gathers the surviving state leaves by the promotion's ``top`` indices
    and runs ``step_fn(state, vecs, budgets[s], budgets[s-1])`` — each lane
    trains only the INCREMENTAL budget, carrying its weights across rungs.
    State leaves keep the per-stage sharding constraints the loss batches
    get. ``return_final_state=True`` additionally returns the last stage's
    surviving state (``(stages, state)``) for callers that extract trained
    weights — the fused sweep itself leaves it device-internal.

    ``lane_counters`` (a list) receives, per stage and in stage order, the
    ``f32[n_s, k]`` that an ``eval_fn`` with device counters
    (:class:`LaneFacts`) counted beside its losses; it stays empty for
    every other evaluation.
    """
    if (eval_fn is None) == (stateful is None):
        raise ValueError(
            "provide exactly one evaluation seam: eval_fn (stateless) or "
            "stateful (StatefulEval warm continuation)"
        )
    if return_final_state and stateful is None:
        raise ValueError("return_final_state=True requires stateful")
    n0 = int(num_configs[0])
    n_rows = vectors.shape[0]
    if n_rows < n0:
        raise ValueError(f"need >= {n0} stage-0 vectors, got {n_rows}")

    # device phase names (obs.timeline.DEVICE_SCOPES): a stateless
    # evaluation is "the trainer" whole; a StatefulEval names its own
    # training and validation (workloads/ensemble.py)
    def eval_stage(vecs: jax.Array, budget: float) -> jax.Array:
        with jax.named_scope("hpb.train"):
            return eval_lanes(eval_fn, vecs, budget, mesh, lane_counters)

    def rank_key(scores: jax.Array, is_pad: jax.Array) -> jax.Array:
        key = jnp.where(jnp.isnan(scores), _CRASH_RANK, scores)
        return jnp.where(is_pad, jnp.inf, key)

    def scores_for(history_cols: List[jax.Array], s: int) -> jax.Array:
        """Promotion scores after stage ``s`` from the survivors' loss
        history ``[n_cur, s+1]``; crashed (NaN-loss) configs stay NaN."""
        hist = jnp.stack(history_cols, axis=1)
        if rank_fn is None or s == 0:
            scores = hist[:, -1]
        else:
            scores = rank_fn(
                jnp.asarray(budgets[: s + 1], jnp.float32), hist,
                float(budgets[-1]),
            )
            # host H2BO parity (optimizers/h2bo.py): where extrapolation is
            # undefined (e.g. an earlier-stage crash left NaN in the
            # history), fall back to the raw current-stage loss ...
            scores = jnp.where(jnp.isnan(scores), hist[:, -1], scores)
            # ... and a crashed CURRENT stage dominates any extrapolation
            scores = jnp.where(jnp.isnan(hist[:, -1]), jnp.nan, scores)
        return scores

    vectors = shard_rows(vectors, mesh, axis)
    if _in_turn(eval_fn, n_rows, len(num_configs), mesh):
        return _sh_bracket_in_turn(eval_fn, vectors, num_configs, budgets,
                                   rank_key, scores_for, lane_counters)
    state = None
    if stateful is not None:
        # one lane per row (padding rows train too — they can never be
        # promoted, so their lanes are dead weight the mesh alignment pays)
        state = _shard_state(stateful.init_fn(vectors), mesh, axis)
        state, losses0 = stateful.step_fn(
            state, vectors, float(budgets[0]), 0.0
        )
        losses0 = losses0.astype(jnp.float32)
    else:
        losses0 = eval_stage(vectors, float(budgets[0]))
    with jax.named_scope("hpb.promote"):
        cur_idx = jnp.arange(n_rows, dtype=jnp.int32)
        history = [losses0]  # per-stage losses of the CURRENT survivor set
        cur_key = rank_key(scores_for(history, 0), cur_idx >= n0)
        out = [(jnp.arange(n0, dtype=jnp.int32), losses0[:n0])]

    for s in range(1, len(num_configs)):
        k = int(num_configs[s])
        with jax.named_scope("hpb.promote"):
            _, top = jax.lax.top_k(-cur_key, k)
            top = jnp.sort(top)  # preserve original ordering among survivors
            sel_idx = cur_idx[top]
            sel_vecs = shard_rows(vectors[sel_idx], mesh, axis)
            if stateful is not None:
                # warm continuation: gather the SURVIVING lanes' live state
                # by the same local top-k indices the rank just promoted;
                # evicted lanes simply drop out of the gather
                state = _shard_state(
                    jax.tree.map(lambda leaf: leaf[top], state), mesh, axis
                )
        if stateful is not None:
            # ... then train only the incremental budget from where the
            # survivors left off
            state, losses_s = stateful.step_fn(
                state, sel_vecs, float(budgets[s]), float(budgets[s - 1])
            )
            losses_s = losses_s.astype(jnp.float32)
        else:
            losses_s = eval_stage(sel_vecs, float(budgets[s]))
        with jax.named_scope("hpb.promote"):
            cur_idx = sel_idx
            history = [col[top] for col in history] + [losses_s]
            cur_key = rank_key(
                scores_for(history, s), jnp.zeros_like(sel_idx, dtype=bool)
            )
        out.append((cur_idx, losses_s))
    if return_final_state:
        return out, state
    return out


def _pack_stages(stages):
    """Concatenate per-stage (idx, losses) into two flat arrays — a single
    pair of device->host transfers instead of two per stage (the transfer
    count, not bytes, dominates on high-latency links)."""
    return (
        jnp.concatenate([s[0] for s in stages]),
        jnp.concatenate([s[1] for s in stages]),
    )


def _unpack_stages(packed, num_configs):
    # one device_get over the pair: both transfers issue together instead of
    # the second blocking behind the first (round-trips dominate on
    # high-latency links)
    idx_flat, loss_flat = jax.device_get(tuple(packed))
    note_transfer("d2h", idx_flat.nbytes + loss_flat.nbytes, buffers=2)
    out, off = [], 0
    for k in num_configs:
        out.append((idx_flat[off:off + k], loss_flat[off:off + k]))
        off += k
    return out


#: process-wide compiled-bracket cache: optimizer/executor instances come
#: and go (warmups, repeated runs), but a (objective, bracket shape, mesh)
#: combination should compile exactly once per process. Bounded so misses
#: from throwaway closures cannot pin datasets/executables forever.
from hpbandster_tpu.utils.lru import LRUCache as _LRUCache

_FUSED_FN_CACHE: _LRUCache = _LRUCache(maxsize=64)


def make_fused_bracket_fn(
    eval_fn: Callable[[jax.Array, float], jax.Array],
    num_configs: Sequence[int],
    budgets: Sequence[float],
    mesh=None,
    axis: str = "config",
):
    """Compile a fused-bracket runner for one bracket shape.

    Returns ``fn(vectors[n0, d]) -> [(indices, losses), ...]``. With a mesh,
    the stage-0 batch is padded to the mesh size and sharded over ``axis``;
    XLA inserts the all-gathers the cross-shard top-k needs.
    """
    import numpy as np

    num_configs = tuple(int(n) for n in num_configs)
    budgets = tuple(float(b) for b in budgets)
    cache_key = (eval_fn, num_configs, budgets, mesh, axis)
    cached = _FUSED_FN_CACHE.get(cache_key)
    if cached is not None:
        return cached
    n0 = num_configs[0]

    def bracket(vectors: jax.Array):
        return _pack_stages(
            fused_sh_bracket(
                eval_fn, vectors, num_configs, budgets, mesh=mesh, axis=axis
            )
        )

    # donation contract (docs/perf_notes.md): the packed (idx, loss)
    # outputs cannot alias the [n0, d] vectors input, so donating it would
    # be a warning-only no-op — declined explicitly. The state-threading
    # donation lives where an alias exists (ops/sweep.py return_state).
    if mesh is None:
        jitted_plain = tracked_jit(
            bracket, name="fused_bracket", donate_argnums=()
        )

        def dispatch(vectors):
            """Launch the bracket; returns packed DEVICE arrays without
            blocking — callers may overlap several brackets before fetching."""
            note_transfer("h2d", int(getattr(vectors, "nbytes", 0)))
            return jitted_plain(vectors)

    else:
        from jax.sharding import NamedSharding, PartitionSpec

        m = int(np.prod(list(mesh.shape.values())))
        n_pad = ((n0 + m - 1) // m) * m
        shard = NamedSharding(mesh, PartitionSpec(axis))
        jitted = tracked_jit(
            bracket, name="fused_bracket_sharded", in_shardings=(shard,),
            donate_argnums=(),
        )

        def dispatch(vectors):
            vectors = np.asarray(vectors, np.float32)
            if vectors.shape[0] != n0:
                raise ValueError(
                    f"expected {n0} stage-0 vectors, got {vectors.shape[0]}"
                )
            if n_pad != n0:
                vectors = np.concatenate(
                    [vectors, np.zeros((n_pad - n0, vectors.shape[1]), np.float32)]
                )
            note_transfer("h2d", vectors.nbytes)
            from hpbandster_tpu.parallel.mesh import is_multiprocess_mesh

            if is_multiprocess_mesh(mesh):
                # multiprocess meshes reject raw numpy against a sharded
                # in_sharding — build the global array explicitly (every
                # rank holds identical rows), like _BucketRunner.dispatch
                host = vectors
                vectors = jax.make_array_from_callback(
                    host.shape, shard, lambda idx: host[idx]
                )
            return jitted(vectors)

    def runner(vectors):
        return _unpack_stages(dispatch(vectors), num_configs)

    runner.dispatch = dispatch
    _FUSED_FN_CACHE[cache_key] = runner
    return runner
