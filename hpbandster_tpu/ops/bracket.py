"""Pure successive-halving / HyperBand bracket arithmetic.

The reference scatters this math across ``optimizers/hyperband.py`` /
``optimizers/bohb.py`` (ladder + bracket sizing) and
``optimizers/iterations/successivehalving.py`` (the promotion rule) — see
SURVEY.md §2 rows "HyperBand optimizer" and "SuccessiveHalving iteration".
Here it lives as standalone pure functions: host-side schedule construction
(static shapes, plain numpy) and a jittable / vmappable promotion kernel.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "max_sh_iterations",
    "budget_ladder",
    "BracketPlan",
    "hyperband_bracket",
    "hyperband_schedule",
    "mesh_aligned_plan",
    "sh_promotion_mask",
    "sh_promotion_mask_compiled",
    "sh_promotion_mask_np",
    "sh_resample_mask",
    "pareto_rank",
    "pareto_rank_np",
    "pareto_promotion_mask",
    "pareto_promotion_mask_np",
    "power_law_extrapolate",
]


def max_sh_iterations(min_budget: float, max_budget: float, eta: float) -> int:
    """Number of distinct successive-halving bracket shapes.

    Reference: ``max_SH_iter = floor(log(max/min)/log(eta)) + 1``
    (SURVEY.md §3.1, BOHB.__init__).
    """
    if not (max_budget > 0 and min_budget > 0 and max_budget >= min_budget):
        raise ValueError(f"need 0 < min_budget <= max_budget, got [{min_budget}, {max_budget}]")
    if eta <= 1:
        raise ValueError(f"need eta > 1, got {eta}")
    # epsilon-robust floor: log(243)/log(3) = 4.999999999999999 in f64, and a
    # bare floor would silently drop the lowest rung of an exact ladder
    ratio = np.log(max_budget / min_budget) / np.log(eta)
    return int(np.floor(ratio + 1e-9)) + 1


def budget_ladder(min_budget: float, max_budget: float, eta: float) -> np.ndarray:
    """Ascending geometric budget ladder ending exactly at ``max_budget``.

    Reference: ``budgets = max_budget * eta ** (-linspace(max_SH_iter-1, 0))``.
    """
    k = max_sh_iterations(min_budget, max_budget, eta)
    return max_budget * np.power(float(eta), -np.arange(k - 1, -1, -1, dtype=np.float64))


class BracketPlan(NamedTuple):
    """Static description of one successive-halving bracket."""

    #: configs alive at each stage, e.g. [9, 3, 1]
    num_configs: Tuple[int, ...]
    #: budget evaluated at each stage (same length)
    budgets: Tuple[float, ...]

    @property
    def n_stages(self) -> int:
        return len(self.num_configs)

    @property
    def total_evaluations(self) -> int:
        return int(sum(self.num_configs))


def hyperband_bracket(
    iteration_index: int, min_budget: float, max_budget: float, eta: float
) -> BracketPlan:
    """The bracket HyperBand runs at global iteration ``iteration_index``.

    Reference arithmetic (SURVEY.md §2 "HyperBand optimizer"):
    ``s = max_SH_iter - 1 - (i % max_SH_iter)``;
    ``n0 = ceil(max_SH_iter / (s+1) * eta**s)``;
    ``ns = [max(floor(n0 * eta**(-j)), 1) for j in 0..s]``;
    budgets are the last ``s+1`` rungs of the ladder.
    """
    k = max_sh_iterations(min_budget, max_budget, eta)
    ladder = budget_ladder(min_budget, max_budget, eta)
    s = k - 1 - (iteration_index % k)
    n0 = int(math.ceil((k / (s + 1)) * eta**s))
    ns = tuple(max(int(n0 * eta ** (-j)), 1) for j in range(s + 1))
    budgets = tuple(float(b) for b in ladder[-(s + 1):])
    return BracketPlan(num_configs=ns, budgets=budgets)


def hyperband_schedule(
    n_iterations: int, min_budget: float, max_budget: float, eta: float
) -> Tuple[BracketPlan, ...]:
    """Plans for ``n_iterations`` consecutive HyperBand iterations."""
    return tuple(
        hyperband_bracket(i, min_budget, max_budget, eta) for i in range(n_iterations)
    )


def mesh_aligned_plan(
    n_configs: int,
    min_budget: float,
    max_budget: float,
    eta: float,
    mesh_size: int = 1,
) -> BracketPlan:
    """One deep successive-halving bracket sized for a sharded mesh.

    The 100k-1M tier's schedule: stage 0 starts at ``n_configs`` and each
    rung keeps ``1/eta`` of the survivors, every stage count rounded UP to
    a multiple of ``mesh_size`` (floor ``mesh_size``) so the config axis
    shards evenly at every rung — the sharded sampler and the per-stage
    sharding constraints both need divisible widths. Budgets are the full
    ``min_budget..max_budget`` geometric ladder. The roundup waste per
    stage is at most ``mesh_size - 1`` rows — negligible against 100k+
    rows, and zero when ``n_configs`` and ``eta`` are powers of two on a
    pow2 mesh (the amortization the pow2 bucket geometry already relies
    on).
    """
    m = max(int(mesh_size), 1)
    ladder = budget_ladder(min_budget, max_budget, eta)
    depth = len(ladder)
    ns = []
    for j in range(depth):
        n = max(int(n_configs * float(eta) ** (-j)), 1)
        ns.append(max(((n + m - 1) // m) * m, m))
    # roundup of a decreasing profile can create equal neighbors but must
    # never create an INCREASING step
    for j in range(depth - 2, -1, -1):
        ns[j] = max(ns[j], ns[j + 1])
    return BracketPlan(
        num_configs=tuple(ns), budgets=tuple(float(b) for b in ladder)
    )


def sh_promotion_mask(losses: jax.Array, k) -> jax.Array:
    """The successive-halving promotion rule as a pure jittable kernel.

    ``losses`` is ``f32[n]`` for one finished stage (NaN = crashed config);
    returns ``bool[n]`` marking the ``k`` best (lowest-loss) configs.

    Reference rule (SURVEY.md §3.3): ``ranks = argsort(argsort(losses));
    advance = ranks < k`` — NaNs (crashed runs) rank last because they are
    replaced by ``+inf`` before ranking, matching the reference's
    crashed-config-never-promoted behavior. ``vmap`` over a leading bracket
    axis batches many brackets' promotions into one dispatch.
    """
    losses = jnp.asarray(losses)
    clean = jnp.where(jnp.isnan(losses), jnp.inf, losses)
    ranks = jnp.argsort(jnp.argsort(clean))
    return ranks < k


#: process-wide compiled promotion kernel, built on first use. A plain
#: module-level jit would be fine for dispatch, but routing it through
#: ``obs.runtime.tracked_jit`` journals its (single, scalar-k) compile in
#: the same ledger as the fused brackets — the whole on-device promotion
#: tier accounted under one vocabulary.
_PROMOTION_JIT = None


def sh_promotion_mask_compiled():
    """The tracked-jit compilation of :func:`sh_promotion_mask` (lazy,
    one per process). ``k`` stays a traced scalar so every bracket width
    shares one executable — callers pass it as an ``i32`` array."""
    global _PROMOTION_JIT
    if _PROMOTION_JIT is None:
        from hpbandster_tpu.obs.runtime import tracked_jit

        # donation declined explicitly (docs/perf_notes.md): the bool[n]
        # mask output cannot alias the f32[n] losses input (dtype differs)
        _PROMOTION_JIT = tracked_jit(
            sh_promotion_mask, name="sh_promotion_mask", donate_argnums=()
        )
    return _PROMOTION_JIT


def sh_promotion_mask_np(losses: np.ndarray, k) -> np.ndarray:
    """Host (numpy) twin of :func:`sh_promotion_mask` — bit-identical
    semantics (NaN -> +inf, stable double-argsort ranking, rank < k).

    The Master's per-stage bookkeeping runs over a few dozen host floats; a
    device dispatch there costs a full accelerator round-trip to rank an
    81-element array. The jittable version
    stays the on-device rule inside fused brackets and vmapped sweeps.
    """
    # rank in float32, same as the device twin — float64 here would break
    # tie-handling parity with the fused on-device bracket on near-equal
    # losses (distinct in f64, tied after f32 rounding)
    losses = np.asarray(losses, dtype=np.float32)
    clean = np.where(np.isnan(losses), np.float32(np.inf), losses)
    ranks = np.argsort(np.argsort(clean, kind="stable"), kind="stable")
    return ranks < k


def pareto_rank(objectives: jax.Array) -> jax.Array:
    """Domination-count Pareto ranking, jittable: ``objectives f32[n, m]``
    (all minimized) -> ``i32[n]`` where rank 0 is the Pareto front.

    ``rank[j]`` counts the rows that dominate row ``j`` (all objectives
    <= and at least one <). A NaN in column 0 (the loss: a CRASHED
    config) invalidates its whole row — every entry becomes +inf, so a
    crashed config that happened to fail cheaply cannot ride its low
    measured cost onto the front and displace a healthy config from a
    promotion slot. A NaN in a later column alone (an unmeasured cost)
    only infs that entry: the row stays rankable by its finite loss.
    O(n^2 m) pairwise compare: the rung widths this ranks are
    bracket-sized (dozens to low thousands), far under the sort-based
    kernels' scale.
    """
    obj = jnp.asarray(objectives, jnp.float32)
    crashed = jnp.isnan(obj[:, 0])
    clean = jnp.where(
        jnp.isnan(obj) | crashed[:, None], jnp.inf, obj
    )
    # dominates[i, j]: row i dominates row j
    le = (clean[:, None, :] <= clean[None, :, :]).all(axis=-1)
    lt = (clean[:, None, :] < clean[None, :, :]).any(axis=-1)
    return (le & lt).sum(axis=0).astype(jnp.int32)


def pareto_promotion_mask(objectives: jax.Array, k) -> jax.Array:
    """Pareto-front top-``k`` promotion as a pure jittable kernel.

    ``objectives`` is ``f32[n, m]`` with column 0 the rung loss (NaN =
    crashed) and the remaining columns measured costs (NaN = unmeasured,
    treated as +inf). Selection order is (domination count, loss rank,
    row index) — Pareto fronts peel first, ties inside a front resolve
    by the loss column under the same f32 double-argsort ranking as
    :func:`sh_promotion_mask`, so the single-objective case degrades to
    exactly the successive-halving rule. Crashed rows (NaN loss) are
    NEVER promoted, whatever ``k`` — the same crash-safety contract as
    ``sh_promotion_mask``'s NaN -> +inf — and, because
    :func:`pareto_rank` infs a crashed row WHOLESALE, a config that
    crashed cheaply cannot occupy a front slot and displace a healthy
    config out of the top-k either.
    """
    obj = jnp.asarray(objectives, jnp.float32)
    loss = obj[:, 0]
    ranks = pareto_rank(obj)
    clean_loss = jnp.where(jnp.isnan(loss), jnp.inf, loss)
    loss_order = jnp.argsort(jnp.argsort(clean_loss))
    # lexicographic (pareto rank, loss rank) via two stable sorts
    # (secondary first, then primary over the permuted rows) — a
    # composite integer key `ranks * n + order` would overflow i32 near
    # n = 46341, and i64 is unavailable with x64 disabled
    by_loss = jnp.argsort(loss_order)
    final_perm = by_loss[jnp.argsort(ranks[by_loss])]
    positions = jnp.argsort(final_perm)
    return (positions < k) & ~jnp.isnan(loss)


def pareto_rank_np(objectives: np.ndarray) -> np.ndarray:
    """Host (numpy) twin of :func:`pareto_rank` — identical f32
    semantics, for the Master's host-side bracket bookkeeping."""
    obj = np.asarray(objectives, dtype=np.float32)
    crashed = np.isnan(obj[:, 0])
    clean = np.where(
        np.isnan(obj) | crashed[:, None], np.float32(np.inf), obj
    )
    le = (clean[:, None, :] <= clean[None, :, :]).all(axis=-1)
    lt = (clean[:, None, :] < clean[None, :, :]).any(axis=-1)
    return (le & lt).sum(axis=0).astype(np.int32)


def pareto_promotion_mask_np(objectives: np.ndarray, k) -> np.ndarray:
    """Host twin of :func:`pareto_promotion_mask` (stable argsorts, f32
    comparisons) — bit-identical masks to the device kernel."""
    obj = np.asarray(objectives, dtype=np.float32)
    loss = obj[:, 0]
    ranks = pareto_rank_np(obj)
    clean_loss = np.where(np.isnan(loss), np.float32(np.inf), loss)
    loss_order = np.argsort(
        np.argsort(clean_loss, kind="stable"), kind="stable"
    )
    # same two-stable-sort lexicographic selection as the device kernel
    # (overflow-free at any n, identical tie resolution)
    by_loss = np.argsort(loss_order, kind="stable")
    final_perm = by_loss[np.argsort(ranks[by_loss], kind="stable")]
    positions = np.argsort(final_perm, kind="stable")
    return (positions < k) & ~np.isnan(loss)


def power_law_extrapolate(
    budgets: jax.Array, losses: jax.Array, target_budget: float,
    floor: float = 1e-6,
) -> jax.Array:
    """Jittable twin of ``models.learning_curves.PowerLawModel.predict``,
    vectorized over configs: ``budgets f32[s]`` (ascending), ``losses
    f32[n, s]`` -> extrapolated loss at ``target_budget``, ``f32[n]``.

    Fallback semantics mirror the host model exactly: fewer than 3 points,
    non-positive residuals, all-increasing curves, or a positive slope fall
    back to the last observed value. The on-device H2BO promotion
    (``FusedH2BO``) ranks by these scores.
    """
    budgets = jnp.asarray(budgets, jnp.float32)
    losses = jnp.asarray(losses, jnp.float32)
    n, s = losses.shape
    last = losses[:, -1]
    if s < 3:
        return last

    y0, y1, y2 = losses[:, -3], losses[:, -2], losses[:, -1]
    denom = y0 + y2 - 2.0 * y1
    c_est = jnp.where(
        jnp.abs(denom) > 1e-12, (y0 * y2 - y1 * y1) / denom, -jnp.inf
    )
    ymin = losses.min(axis=1)
    # scale-aware floor (twin of PowerLawModel.predict): a fixed 1e-12 is
    # not representable next to f32 values of order 1
    floor_eff = jnp.maximum(floor, jnp.abs(ymin) * 1e-5)
    c = jnp.where(
        jnp.isfinite(c_est),
        jnp.minimum(c_est, ymin - floor_eff),
        ymin - floor_eff,
    )
    resid = losses - c[:, None]
    bad = (resid <= 0).any(axis=1) | (jnp.diff(losses, axis=1) > 0).all(axis=1)

    log_b = jnp.log(budgets)[None, :]
    log_r = jnp.log(jnp.maximum(resid, 1e-30))
    mb = log_b.mean(axis=1)
    mr = log_r.mean(axis=1)
    cov = ((log_b - mb[:, None]) * (log_r - mr[:, None])).mean(axis=1)
    var = jnp.maximum(((log_b - mb[:, None]) ** 2).mean(axis=1), 1e-30)
    slope = cov / var
    intercept = mr - slope * mb
    bad = bad | (slope > 0)
    pred = c + jnp.exp(intercept + slope * jnp.log(jnp.float32(target_budget)))
    return jnp.where(bad, last, pred)


def sh_resample_mask(
    losses: jax.Array, k, resampling_rate: float, key: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """SuccessiveResampling variant (SURVEY.md §2): promote only
    ``ceil(k * (1 - resampling_rate))`` survivors; the caller fills the rest of
    the next stage with fresh samples.

    Returns ``(promote_mask, n_resampled)``.
    """
    del key  # selection is deterministic; the resample draw happens upstream
    losses = jnp.asarray(losses)
    n_promote = jnp.maximum(
        jnp.ceil(k * (1.0 - resampling_rate)).astype(jnp.int32), 1
    )
    mask = sh_promotion_mask(losses, n_promote)
    return mask, jnp.asarray(k, jnp.int32) - n_promote
