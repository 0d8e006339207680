"""Mixed-type kernel-density estimation + TPE-style proposal, in pure JAX.

Re-implements the model math of the reference's BOHB config generator
(SURVEY.md §2 "BOHB config generator (KDE)" and §3.4) — which there is a
Python loop over ``statsmodels.KDEMultivariate`` pdf calls — as jittable,
vmappable array kernels:

* product kernels per statsmodels convention: Gaussian for continuous dims,
  Aitchison–Aitken for unordered categoricals, Wang–van Ryzin for ordinals;
* normal-reference ("Scott/Silverman") bandwidths;
* truncated-normal / keep-or-resample candidate sampling around good points;
* the ``l(x)/g(x)`` acquisition maximized over ``num_samples`` candidates.

Shapes are static: observation sets are padded to a fixed capacity with a
0/1 mask, so a growing observation history causes at most ``log2`` many
recompilations. A whole stage of proposals is one ``vmap`` over keys — this
is the batched path the rebuild's north star asks for (SURVEY.md §0).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.scipy.special import logsumexp, ndtr, ndtri

from hpbandster_tpu.obs.runtime import tracked_jit

__all__ = [
    "KDE",
    "LOG_PDF_FLOOR",
    "normal_reference_bandwidths",
    "kde_logpdf",
    "sample_around",
    "propose",
    "propose_batch",
    "propose_batch_seeded_scored",
    "impute_conditional_masked",
    "fit_kde_pair_masked",
    "refit_propose_batch_seeded",
]

#: reference clips pdf values at 1e-32 before the ratio (SURVEY.md §3.4)
LOG_PDF_FLOOR = math.log(1e-32)

_LOG_SQRT_2PI = 0.5 * math.log(2.0 * math.pi)


class KDE(NamedTuple):
    """A fitted mixed-type KDE over unit-hypercube observation vectors.

    ``data`` is ``f32[capacity, d]`` (imputed — no NaNs), ``mask`` is
    ``f32[capacity]`` with 1 for real observations, ``bw`` is ``f32[d]``.
    """

    data: jax.Array
    mask: jax.Array
    bw: jax.Array


def _discrete_bw_cap(cards: jax.Array) -> jax.Array:
    """Aitchison–Aitken lambda must stay below (k-1)/k; continuous dims uncapped."""
    cards_f = jnp.maximum(cards.astype(jnp.float32), 2.0)
    cap = (cards_f - 1.0) / cards_f
    return jnp.where(cards > 0, cap, jnp.inf)


def normal_reference_bandwidths(
    data: jax.Array,
    mask: jax.Array,
    cards: jax.Array,
    min_bandwidth: float = 1e-3,
) -> jax.Array:
    """Per-dim normal-reference rule: ``1.06 * sigma_j * n^(-1/(d+4))``.

    Matches statsmodels' ``bw='normal_reference'`` default that the reference
    relies on, with the reference's ``min_bandwidth`` floor applied to every
    dim and the Aitchison–Aitken cap applied to discrete dims.

    Constant derivation (VERDICT r1 "missing #2"): the asymptotically
    optimal Gaussian-reference constant is ``(4/3)^(1/5) ≈ 1.05922`` for
    d=1; statsmodels' ``_normal_reference`` hardcodes the ROUNDED value
    ``C = 1.06`` and applies it for every d with ``np.std`` (ddof=0) and
    ``n^(-1/(d+4))``. We match statsmodels bit-for-bit, not the theory:
    **1.06**, population sigma, same exponent.
    """
    data = jnp.asarray(data, jnp.float32)
    mask = jnp.asarray(mask, jnp.float32)
    d = data.shape[-1]
    n = jnp.maximum(mask.sum(), 1.0)
    mean = (data * mask[:, None]).sum(0) / n
    var = (jnp.square(data - mean) * mask[:, None]).sum(0) / n
    sigma = jnp.sqrt(jnp.maximum(var, 0.0))
    bw = 1.06 * sigma * n ** (-1.0 / (4.0 + d))
    bw = jnp.clip(bw, min_bandwidth, _discrete_bw_cap(cards))
    return bw


def _per_dim_log_kernels(
    x: jax.Array,
    data: jax.Array,
    bw: jax.Array,
    vartypes: jax.Array,
    cards: jax.Array,
) -> jax.Array:
    """log kernel value for each (datum, dim) pair; shape ``[capacity, d]``.

    vartypes codes: 0 continuous (Gaussian), 1 unordered (Aitchison–Aitken),
    2 ordered (Wang–van Ryzin) — see space.VARTYPE_CODES.
    """
    diff = x[None, :] - data  # [cap, d]
    bw = jnp.clip(bw, 1e-10, None)

    # Gaussian, normalized
    log_c = -0.5 * jnp.square(diff / bw) - jnp.log(bw) - _LOG_SQRT_2PI

    same = jnp.abs(diff) < 0.5  # discrete dims hold integer codes
    lam = jnp.clip(bw, 1e-10, 1.0 - 1e-7)
    km1 = jnp.maximum(cards.astype(jnp.float32) - 1.0, 1.0)

    # Aitchison–Aitken: 1-lam if match else lam/(k-1)
    log_u = jnp.where(same, jnp.log1p(-lam), jnp.log(lam) - jnp.log(km1))

    # Wang–van Ryzin: 1-lam if match else 0.5*(1-lam)*lam^|x-xi|
    log_o = jnp.where(
        same,
        jnp.log1p(-lam),
        math.log(0.5) + jnp.log1p(-lam) + jnp.abs(diff) * jnp.log(lam),
    )

    vt = vartypes[None, :]
    return jnp.where(vt == 0, log_c, jnp.where(vt == 1, log_u, log_o))


def kde_logpdf(
    x: jax.Array,
    kde: KDE,
    vartypes: jax.Array,
    cards: jax.Array,
) -> jax.Array:
    """Mixture log-density of one point under the product-kernel KDE."""
    log_k = _per_dim_log_kernels(x, kde.data, kde.bw, vartypes, cards)  # [cap, d]
    per_datum = log_k.sum(-1)  # [cap]
    log_w = jnp.where(kde.mask > 0, 0.0, -jnp.inf)
    n = jnp.maximum(kde.mask.sum(), 1.0)
    return logsumexp(per_datum + log_w) - jnp.log(n)


def _truncnorm_unit(key: jax.Array, mean: jax.Array, sd: jax.Array) -> jax.Array:
    """Truncated-normal sample on [0, 1] via inverse-CDF (vectorized over dims)."""
    sd = jnp.clip(sd, 1e-6, None)
    a = ndtr((0.0 - mean) / sd)
    b = ndtr((1.0 - mean) / sd)
    u = jax.random.uniform(key, mean.shape, minval=a, maxval=b)
    u = jnp.clip(u, 1e-7, 1.0 - 1e-7)
    return jnp.clip(mean + sd * ndtri(u), 0.0, 1.0)


def sample_around(
    key: jax.Array,
    datum: jax.Array,
    bw: jax.Array,
    vartypes: jax.Array,
    cards: jax.Array,
    bandwidth_factor: float = 3.0,
    min_bandwidth: float = 1e-3,
) -> jax.Array:
    """One BOHB candidate: perturb a good observation per-dim.

    Continuous dims: truncnorm(mean=datum, sd=bw*bandwidth_factor) on [0,1];
    discrete dims: keep the datum's value w.p. (1-bw), else uniform over the
    other choices — the reference's sampling scheme (SURVEY.md §3.4).
    """
    k_cont, k_keep, k_cat = jax.random.split(key, 3)
    sd = jnp.clip(bw * bandwidth_factor, min_bandwidth, None)
    cont = _truncnorm_unit(k_cont, datum, sd)

    lam = jnp.clip(bw, 0.0, 1.0 - 1e-7)
    keep = jax.random.uniform(k_keep, datum.shape) >= lam
    cards_safe = jnp.maximum(cards, 1)
    rand_choice = jax.random.uniform(k_cat, datum.shape) * cards_safe.astype(jnp.float32)
    rand_choice = jnp.clip(jnp.floor(rand_choice), 0, cards_safe - 1).astype(jnp.float32)
    disc = jnp.where(keep, datum, rand_choice)

    return jnp.where(vartypes == 0, cont, disc)


@partial(tracked_jit, static_argnames=("num_samples",))
def propose(
    key: jax.Array,
    good: KDE,
    bad: KDE,
    vartypes: jax.Array,
    cards: jax.Array,
    num_samples: int = 64,
    bandwidth_factor: float = 3.0,
    min_bandwidth: float = 1e-3,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One BOHB proposal: the best of ``num_samples`` candidates by l(x)/g(x).

    Returns ``(best_vector, candidates, scores)``; scores are
    ``log l(x) - log g(x)`` with both log-densities floored at
    ``LOG_PDF_FLOOR`` exactly like the reference's ``max(1e-32, pdf)`` clamp.
    """
    # device phase names (obs.timeline.DEVICE_SCOPES): generating the
    # candidates is sampling, ranking them is the scorer
    with jax.named_scope("hpb.sample"):
        k_idx, k_samp = jax.random.split(key)
        logits = jnp.where(good.mask > 0, 0.0, -jnp.inf)
        idx = jax.random.categorical(k_idx, logits, shape=(num_samples,))
        data = good.data[idx]  # [S, d]

        keys = jax.random.split(k_samp, num_samples)
        cands = jax.vmap(
            lambda k, x: sample_around(
                k, x, good.bw, vartypes, cards, bandwidth_factor,
                min_bandwidth,
            )
        )(keys, data)

    with jax.named_scope("hpb.kde_score"):
        lg = jax.vmap(lambda c: kde_logpdf(c, good, vartypes, cards))(cands)
        lb = jax.vmap(lambda c: kde_logpdf(c, bad, vartypes, cards))(cands)
        scores = (
            jnp.maximum(lg, LOG_PDF_FLOOR) - jnp.maximum(lb, LOG_PDF_FLOOR)
        )
        best = cands[jnp.argmax(scores)]
    return best, cands, scores


def generate_candidates(
    key: jax.Array,
    good: KDE,
    vartypes: jax.Array,
    cards: jax.Array,
    total: int,
    bandwidth_factor: float = 3.0,
    min_bandwidth: float = 1e-3,
) -> jax.Array:
    """``total`` perturbed-good-point candidates, ``f32[total, d]`` — the
    generation half of the BOHB proposal, shared by the seeded host entry
    point and the fused-sweep tracer so the sampling scheme has one home."""
    with jax.named_scope("hpb.sample"):
        k_idx, k_samp = jax.random.split(key)
        logits = jnp.where(good.mask > 0, 0.0, -jnp.inf)
        idx = jax.random.categorical(k_idx, logits, shape=(total,))
        keys = jax.random.split(k_samp, total)
        return jax.vmap(
            lambda k, x: sample_around(
                k, x, good.bw, vartypes, cards, bandwidth_factor,
                min_bandwidth,
            )
        )(keys, good.data[idx])


@partial(tracked_jit, static_argnames=("n", "num_samples"))
def generate_candidates_seeded(
    seed: jax.Array,
    good: KDE,
    vartypes: jax.Array,
    cards: jax.Array,
    n: int,
    num_samples: int = 64,
    bandwidth_factor: float = 3.0,
    min_bandwidth: float = 1e-3,
) -> jax.Array:
    """All ``n * num_samples`` candidates for a stage of proposals,
    flattened to ``f32[n*num_samples, d]`` — :func:`generate_candidates`
    keyed from one scalar seed (one scalar transfer on high-latency links),
    so an external scorer (e.g. ``ops.pallas_kde``) can do the scoring half."""
    return generate_candidates(
        jax.random.key(seed), good, vartypes, cards, n * num_samples,
        bandwidth_factor, min_bandwidth,
    )


@partial(tracked_jit, static_argnames=("n", "num_samples"))
def propose_batch_seeded_scored(
    seed: jax.Array,
    good: KDE,
    bad: KDE,
    vartypes: jax.Array,
    cards: jax.Array,
    n: int,
    num_samples: int = 64,
    bandwidth_factor: float = 3.0,
    min_bandwidth: float = 1e-3,
) -> Tuple[jax.Array, jax.Array]:
    """Like :func:`propose_batch` but derives the key batch on-device from
    a single uint32 seed — one scalar transfer instead of an [n, 2] key
    upload — and
    also returns each proposal's winning acquisition score:
    ``(f32[n, d], f32[n])`` where the score is the selected candidate's
    ``log l(x) - log g(x)`` (the max over the same score vector the
    argmax already computed), so the audit trail (``obs/audit.py``)
    costs one extra [n] fetch, not a different draw."""
    keys = jax.random.split(jax.random.key(seed), n)

    def one(k):
        best, _, scores = propose(
            k, good, bad, vartypes, cards, num_samples, bandwidth_factor,
            min_bandwidth,
        )
        return best, jnp.max(scores)

    return jax.vmap(one)(keys)


def propose_batch_seeded(
    seed: jax.Array,
    good: KDE,
    bad: KDE,
    vartypes: jax.Array,
    cards: jax.Array,
    n: int,
    num_samples: int = 64,
    bandwidth_factor: float = 3.0,
    min_bandwidth: float = 1e-3,
) -> jax.Array:
    """:func:`propose_batch_seeded_scored` without the scores — one
    proposal body to maintain (the discarded per-proposal max is trivial
    next to the candidate scoring it reuses)."""
    return propose_batch_seeded_scored(
        seed, good, bad, vartypes, cards, n, num_samples, bandwidth_factor,
        min_bandwidth,
    )[0]


def impute_conditional_masked(
    key: jax.Array, data: jax.Array, cards: jax.Array
) -> jax.Array:
    """Device twin of ``BOHBKDE.impute_conditional_data``: every NaN
    (inactive-dim) entry borrows the value of a uniformly random *active*
    row of the same column; columns with no active rows fall back to a
    random category (discrete) or uniform draw (continuous).

    O(n·d): donors are drawn by inverse-CDF over each column's running
    active count (no n x n materialization). Lived in ``ops/sweep.py``
    until the in-trace refit op below needed it too — the sweep imports it
    from here now, so the imputation scheme has exactly one definition."""
    n, d = data.shape
    isnan = jnp.isnan(data)
    active = (~isnan).astype(jnp.int32)
    cnt = jnp.cumsum(active, axis=0)  # [n, d] running donor count
    total = cnt[-1, :]  # [d]
    k_pick, k_fb = jax.random.split(key)
    u = jax.random.uniform(k_pick, (n, d))
    # r-th donor (1-indexed) per entry; searchsorted over the column's
    # non-decreasing count finds its row
    r = jnp.floor(u * jnp.maximum(total, 1)[None, :]).astype(jnp.int32) + 1
    rows = jax.vmap(
        lambda c, rr: jnp.searchsorted(c, rr, side="left"), in_axes=(1, 1),
        out_axes=1,
    )(cnt, r)
    donated = jnp.take_along_axis(data, jnp.clip(rows, 0, n - 1), axis=0)

    u_fb = jax.random.uniform(k_fb, (n, d))
    cards_f = jnp.maximum(cards.astype(jnp.float32), 1.0)
    disc = jnp.clip(jnp.floor(u_fb * cards_f), 0, cards_f - 1)
    fallback = jnp.where(cards[None, :] > 0, disc, u_fb)

    fill = jnp.where((total > 0)[None, :], donated, fallback)
    return jnp.where(isnan, fill, data)


def fit_kde_pair_masked(
    vecs: jax.Array,
    losses: jax.Array,
    count: jax.Array,
    n_good: jax.Array,
    n_bad: jax.Array,
    cards: jax.Array,
    min_bandwidth: float,
    impute_key=None,
) -> Tuple[KDE, KDE]:
    """Traced-count good/bad KDE fit over a full-capacity buffer.

    ``vecs``/``losses`` are FULL capacity buffers (``f32[C, d]`` /
    ``f32[C]``, empty slots carrying ``+inf`` loss); ``count`` / ``n_good``
    / ``n_bad`` are traced i32 scalars. Split membership is a rank mask
    over the loss-sorted buffer instead of a static slice — every KDE
    primitive downstream (bandwidths, log-pdf, candidate sampling, the
    Pallas scorer) is mask-weighted, so the fitted model is the same; only
    observation COUNTS stay out of the compiled program. This is the one
    definition behind both the dynamic-count fused sweep
    (``ops/sweep.py``) and the in-trace refit+propose op below.
    """
    def mk(data: jax.Array, mask: jax.Array) -> KDE:
        mask = mask.astype(jnp.float32)
        bw = normal_reference_bandwidths(data, mask, cards, min_bandwidth)
        return KDE(data, mask, bw)

    with jax.named_scope("hpb.kde_fit"):
        cap = vecs.shape[0]
        order = jnp.argsort(losses, stable=True)  # +inf pads sort last
        sorted_v = vecs[order]
        rank = jnp.arange(cap, dtype=jnp.int32)
        good_mask = rank < n_good
        bad_mask = (rank >= count - n_bad) & (rank < count)
        if impute_key is not None:
            # conditional spaces: donor-impute each split side exactly like
            # the static path, with non-members NaN'd out so they neither
            # donate nor constrain (their filled values are then masked
            # from the fit)
            kg, kb = jax.random.split(impute_key)
            good_data = impute_conditional_masked(
                kg, jnp.where(good_mask[:, None], sorted_v, jnp.nan), cards
            )
            bad_data = impute_conditional_masked(
                kb, jnp.where(bad_mask[:, None], sorted_v, jnp.nan), cards
            )
        else:
            good_data = bad_data = sorted_v
        return mk(good_data, good_mask), mk(bad_data, bad_mask)


# the observation buffers are rebuilt host-side per refit and never reread
# by the caller, but they cannot alias the [n, d] proposal outputs, so
# donation buys nothing here — declined explicitly (jit-donation contract,
# docs/perf_notes.md)
@partial(
    tracked_jit, static_argnames=("n", "num_samples"), donate_argnums=()
)
def refit_propose_batch_seeded(
    seed: jax.Array,
    obs_v: jax.Array,
    obs_l: jax.Array,
    count: jax.Array,
    n_good: jax.Array,
    n_bad: jax.Array,
    vartypes: jax.Array,
    cards: jax.Array,
    n: int,
    num_samples: int = 64,
    bandwidth_factor: float = 3.0,
    min_bandwidth: float = 1e-3,
    impute_seed: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """KDE refit + a whole stage of proposals in ONE device dispatch.

    The host path (``models/bohb_kde.py`` default) fits the KDE pair in
    numpy, uploads the fitted arrays, then runs the proposal kernel — the
    refit state round-trips through the host every rung. This op keeps it
    in-trace: raw observation buffers go up (``f32[C, d]`` vectors,
    ``f32[C]`` losses, ``+inf`` in empty slots), the good/bad split,
    bandwidths, candidate generation, scoring and the per-proposal argmax
    all happen inside one compiled program, and only the selected
    ``(f32[n, d], f32[n])`` proposals + scores come back.

    ``count``/``n_good``/``n_bad`` are traced i32 (the caller runs the
    reference's split arithmetic), so observation growth recompiles only
    when the buffer capacity doubles. Pass ``impute_seed`` on conditional
    spaces to donor-impute NaN dims in-trace (a distinct RNG consumer from
    the host path's ``rng.choice`` — documented, like the dynamic sweep
    tier).
    """
    impute_key = (
        None if impute_seed is None else jax.random.key(impute_seed)
    )
    good, bad = fit_kde_pair_masked(
        obs_v, obs_l, count, n_good, n_bad, cards, min_bandwidth,
        impute_key=impute_key,
    )
    keys = jax.random.split(jax.random.key(seed), n)

    def one(k):
        best, _, scores = propose(
            k, good, bad, vartypes, cards, num_samples, bandwidth_factor,
            min_bandwidth,
        )
        return best, jnp.max(scores)

    return jax.vmap(one)(keys)


@partial(tracked_jit, static_argnames=("num_samples",))
def propose_batch(
    keys: jax.Array,
    good: KDE,
    bad: KDE,
    vartypes: jax.Array,
    cards: jax.Array,
    num_samples: int = 64,
    bandwidth_factor: float = 3.0,
    min_bandwidth: float = 1e-3,
) -> jax.Array:
    """A whole stage of proposals in one dispatch: vmap of :func:`propose`.

    ``keys`` is ``[n, 2]`` (uint32 key batch); returns ``f32[n, d]``. This is
    the vmapped replacement for the reference's one-proposal-per-RPC loop.
    """
    return jax.vmap(
        lambda k: propose(
            k, good, bad, vartypes, cards, num_samples, bandwidth_factor, min_bandwidth
        )[0]
    )(keys)
