"""Pallas TPU kernels for the BOHB acquisition scorer and bandwidth fit.

The proposal hot loop scores ``n_configs x num_samples`` candidates against
two mixed-type KDEs (good/bad) — for a 729-proposal stage with the default
64 samples that is ~47k candidates x 2 KDEs x up to 8192+ observations x d
dims of product-kernel work plus two logsumexps. The scorer kernel computes
one mixture log-density per call (Gaussian / Aitchison–Aitken /
Wang–van Ryzin selected per dim, matching ``ops.kde``); the acquisition
``max(lg, F) - max(lb, F)`` is two calls and one subtraction.

Layout notes (see /opt/skills/guides/pallas_guide.md):
* the grid is (candidate tiles, observation tiles): 128 candidate rows x
  up to 512 observation columns per program, the observation axis last
  and sequential, folded with a running max / running sum (online
  logsumexp) held in VMEM scratch — so the VMEM footprint is fixed by the
  tile, not by ``n_obs`` (every capacity ``pow2_capacities`` produces
  compiles, 256 through 16384 and beyond);
* observation matrices are passed TRANSPOSED (``[d, n_obs]``, d padded to
  the 8-row sublane tile) so each dim is one lane-aligned row broadcast
  against the candidate column;
* every per-dim scalar the kernels need (1/bw, log-normalizers, vartype
  code) is computed once outside the kernel and read from SMEM;
* the dim loop is a static Python unroll (d is small in HPO spaces);
* padding observations carry mask 0, padding candidates are sliced off.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from hpbandster_tpu.ops.kde import KDE, LOG_PDF_FLOOR

__all__ = [
    "pallas_score_candidates",
    "pallas_propose_batch",
    "pallas_propose_batch_seeded",
    "pallas_refit_propose_batch_seeded",
    "pallas_available",
]

_LOG_SQRT_2PI = 0.5 * math.log(2.0 * math.pi)
_TILE_S = 128
_TILE_N = 512
_LANE = 128
_SUBLANE = 8
#: running-max seed: finite (so ``exp(m_old - m_new)`` never sees
#: ``-inf - -inf``) and below any real log-kernel sum
_NEG_BIG = -1e30

#: rows of the per-dim scalar table the scorer reads from SMEM
(_P_INV_BW, _P_LOG_C0, _P_LOG_SAME, _P_LOG_U_DIFF, _P_LOG_O_BASE,
 _P_LOG_LAM, _P_VARTYPE, _P_LOG_N_EFF) = range(8)


def pallas_available() -> bool:
    """Mosaic compiles Pallas kernels for the TPU backend only; anywhere
    else a caller that asks for them gets the Pallas interpreter."""
    return jax.default_backend() == "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _logpdf_kernel(par_ref, cand_ref, dataT_ref, mask_ref,
                   out_ref, m_ref, s_ref):
    """One (candidate tile, observation tile) step of the mixture
    log-density: accumulate the tile's masked logsumexp into the running
    (max, sum) scratch; the last observation tile writes the result."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():  # noqa: ANN202 — pallas when-block
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        s_ref[:] = jnp.zeros_like(s_ref)

    acc = jnp.zeros((cand_ref.shape[0], dataT_ref.shape[1]), jnp.float32)
    for j in range(par_ref.shape[1]):  # static unroll over real dims
        diff = cand_ref[:, j:j + 1] - dataT_ref[j:j + 1, :]  # [TS, TN]
        adiff = jnp.abs(diff)
        same = adiff < 0.5  # discrete dims hold integer codes
        log_same = par_ref[_P_LOG_SAME, j]
        log_c = (
            -0.5 * jnp.square(diff * par_ref[_P_INV_BW, j])
            + par_ref[_P_LOG_C0, j]
        )
        log_u = jnp.where(same, log_same, par_ref[_P_LOG_U_DIFF, j])
        log_o = jnp.where(
            same, log_same,
            par_ref[_P_LOG_O_BASE, j] + adiff * par_ref[_P_LOG_LAM, j],
        )
        vt = par_ref[_P_VARTYPE, j]
        acc = acc + jnp.where(
            vt == 0.0, log_c, jnp.where(vt == 1.0, log_u, log_o)
        )
    ll = jnp.where(mask_ref[0:1, :] > 0.0, acc, -jnp.inf)
    m_old = m_ref[:]
    m_new = jnp.maximum(m_old, jnp.max(ll, axis=1, keepdims=True))
    s_ref[:] = s_ref[:] * jnp.exp(m_old - m_new) + jnp.sum(
        jnp.exp(ll - m_new), axis=1, keepdims=True
    )
    m_ref[:] = m_new

    @pl.when(k == pl.num_programs(1) - 1)
    def _finish():  # noqa: ANN202 — pallas when-block
        m = m_ref[:]
        # an all-masked mixture keeps the seed max; report it like the
        # XLA path's logsumexp-of-nothing floor instead of -1e30
        m_safe = jnp.where(m > _NEG_BIG, m, 0.0)
        out_ref[:] = (
            m_safe + jnp.log(jnp.maximum(s_ref[:], 1e-38))
            - par_ref[_P_LOG_N_EFF, 0]
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _logpdf_padded(
    params,  # [8, d] per-dim scalar table (SMEM); d = the real dim count
    cands,   # [S_pad, D_pad]
    dataT,   # [D8, N_pad], N_pad <= _TILE_N or a multiple of it
    mask,    # [1, N_pad]
    interpret: bool,
):
    s_pad, d_pad = cands.shape
    d8, n_pad = dataT.shape
    tile_n = min(_TILE_N, n_pad)
    return pl.pallas_call(
        _logpdf_kernel,
        out_shape=jax.ShapeDtypeStruct((s_pad, 1), jnp.float32),
        grid=(s_pad // _TILE_S, n_pad // tile_n),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_TILE_S, d_pad), lambda i, k: (i, 0)),
            pl.BlockSpec((d8, tile_n), lambda i, k: (0, k)),
            pl.BlockSpec((1, tile_n), lambda i, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((_TILE_S, 1), lambda i, k: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((_TILE_S, 1), jnp.float32),
            pltpu.VMEM((_TILE_S, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(params, cands, dataT, mask)


def _prep_kde(kde: KDE, vartypes: jax.Array, cards: jax.Array):
    """``(params, dataT, mask)`` for one KDE: the SMEM scalar table plus
    the transposed observation matrix and mask, zero-padded to whole
    observation tiles (one lane-aligned tile when it fits)."""
    data = kde.data.astype(jnp.float32)
    n, d = data.shape
    n_pad = _round_up(n, min(_TILE_N, _round_up(n, _LANE)))
    dataT = jnp.pad(data.T, ((0, _round_up(d, _SUBLANE) - d), (0, n_pad - n)))
    mask = kde.mask.astype(jnp.float32)
    mask2 = jnp.pad(mask, (0, n_pad - n))[None, :]

    bw = jnp.maximum(kde.bw.astype(jnp.float32), 1e-10)
    lam = jnp.clip(bw, 1e-10, 1.0 - 1e-7)
    km1 = jnp.maximum(jnp.asarray(cards, jnp.float32) - 1.0, 1.0)
    log_same = jnp.log1p(-lam)
    log_lam = jnp.log(lam)
    log_n_eff = jnp.log(jnp.maximum(jnp.sum(mask), 1.0))
    params = jnp.stack([
        1.0 / bw,
        -jnp.log(bw) - _LOG_SQRT_2PI,
        log_same,
        log_lam - jnp.log(km1),
        math.log(0.5) + log_same,
        log_lam,
        jnp.asarray(vartypes, jnp.float32),
        jnp.full((d,), log_n_eff, jnp.float32),
    ])
    return params, dataT, mask2


def pallas_score_candidates(
    cands: jax.Array,
    good: KDE,
    bad: KDE,
    vartypes: jax.Array,
    cards: jax.Array,
    interpret: bool = False,
    mesh=None,
    axis: str = "config",
) -> jax.Array:
    """Score ``f32[S, d]`` candidates; returns ``f32[S]`` acquisition scores.

    Drop-in replacement for the XLA path
    ``max(logpdf_good, F) - max(logpdf_bad, F)`` (see ``ops.kde.propose``).
    All padding is jnp over static shapes, so the scorer can live INSIDE a
    larger jitted program — e.g. the fused whole-sweep (``ops/sweep.py``).
    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU tests).

    With ``mesh``, the kernel runs under a ``shard_map``: each device on
    ``axis`` scores its own rows of the candidates against replicated
    KDEs (XLA's SPMD partitioner cannot split a Mosaic call on its own).
    Row math is identical either way, so sharded and unsharded scores
    agree.
    """
    from hpbandster_tpu.parallel.mesh import shard_count

    def score(cpad, gpar, goodT, gmask, bpar, badT, bmask):
        lg = _logpdf_padded(gpar, cpad, goodT, gmask, interpret=interpret)
        lb = _logpdf_padded(bpar, cpad, badT, bmask, interpret=interpret)
        return jnp.maximum(lg, LOG_PDF_FLOOR) - jnp.maximum(lb, LOG_PDF_FLOOR)

    # the same device phase name as the XLA scorer in ops.kde.propose
    with jax.named_scope("hpb.kde_score"):
        cands = jnp.asarray(cands, jnp.float32)
        s, d = cands.shape
        n_shards = shard_count(mesh, axis)
        s_pad = _round_up(s, _TILE_S * n_shards)
        cpad = jnp.pad(cands, ((0, s_pad - s), (0, _round_up(d, _LANE) - d)))
        gpar, goodT, gmask = _prep_kde(good, vartypes, cards)
        bpar, badT, bmask = _prep_kde(bad, vartypes, cards)
        if mesh is not None:
            rows = P(axis) if n_shards > 1 else P()
            score = jax.shard_map(
                score, mesh=mesh, in_specs=(rows,) + (P(),) * 6,
                out_specs=rows, check_vma=False,
            )
        return score(cpad, gpar, goodT, gmask, bpar, badT, bmask)[:s, 0]


def pallas_propose_batch(
    key: jax.Array,
    good: KDE,
    bad: KDE,
    vartypes: jax.Array,
    cards: jax.Array,
    n: int,
    num_samples: int = 64,
    bandwidth_factor: float = 3.0,
    min_bandwidth: float = 1e-3,
    interpret: bool = False,
    mesh=None,
    axis: str = "config",
) -> jax.Array:
    """A whole stage of BOHB proposals with Pallas-scored acquisition:
    generate ``n * num_samples`` candidates (``ops.kde.generate_candidates``),
    score them in the fused kernel, return the per-proposal argmax —
    ``f32[n, d]``, fully trace-safe (the fused sweep calls this inside its
    program; the host path wraps it via :func:`pallas_propose_batch_seeded`).
    ``mesh``/``axis`` as in :func:`pallas_score_candidates`.

    RNG stream differs from the per-proposal :func:`ops.kde.propose` path
    (one flat candidate draw instead of per-proposal splits) — same
    distribution, different numbers.
    """
    from hpbandster_tpu.ops.kde import generate_candidates

    cands = generate_candidates(
        key, good, vartypes, cards, n * num_samples,
        bandwidth_factor, min_bandwidth,
    )
    scores = pallas_score_candidates(
        cands, good, bad, vartypes, cards, interpret=interpret,
        mesh=mesh, axis=axis,
    )
    with jax.named_scope("hpb.kde_score"):
        best = jnp.argmax(scores.reshape(n, num_samples), axis=1)
        return cands.reshape(n, num_samples, -1)[jnp.arange(n), best]


@functools.partial(
    jax.jit, static_argnames=("n", "num_samples", "interpret")
)
def pallas_propose_batch_seeded(
    seed: jax.Array,
    good: KDE,
    bad: KDE,
    vartypes: jax.Array,
    cards: jax.Array,
    n: int,
    num_samples: int = 64,
    bandwidth_factor: float = 3.0,
    min_bandwidth: float = 1e-3,
    interpret: bool = False,
) -> jax.Array:
    """:func:`pallas_propose_batch` keyed from one scalar seed (same key
    derivation as ``ops.kde.generate_candidates_seeded``)."""
    return pallas_propose_batch(
        jax.random.key(seed), good, bad, vartypes, cards, n, num_samples,
        bandwidth_factor, min_bandwidth, interpret,
    )


def pallas_refit_propose_batch_seeded(
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
    min_bandwidth_fit: float = 1e-3,
    impute_seed=None,
    interpret: bool = False,
) -> jax.Array:
    """Pallas twin of ``ops.kde.refit_propose_batch_seeded``: the KDE
    refit (good/bad split + bandwidths over raw observation buffers, all
    traced counts) AND the fused-kernel acquisition scoring happen in one
    compiled dispatch — the refit state never visits the host. Returns
    the selected proposals ``f32[n, d]`` (the Pallas pipeline is
    score-less on the host side, like :func:`pallas_propose_batch`).
    """
    from hpbandster_tpu.ops.kde import fit_kde_pair_masked

    impute_key = (
        None if impute_seed is None else jax.random.key(impute_seed)
    )
    good, bad = fit_kde_pair_masked(
        obs_v, obs_l, count, n_good, n_bad, cards, min_bandwidth_fit,
        impute_key=impute_key,
    )
    return pallas_propose_batch(
        jax.random.key(seed), good, bad, vartypes, cards, n, num_samples,
        bandwidth_factor, min_bandwidth, interpret,
    )
