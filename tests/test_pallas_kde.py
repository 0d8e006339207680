"""Pallas KDE scorer vs. the XLA reference path (interpreter mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.ops import KDE, LOG_PDF_FLOOR, kde_logpdf, normal_reference_bandwidths
from hpbandster_tpu.ops.pallas_kde import pallas_score_candidates


def make_kde(rng, n, d, cards, cap=64):
    data = np.zeros((n, d), np.float32)
    for j in range(d):
        if cards[j] > 0:
            data[:, j] = rng.integers(cards[j], size=n)
        else:
            data[:, j] = rng.uniform(size=n)
    padded = np.zeros((cap, d), np.float32)
    padded[:n] = data
    mask = np.zeros(cap, np.float32)
    mask[:n] = 1.0
    bw = np.asarray(
        normal_reference_bandwidths(padded, mask, np.asarray(cards, np.int32))
    )
    return KDE(jnp.asarray(padded), jnp.asarray(mask), jnp.asarray(bw))


def xla_scores(cands, good, bad, vt, cards):
    import jax

    lg = jax.vmap(lambda c: kde_logpdf(c, good, vt, cards))(cands)
    lb = jax.vmap(lambda c: kde_logpdf(c, bad, vt, cards))(cands)
    return np.asarray(
        jnp.maximum(lg, LOG_PDF_FLOOR) - jnp.maximum(lb, LOG_PDF_FLOOR)
    )


@pytest.mark.parametrize(
    "d,cards",
    [
        (2, [0, 0]),
        (4, [0, 0, 3, 4]),  # mixed: continuous + categorical('u'-style codes)
        (6, [0, 3, 0, 5, 2, 0]),
    ],
)
def test_matches_xla_path(d, cards):
    rng = np.random.default_rng(0)
    vt = np.asarray([0 if c == 0 else (1 if i % 2 else 2) for i, c in enumerate(cards)], np.int32)
    # force consistent vartype: categorical dims alternate 'u'/'o'
    vt = np.asarray([0 if c == 0 else (1 + (i % 2)) for i, c in enumerate(cards)], np.int32)
    cards_arr = np.asarray(cards, np.int32)
    good = make_kde(rng, 20, d, cards)
    bad = make_kde(rng, 25, d, cards)

    cands = np.zeros((37, d), np.float32)  # non-multiple of tile size
    for j in range(d):
        if cards[j] > 0:
            cands[:, j] = rng.integers(cards[j], size=37)
        else:
            cands[:, j] = rng.uniform(size=37)

    got = np.asarray(
        pallas_score_candidates(
            cands, good, bad, jnp.asarray(vt), jnp.asarray(cards_arr),
            interpret=True,
        )
    )
    want = xla_scores(jnp.asarray(cands), good, bad, jnp.asarray(vt), jnp.asarray(cards_arr))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_empty_mask_rows_ignored():
    rng = np.random.default_rng(1)
    cards = [0, 0]
    vt = np.zeros(2, np.int32)
    good = make_kde(rng, 5, 2, cards)
    bad = make_kde(rng, 5, 2, cards)
    cands = rng.uniform(size=(8, 2)).astype(np.float32)
    got = np.asarray(
        pallas_score_candidates(cands, good, bad, vt, np.asarray(cards, np.int32), interpret=True)
    )
    want = xla_scores(jnp.asarray(cands), good, bad, jnp.asarray(vt), jnp.asarray(cards, dtype=jnp.int32))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_observation_tiled_scorer_matches_xla_at_8192():
    """The capacity the 10k-config sweeps hand the kernel: 8192 slots is
    16 observation tiles, so every candidate's score crosses the running
    max/sum fold many times — and good/bad hold different live counts.
    On a mesh each device scores its own candidate rows: same numbers."""
    import jax

    from hpbandster_tpu.parallel.mesh import config_mesh

    rng = np.random.default_rng(2)
    cards = [0, 4, 0]
    vt = jnp.asarray([0, 1, 0], jnp.int32)
    cards_arr = jnp.asarray(cards, jnp.int32)
    good = make_kde(rng, 5000, 3, cards, cap=8192)
    bad = make_kde(rng, 7000, 3, cards, cap=8192)
    cands = np.zeros((300, 3), np.float32)
    cands[:, 0] = rng.uniform(size=300)
    cands[:, 1] = rng.integers(4, size=300)
    cands[:, 2] = rng.uniform(size=300)

    got = np.asarray(pallas_score_candidates(
        cands, good, bad, vt, cards_arr, interpret=True))
    want = xla_scores(jnp.asarray(cands), good, bad, vt, cards_arr)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    mesh = config_mesh(jax.devices())
    assert mesh.size == 8  # the conftest-forced CPU mesh
    sharded = np.asarray(jax.jit(
        lambda c: pallas_score_candidates(
            c, good, bad, vt, cards_arr, interpret=True, mesh=mesh)
    )(cands))
    np.testing.assert_allclose(sharded, got, rtol=1e-5, atol=1e-5)


def test_bohb_generator_pallas_path_end_to_end():
    """Force the pallas proposal path (interpreted on CPU) through BOHBKDE."""
    from hpbandster_tpu.core.job import Job
    from hpbandster_tpu.models.bohb_kde import BOHBKDE
    from tests.toys import branin_space

    cs = branin_space(seed=0)
    cg = BOHBKDE(cs, seed=0, min_points_in_model=4, num_samples=16,
                 proposal_batch_size=8)
    cg.use_pallas = True  # bypass the TPU-only gate; interpret mode kicks in
    rng = np.random.default_rng(0)
    for i in range(12):
        cfg = dict(cs.sample_configuration())
        j = Job((0, 0, i), config=cfg, budget=1.0)
        x = cfg["x"]
        j.result = {"loss": float((x - 2.0) ** 2 + 0.1 * rng.standard_normal())}
        cg.new_result(j)
    batch = cg.get_config_batch(3.0, 6)
    assert len(batch) == 6
    model_picks = [cfg for cfg, info in batch if info["model_based_pick"]]
    assert model_picks, "pallas path produced no model-based picks"
    for cfg in model_picks:
        assert -5.0 <= cfg["x"] <= 10.0 and 0.0 <= cfg["y"] <= 15.0
