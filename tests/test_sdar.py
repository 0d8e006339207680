"""The SDAR lane (grouped-query attention under a per-head norm, softmax-routed
experts, trained by masked diffusion over blocks: a clean and a masked copy of
every sequence under a rule of sight that is not causal, a weighted
cross-entropy on the masked rows) against the benchmark's plain reference, on
the CPU at a small size (``sdar_small.py``): the rule of sight pair by pair
against a loop, the blocks that are never computed, what a masked row may not
see, the forward pass and the loss, the trainer's gradient of every leaf
against ``jax.grad`` of the reference's whole loss, one and three steps, the
shares of the expert layer, and the comparison that decides the cell's
``correct`` with its planted faults.

Where a test holds the equations to the reference it sets the lanes'
matrix-product operands to float32 (``lane._OPERAND``): then only the order
of float32 sums differs, and the tolerances say so. Where it runs the lane
as the chip does (bfloat16 operands), the tolerance is bfloat16's.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads import sdar as D

from sdar_small import SMALL, load, small

S, L = 32, 4


@pytest.fixture(scope="module")
def reference():
    return load("reference", "sdar-sgd.py")


@pytest.fixture(scope="module")
def lane_config():
    # the builders import the harness's ``program`` by that name
    sys.modules.setdefault("program", load("program.py"))
    return load("configs", "sdar-sgd.py").lane_config


@pytest.fixture
def float32_operands(monkeypatch):
    monkeypatch.setattr(lane, "_OPERAND", jnp.float32)


def _cfg(lane_config, config=SMALL):
    return lane_config(config)._replace(attn_query_block=16)


def _unstacked(params):
    """The program's tree under the reference's names: ``layers`` taken
    apart into ``l<i>``."""
    tree = dict(params)
    stacked = tree.pop("layers")
    for i in range(jax.tree.leaves(stacked)[0].shape[0]):
        tree["l%d" % i] = jax.tree.map(lambda x: x[i], stacked)
    return tree


def _record(data, i):
    """Sequence ``i`` of the program's data; and as the reference takes it."""
    seq = jax.tree.map(lambda x: x[i], data)
    return seq, (seq["tokens"], seq["mask"], seq["weight"])


def _gradient_steps(p):
    """``(v, update)``: a momentum of zeros and an update that keeps the
    parameters and hands the gradient back as the momentum."""
    return jax.tree.map(jnp.zeros_like, p), lambda pl, vl, g: (pl, g)


def _worst(got, want):
    """Per leaf, the largest difference against the leaf's largest entry."""
    return {jax.tree_util.keystr(path): float(
        jnp.abs(g - w).max() / (jnp.abs(w).max() + 1e-12))
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(want))}


def _trainers_gradient(params, seq, cfg, exits=None):
    v, keep = _gradient_steps(params)
    _, got, _, _ = jax.jit(lambda p, v: lane._pass(
        p, v, seq, jnp.bool_(True), D._visits(cfg), exits or D._exits(cfg), keep))(params, v)
    return got


# ------------------------------------------------------------ the rule of sight
def _sees(query, key, s, length):
    """The four sentences of the rule, one pair of rows at a time."""
    block = lambda row: (row % s) // length
    if query < s:                                   # a clean query
        return key < s and block(key) <= block(query)   # clean keys; no masked row
    if key < s:                                     # a masked query, a clean key
        return block(key) < block(query)
    return block(key) == block(query)               # a masked query, a masked key


def _loop_mask(s, length):
    return np.asarray([[_sees(q, k, s, length) for k in range(2 * s)] for q in range(2 * s)])


def _seen_by_attention(s, length, block):
    """What ``banded_attention`` lets each row see, read off its output: with
    scores of zero the softmax is uniform over the keys seen, and values that
    are the rows of the identity spell out which those are."""
    rows = 2 * s
    zeros = jnp.zeros((rows, 1, 1, rows))
    out = lane.banded_attention(zeros, zeros[:, :, 0], jnp.eye(rows)[:, None, :],
                                lane.BlockDiffusion(length), block)
    return np.asarray(out[:, 0, 0, :])


@pytest.mark.parametrize("block", [4, 8, 16])
def test_the_mask_is_the_four_sentences_pair_by_pair(float32_operands, block):
    """Every (query, key) pair of the 32 x 32 square at S = 16, L = 4, for
    blocks of queries of one, two and four diffusion blocks."""
    want = _loop_mask(16, 4)
    # by hand: a clean row sees its whole block both ways and the ones before
    assert want[5, :8].all() and not want[5, 8:].any()
    # a masked row sees clean earlier blocks and the masked copy of its own
    assert want[16 + 5, :4].all() and not want[16 + 5, 4:16 + 4].any()
    assert want[16 + 5, 16 + 4:16 + 8].all() and not want[16 + 5, 16 + 8:].any()
    assert want.sum() == 16 * 16 // 2 + 16 * 4 // 2 + 16 * 16 // 2 - 16 * 4 // 2 + 16 * 4
    got = _seen_by_attention(16, 4, block)
    assert ((got > 0) == want).all()
    # one softmax a query over all it sees
    np.testing.assert_allclose(got.sum(1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(got, want / want.sum(1, keepdims=True), rtol=1e-6)
    # the rule itself, on the pairs outright
    rows = jnp.arange(32)
    assert (np.asarray(lane.BlockDiffusion(4).seen(rows[:, None], rows[None, :], 32))
            == want).all()
    # the reference builds the same array from the same sentences
    assert (np.asarray(load("reference", "sdar-sgd.py").sight_mask(16, 4)) == want).all()


@pytest.mark.parametrize("s, length, block", [(16, 4, 4), (16, 4, 8), (32, 4, 16), (32, 8, 8)])
def test_no_block_wholly_outside_the_mask_is_computed(s, length, block):
    """``attention_key_blocks`` counts the blocks of scores the spans reach:
    exactly those of the square that hold a pair the loop says is seen."""
    want = _loop_mask(s, length)
    side = 2 * s // block
    holds_a_pair = want.reshape(side, block, side, block).any(axis=(1, 3))
    computed, square = lane.attention_key_blocks(2 * s, [lane.BlockDiffusion(length)], block)
    assert (computed, square) == (int(holds_a_pair.sum()), side * side)
    reached = np.zeros((side, side), bool)
    for lo, hi, runs in lane._attention_spans(2 * s, lane.BlockDiffusion(length), block):
        assert hi - lo == block
        for klo, khi in runs:
            reached[lo // block, klo // block:-(-khi // block)] = True
    assert (reached == holds_a_pair).all()


def test_the_blocks_of_the_published_cell():
    full = D.SdarConfig()
    sight = lane.BlockDiffusion(full.block_length)
    computed, square = lane.attention_key_blocks(
        2 * full.seq_len, [sight], full.attn_query_block)
    # clean block q: q + 1 blocks of keys; masked block q: q + 1 clean, its own
    assert (computed, square) == (36 + 44, 256) and computed / square < 0.35
    assert lane.attention_key_blocks(8192, [sight] * 4, 512) == (320, 1024)
    # a block of queries is whole diffusion blocks, a copy whole blocks
    with pytest.raises(ValueError):
        lane._attention_spans(64, lane.BlockDiffusion(4), 6)
    with pytest.raises(ValueError):
        lane._attention_spans(48, lane.BlockDiffusion(4), 16)
    # the widest block of scores is a masked one: its clean keys and itself
    assert lane._widest_scores(8192, sight, 512) == 512 * (4096 + 512)
    assert lane.attention_alive_bytes(8192, 4, 8, 128, [sight], 512) == 3 * 4 * 8 * 512 * 4608


def _dense(q, k, v, mask):
    """Grouped-query softmax attention over an explicit mask, all at once."""
    t, g, r, d = q.shape
    scores = jnp.einsum("tgrd,sgd->grts", q, k) / d ** 0.5
    att = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("grts,sgd->tgrd", att, v)


@pytest.mark.parametrize("scores_at_once", [lane._SCORES_AT_ONCE, 1])
def test_attention_under_the_rule_is_the_dense_masked_softmax(float32_operands, scores_at_once):
    key = jax.random.key(0)
    q = jax.random.normal(key, (64, 2, 2, 8))
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (64, 2, 8)) for i in (1, 2))
    mask = jnp.asarray(_loop_mask(32, 4))
    ours = lambda *x: lane.banded_attention(*x, lane.BlockDiffusion(4), 16, scores_at_once)
    np.testing.assert_allclose(ours(q, k, v), _dense(q, k, v, mask), atol=2e-6)
    got = jax.grad(lambda *x: (ours(*x) ** 2).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *x: (_dense(*x, mask) ** 2).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_positions_repeat_and_the_kernels_take_the_rule(monkeypatch):
    sight = lane.BlockDiffusion(4)
    np.testing.assert_array_equal(sight.positions(8), [0, 1, 2, 3, 0, 1, 2, 3])
    inv_freq = 10000.0 ** (-np.arange(0, 8, 2) / 8)
    cos, sin = lane._rotary_tables(inv_freq, 1.0, sight.positions(8))
    plain = lane._rotary_tables(inv_freq, 1.0, 4)
    for twice, once in ((cos, plain[0]), (sin, plain[1])):
        np.testing.assert_array_equal(twice[:4], once)
        np.testing.assert_array_equal(twice[4:], once)
    # off the chip the plain form, whatever the rule
    assert lane._kernel_tiles(8192, 128, 8, 4, sight) is None
    assert lane.attention_counters(8192, 128, 8, 4, sight) == (
        ("attn_scores_in_vmem", 0.0), ("attn_rotation_in_vmem", 0.0))
    # where Mosaic compiles, the backend and the shapes decide under either
    # rule: the fused kernels walk the tiles the rule gives them
    monkeypatch.setattr(lane, "pallas_available", lambda: True)
    assert lane._kernel_tiles(8192, 128, 8, 4) == (128, 512)
    assert lane._kernel_tiles(8192, 128, 8, 4, lane.Causal(1024)) == (128, 512)
    assert lane._kernel_tiles(8192, 128, 8, 4, sight) == (128, 512)
    assert lane._kernel_tiles(8192, 64, 4, 8, sight) == (128, 512)
    assert lane.attention_counters(8192, 128, 8, 4, sight) == (
        ("attn_scores_in_vmem", 1.0), ("attn_rotation_in_vmem", 1.0))
    # a copy that is no whole tiles of keys (17 x 256 rows), diffusion blocks
    # that a block of 128 queries would cut, few keys: the plain form, where
    # the causal rule takes the same number of rows
    assert lane._kernel_tiles(8704, 128, 8, 4) == (128, 512)
    assert lane._kernel_tiles(8704, 128, 8, 4, sight) is None
    assert lane._kernel_tiles(8192, 128, 8, 4, lane.BlockDiffusion(24)) is None
    assert lane._kernel_tiles(2048, 128, 8, 4, sight) is None
    # the counted tiles and the footprint follow the path that runs: 296 of
    # 1,024 tiles of 128 x 512 a layer (a masked block's own keys are a tile
    # of 128), an output and a log-sum-exp a row and no block of scores
    assert lane.attention_key_blocks(
        8192, [sight] * 4, 512, lane._kernel_tiles(8192, 128, 8, 4, sight)) == (4 * 296, 4 * 1024)
    assert lane.attention_alive_bytes(8192, 4, 8, 128, [sight], 512) == 4 * 8192 * 32 * (128 + 128)
    # a bare window is the causal rule
    assert lane._rule(None) == lane.Causal() and lane._rule(8) == lane.Causal(8)
    assert lane._rule(sight) is sight


@pytest.mark.parametrize("operand", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tiles, g, r, d", [((64, 128), 2, 2, 128), ((128, 256), 1, 4, 128),
                                            ((64, 128), 2, 2, 64)])
def test_the_kernels_show_a_masked_row_nothing_the_rule_hides(operand, tiles, g, r, d):
    """The fused kernels under the rule, in the Pallas interpreter: the keys
    and values that the rule hides from the masked rows of a diffusion block
    (its own block's clean copy, every later clean block, every other
    block's masked rows) changed outright move none of those rows' outputs
    and none of their queries' gradients, to the last bit (a hidden pair
    weighs exactly 0 in both kernels), though rows that do see them move;
    and a masked row of the first block, which sees no clean key at all (its
    first walked tile shows it nothing), comes out finite in both."""
    from hpbandster_tpu.ops import pallas_attention

    t, length = 512, 4
    half, rule, tiles = t // 2, lane.BlockDiffusion(length), pallas_attention.Tiles(*tiles)
    assert pallas_attention.fits(t, d, r, g, tiles) and rule.whole_tiles(t, tiles)
    keys = jax.random.split(jax.random.key(7), 6)
    q, weigh = (jax.random.normal(key, (t, g * r * d)) for key in keys[:2])
    k, v, k_other, v_other = (jax.random.normal(key, (t, g * d)) for key in keys[2:])

    def out_and_dq(k, v):
        out, pull = jax.vjp(lambda q: pallas_attention.fused_banded_attention(
            q, k, v, (g, r, d), rule, tiles, operand, "lane.bda", True), q)
        return out, pull(weigh)[0]

    want = out_and_dq(k, v)
    assert all(bool(jnp.isfinite(x).all()) for x in want)
    assert float(jnp.abs(want[0][half:half + length]).max()) > 0
    for block in (0, 17, half // length - 1):
        rows = slice(half + block * length, half + (block + 1) * length)
        at = jnp.arange(t)
        hidden = jnp.where(at < half, at >= block * length, (at < rows.start) | (at >= rows.stop))
        got = out_and_dq(jnp.where(hidden[:, None], k_other, k), jnp.where(hidden[:, None], v_other, v))
        for ours, theirs in zip(got, want):
            np.testing.assert_array_equal(ours[rows], theirs[rows])
            assert float(jnp.abs(ours - theirs).max()) > 0     # the probe reaches other rows


# ----------------------------------------------------------- seed, loss, gradient
def test_weights_tokens_and_noise_come_from_the_seed_alike(reference, lane_config):
    cfg, key = _cfg(lane_config), jax.random.key(1)
    ours = _unstacked(D.init_sdar_params(key, cfg, 0.7))
    theirs = reference.init_params(SMALL, key, 0.7)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()), ours, theirs)))
    assert ours["embed"].shape == (256, 64) and ours["head"].shape == (64, 256)
    assert bool((ours["l1"]["q_norm"] == 1).all()) and ours["l1"]["k_norm"].shape == (16,)
    assert ours["l0"]["router"].shape == (64, 8) and ours["l0"]["e_gate"].shape == (4, 64, 32)
    for mine, (tokens, mask, weight) in zip(
            D.make_diffusion_dataset(jax.random.key(0), cfg), reference.dataset(SMALL)):
        assert mine["tokens"].shape[1] == S and mine["mask"].dtype == bool
        for a, b in ((mine["tokens"], tokens), (mine["mask"], mask), (mine["weight"], weight)):
            assert bool((a == b).all())
        # a sliced vocabulary is a smaller vocabulary: ids over the slice,
        # its last one MASK, which the data never draws
        assert int(tokens.max()) < D.mask_id(cfg) == reference.mask_id(SMALL) == 255
        # the second half of a sequence repeats its first
        assert bool((tokens[:, S // 2 + 1:] == tokens[:, :S // 2 - 1]).all())
        # one noise level a block: a masked position weighs 1 / t of its block
        w = np.asarray(weight).reshape(-1, S // L, L)
        for block in w.reshape(-1, L):
            assert len(set(block[block > 0])) <= 1
        assert bool(((weight > 0) == mask).all()) and float(weight.max()) <= 1e3
        assert bool((weight[mask] >= 1.0).all())
    train, _ = D.make_diffusion_dataset(jax.random.key(0), cfg._replace(n_train=64))
    assert 0.3 < float(train["mask"].mean()) < 0.7     # E[t] is a half
    assert 0.7 < float(train["weight"].mean()) < 1.3   # E[m / t] is one


def test_the_loss_and_the_forward_pass_match_the_reference(
        reference, lane_config, float32_operands):
    cfg = _cfg(lane_config)
    params = D.init_sdar_params(jax.random.key(1), cfg, 1.3)
    seq, theirs = _record(D.make_diffusion_dataset(jax.random.key(0), cfg)[0], 0)
    loss, counters = jax.jit(lambda p: D.sdar_loss(p, seq, cfg))(params)
    want = jax.jit(lambda p: reference.loss_fn(p, theirs, SMALL))(_unstacked(params))
    # float32 both sides, another order of summation (blocks of keys against
    # the whole row, sorted rows against a masked loop over the experts)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    # the layers are one visit, which counts their sum
    assert counters.shape == (1, 3)
    again, same, hs = D.sdar_forward(params, seq, cfg)
    assert float(again) == pytest.approx(float(loss), rel=1e-6)
    np.testing.assert_allclose(same, counters, rtol=1e-6)
    # 2 S rows: the clean copy's embeddings, then the masked copy's
    assert len(hs) == 2 and hs[0].shape == (2 * S, 64)
    np.testing.assert_array_equal(hs[0][:S], params["embed"][seq["tokens"]])
    np.testing.assert_array_equal(
        hs[0][S:], params["embed"][jnp.where(seq["mask"], 255, seq["tokens"])])
    np.testing.assert_allclose(
        hs[-1], reference.hidden(_unstacked(params), theirs[0], theirs[1], SMALL), atol=2e-5)


def test_the_trainers_gradient_is_that_of_the_references_whole_loss(
        reference, lane_config, float32_operands):
    """Every leaf, against ``jax.grad`` of the reference's loss over its
    explicit mask and its 2 S rows outright."""
    cfg = _cfg(lane_config)
    params = D.init_sdar_params(jax.random.key(1), cfg, 1.3)
    seq, theirs = _record(D.make_diffusion_dataset(jax.random.key(0), cfg)[0], 1)
    want = jax.jit(jax.grad(lambda p: reference.loss_fn(p, theirs, SMALL)))(_unstacked(params))
    got = _unstacked(_trainers_gradient(params, seq, cfg))
    worst = _worst(got, want)
    assert set(worst) >= {"['embed']", "['head']", "['norm_f']", "['l0']['q_norm']",
                          "['l0']['k_norm']", "['l0']['wq']", "['l1']['router']",
                          "['l1']['e_down']"}
    # float32 both sides, sums in another order
    assert max(worst.values()) < 1e-5, worst
    # the lookup's gradient lands on the clean and the masked copy's ids:
    # MASK's row moves, and a row that neither copy looked up does not
    assert float(jnp.abs(got["embed"][255]).max()) > 0
    unseen = np.setdiff1d(np.arange(255), np.asarray(seq["tokens"]))
    assert len(unseen) > 100 and not float(jnp.abs(got["embed"][unseen]).max())
    # bfloat16 parameters would not pass: rounding them alone moves a leaf's
    # gradient by more than a hundred times that
    rounded = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    assert max(_worst(_unstacked(_trainers_gradient(rounded, seq, cfg)), want).values()) > 2e-3


def test_a_held_out_pass_leaves_the_lane_as_it_is(lane_config, float32_operands):
    cfg = _cfg(lane_config)
    params = D.init_sdar_params(jax.random.key(1), cfg, 1.0)
    seq, _ = _record(D.make_diffusion_dataset(jax.random.key(0), cfg)[1], 0)
    v, keep = _gradient_steps(params)
    p, same_v, loss, (counters, masked) = jax.jit(lambda p, v: lane._pass(
        p, v, seq, jnp.bool_(False), D._visits(cfg), D._exits(cfg), keep))(params, v)
    assert all(jax.tree.leaves(jax.tree.map(lambda a, b: bool((a == b).all()), p, params)))
    assert not any(float(jnp.abs(x).max()) for x in jax.tree.leaves(same_v))
    assert float(loss) == pytest.approx(float(D.sdar_forward(params, seq, cfg)[0]))
    assert counters.shape == (1, 3) and float(masked[0]) == float(seq["mask"].sum())


# ------------------------------------------------- what a masked row may not see
def test_a_masked_row_sees_neither_later_blocks_nor_its_own_clean_tokens(
        lane_config, float32_operands):
    """The leak that would make the loss trivial: a masked row's state (and
    with it its logits) does not change when a later block's clean tokens
    change nor when its own block's do; it does change when an earlier
    block's clean tokens or its own block's masked copy do."""
    cfg = _cfg(lane_config)
    params = D.init_sdar_params(jax.random.key(1), cfg, 1.0)
    tokens = D.make_diffusion_dataset(jax.random.key(0), cfg)[0]["tokens"][0]
    # every position masked but 13, which its block's masked copy shows
    mask = jnp.ones((S,), bool).at[13].set(False)
    last = jax.jit(lambda tokens: D.sdar_forward(
        params, {"tokens": tokens, "mask": mask, "weight": mask.astype(jnp.float32)}, cfg)[2][-1])
    base = last(tokens)
    row = S + 14                        # a masked row of block 3 (positions 12..15)
    swap = lambda at: tokens.at[at].set((tokens[at] + 7) % 255)
    # a later block's clean token (20, block 5): nothing before it moves
    moved = last(swap(20))
    assert bool((moved[row] == base[row]).all()) and bool((moved[S:S + 20] == base[S:S + 20]).all())
    assert bool((moved[20] != base[20]).any())
    # its own block's clean token (12; masked, so the masked copy is as it was)
    moved = last(swap(12))
    assert bool((moved[row] == base[row]).all()) and bool((moved[S:S + 16] == base[S:S + 16]).all())
    assert bool((moved[14] != base[14]).any())      # the clean rows of the block do see it
    # an earlier block's clean token (5, block 1)
    assert bool((last(swap(5))[row] != base[row]).any())
    # its own block's masked copy (13 is not masked: the copy shows the token)
    assert bool((last(swap(13))[row] != base[row]).any())
    # and a clean row sees no masked row: unmasking changes no clean row
    unmasked = jax.jit(lambda: D.sdar_forward(
        params, {"tokens": tokens, "mask": jnp.zeros((S,), bool),
                 "weight": jnp.zeros((S,))}, cfg)[2][-1])()
    assert bool((unmasked[:S] == base[:S]).all())


def test_all_masked_in_one_block_the_loss_is_the_unweighted_mean(
        reference, lane_config, float32_operands):
    """``t = 1`` everywhere and ``L = S``: every position masked, every masked
    row sees the whole masked copy and nothing else, every weight one."""
    whole = small(train={"block_length": S})
    cfg = lane_config(whole)._replace(attn_query_block=S)
    params = D.init_sdar_params(jax.random.key(1), cfg, 1.0)
    tokens = D.make_diffusion_dataset(jax.random.key(0), cfg)[0]["tokens"][2]
    mask = jnp.ones((S,), bool)
    seq = {"tokens": tokens, "mask": mask, "weight": jnp.ones((S,))}
    loss, _ = jax.jit(lambda p: D.sdar_loss(p, seq, cfg))(params)
    z = reference.logits(_unstacked(params), tokens, mask, whole)
    by_hand = -jnp.take_along_axis(jax.nn.log_softmax(z), tokens[:, None], 1).mean()
    assert float(loss) == pytest.approx(float(by_hand), rel=1e-5)
    # every masked row is the MASK embedding at its position, under one
    # softmax over all of them: the tokens reach the loss as targets alone
    other = dict(seq, tokens=(tokens + 3) % 255)
    np.testing.assert_array_equal(
        D.sdar_forward(params, other, cfg)[2][-1][S:], D.sdar_forward(params, seq, cfg)[2][-1][S:])
    # half the weights: half the loss
    halved, _ = D.sdar_loss(params, dict(seq, weight=jnp.full((S,), 0.5)), cfg)
    assert float(halved) == pytest.approx(float(loss) / 2, rel=1e-6)


# ------------------------------------------------------------------ the steps
@pytest.mark.parametrize("operand, steps, limit", [
    # float32 operands: rounding of sums only, steps amplify it little
    (jnp.float32, 1, 2e-5), (jnp.float32, 3, 1e-4),
    # as the chip runs it: bfloat16 operands (2^-8 a product) through two
    # layers and three steps
    (jnp.bfloat16, 3, 2e-2),
])
def test_steps_match_the_reference(reference, lane_config, monkeypatch, operand, steps, limit):
    monkeypatch.setattr(lane, "_OPERAND", operand)
    cfg = _cfg(lane_config)
    eval_fn = D.make_sdar_eval_fn(cfg, data_seed=SMALL["data_seed"])
    # lr 0.1 at an init scale of 0.5: a lane that learns from its first step
    vec = jnp.asarray([0.75, 0.5, 0.3, 0.35])
    got = float(jax.jit(lambda v: eval_fn(v, float(steps)))(vec))
    hparams = [float(x) for x in lane.decode_lane_hparams(vec)]
    start, want = reference.reference_losses(SMALL, hparams, [0, steps])
    assert abs(want - start) > 0.01  # the steps moved the loss: it is compared
    assert abs(got - want) < limit * (1 + abs(want))
    if operand == jnp.float32 and steps == 1:
        # the control: bfloat16 parameters and momentum fail the same limit
        # (five times over it: a loss after one step hardly tells, which is
        # why the cell's comparison reads the step itself)
        coarse = reference.reference_losses(SMALL, hparams, [steps], dtype=jnp.bfloat16)[0]
        assert abs(coarse - want) > 4 * limit * (1 + abs(want))


def test_one_step_is_the_whole_gradient_of_the_references_loss(
        reference, lane_config, float32_operands):
    """After one step from a momentum of zeros, what the step changed
    (``eval_fn.change``) is ``-lr`` times the gradient of the reference's
    loss on the first training sequence under that sequence's own draw of
    masks and noise levels, decay added: every leaf."""
    cfg = _cfg(lane_config)
    eval_fn = D.make_sdar_eval_fn(cfg, data_seed=SMALL["data_seed"])
    # lr 0.1, momentum 0.5, the least decay (1e-7), init scale 1
    vec = jnp.asarray([0.75, 0.5 / 0.99, 0.0, 0.5])
    lr, _, wd, init = (float(x) for x in lane.decode_lane_hparams(vec))
    change = _unstacked(jax.jit(eval_fn.change)(vec, jnp.float32(1.0)))
    params = reference.init_params(SMALL, jax.random.key(1), jnp.float32(init))
    train, _ = reference.dataset(SMALL)
    grad = jax.grad(lambda p: reference.loss_fn(p, tuple(x[0] for x in train), SMALL))(params)
    want = jax.tree.map(lambda g, p: -lr * (g + wd * p), grad, params)
    # float32: the step is a difference of parameters, a few units of their
    # last place
    worst = _worst(change, want)
    assert max(worst.values()) < 1e-4, worst


# ------------------------------------------------------------------- the shares
def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer(
        reference, lane_config, float32_operands):
    """Four chips of 2 experts each over one router (8 outputs, top 2, a
    softmax over all 8 before the choice): what the four shares give, summed,
    is the reference's whole layer, over all 2 S rows."""
    cfg = _cfg(lane_config)
    p = _unstacked(D.init_sdar_params(jax.random.key(2), cfg, 1.5))["l1"]
    x = jax.random.normal(jax.random.key(5), (2 * S, 64))
    whole = small(cut={"experts_held": list(range(8))})
    key = jax.random.key(7)
    experts = {n: lane._init_leaf(key, n, (8,) + p[n].shape[1:], 1.5)
               for n in ("e_gate", "e_up", "e_down")}
    p = dict(p, **experts)
    want = reference.experts(x, p, whole)
    chosen, weight = reference.router_weights(x, p, whole)
    np.testing.assert_allclose(weight.sum(1), 1.0, rtol=1e-6)     # renormalised
    total, held_choices = jnp.zeros_like(x), 0.0
    for chip in range(4):
        held = (2 * chip, 2 * chip + 1)
        share = dict(p, **{n: experts[n][jnp.asarray(held)] for n in experts})
        y, counters = lane.moe_held_experts(
            x, share, D._experts(cfg)._replace(held=held))
        np.testing.assert_allclose(y, reference.experts(x, share, whole, held=held), atol=2e-5)
        total, held_choices = total + y, held_choices + float(counters[0])
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert held_choices == 2 * S * 2      # every row's choice fell on exactly one chip


# ------------------------------------------------------------- the comparison
def _sweep_record(lrs, inits):
    """A sweep's 13 evaluations (9, 3, 1 lanes at 1, 3, 9 steps; lanes 0, 2,
    8 promoted, lane 2 twice) as ``benchmark/program.py`` records them."""
    lanes = np.asarray(list(range(9)) + [0, 2, 8] + [2])
    return {"bracket": np.zeros(13, int), "lane": lanes,
            "budget": np.asarray([1.0] * 9 + [3.0] * 3 + [9.0]),
            "loss": 10.0 + 0.01 * np.arange(13),
            "config": {"lr": np.asarray(lrs)[lanes], "momentum": np.full(13, 0.5),
                       "weight_decay": np.full(13, 1e-5),
                       "init_scale": np.asarray(inits)[lanes]}}


class _Leaky(lane.BlockDiffusion):
    """Planted: a masked query sees the clean copy of its own block too."""

    def seen(self, at, key, rows):
        half, length = rows // 2, self.block_length
        own, its = (at % half) // length, (key % half) // length
        return super().seen(at, key, rows) | ((at >= half) & (key < half) & (its == own))


def _unweighted(cfg):
    """Planted: the masked rows' cross-entropy without the weights ``1 / t``."""
    sound = _SOUND_EXITS(cfg)
    plain = lambda seq: dict(seq, weight=seq["mask"].astype(jnp.float32))
    return sound._replace(trained=lambda states, leaves, seq: sound.trained(
        states, leaves, plain(seq)))


_SOUND_EXITS = D._exits


@pytest.mark.parametrize("fault, shows", [
    (None, {}),
    ("unchanged", {"all": 0.999, "ends": 0.999, "experts": 0.999, "attention": 0.999}),
    ("leaky", {"attention": 0.15, "all": 0.08, "experts": 0.08, "ends": 0.02}),
    ("unweighted", {"all": 0.3, "ends": 0.3}),
    ("control", {"all": 0.7, "experts": 0.9, "attention": 0.9})])
def test_the_comparison_reads_what_the_first_step_changed(
        reference, lane_config, float32_operands, monkeypatch, fault, shows):
    """``compare`` on a sweep's record whose ``lane_change`` is the lane's
    trainer (``eval_fn.change``, unstacked as the cell's builder hands it):
    the sound trainer's first step is the reference's; a step that changed
    nothing reads 1 in every group; a masked row that sees its own block's
    clean tokens shows in the attention mixers' first (at random weights a
    row's own token tells it little yet: 0.19 where the sound step reads
    under 0.005) and everywhere; a loss without the weights ``1 / t``
    everywhere; the control (the reference with
    bfloat16 parameters and momentum) loses the step of the lane of the
    smallest learning rate (2e-4) altogether. ``sight_leak`` reads the rule
    of sight off the program's own masked rows (``masked_states`` of the
    record, as the cell's builder hands it): 0 but under the planted leak."""
    cfg = _cfg(lane_config)
    if fault == "leaky":
        monkeypatch.setattr(D, "_sight", lambda cfg: _Leaky(cfg.block_length))
    if fault == "unweighted":
        monkeypatch.setattr(D, "_exits", _unweighted)
    eval_fn = D.make_sdar_eval_fn(cfg, data_seed=SMALL["data_seed"])
    change = jax.jit(lambda vec, steps: _unstacked(eval_fn.change(vec, steps)))

    def lane_change(hparams, steps):
        lr, momentum, wd, init = hparams
        vec = jnp.asarray([(np.log10(lr) + 4) / 4, momentum / 0.99, (np.log10(wd) + 7) / 5,
                           (np.log10(init) + 1) / 2], jnp.float32)
        tree = change(vec, jnp.float32(steps))
        return jax.tree.map(jnp.zeros_like, tree) if fault == "unchanged" else tree

    # lanes 2 (top) and 1 (the others' smallest learning rate)
    rec = _sweep_record([0.3, 2e-4, 0.05, 2.5e-3, 1e-3, 0.9, 2.9e-3, 0.02, 0.4],
                        [0.2, 0.5, 0.3, 1.2, 0.4, 0.35, 5.0, 0.6, 3.5])
    rec["lane_change"] = lane_change

    @jax.jit
    def masked_states(init, tokens, mask):
        params = D.init_sdar_params(jax.random.key(SMALL["data_seed"] + 1), cfg, init)
        seq = {"tokens": tokens, "mask": mask, "weight": mask.astype(jnp.float32)}
        return D.sdar_forward(params, seq, cfg)[2][-1][S:]

    rec["masked_states"] = lambda hparams, tokens, mask: masked_states(
        jnp.float32(hparams[3]), tokens, mask)
    numbers = {name: (value, limit) for name, value, limit in reference.compare(
        SMALL, None, [rec], seed=5, control=fault == "control")}
    groups = ("all", "ends", "experts", "attention")
    assert sorted(numbers) == sorted(
        ["change_gap_" + g for g in groups] + ["sight_leak", "loss_gap_max"])
    # the rule of sight read outright: exactly nothing moves that may not;
    # the leak moves a row by a share of the states' own size
    leak, limit = numbers["sight_leak"]
    assert leak > 1e-2 > limit if fault == "leaky" else leak == 0.0
    for group in groups:
        value, limit = numbers["change_gap_" + group]
        if group in shows:
            assert value > shows[group], (group, value)
        elif fault is None:
            # float32 on both sides: at lr 2e-4 a step is a few float32 units
            # of a leaf's entries
            assert value < 5e-3 < limit, (group, value)
    if fault is not None:
        # what the contract asks of the limits: a state left unchanged and
        # the precision below are not correct; nor is a loss without its
        # weights, nor (at this size: on the chip at random weights it reads
        # 0.016 to 0.027 against a limit of 0.02) the leak
        assert any(numbers["change_gap_" + g][0] > numbers["change_gap_" + g][1]
                   for g in groups)
    # the record's losses are made up (10.0 ..): the net is not what is tested
    assert numbers["loss_gap_max"][1] == 0.25


def test_a_change_that_is_no_number_reads_infinity(reference):
    want = {"embed": jnp.ones((3, 2)), "norm_f": jnp.ones((2,)), "head": jnp.ones((2, 3)),
            "l0": {"wq": jnp.full((2, 2), 2.0), "norm1": jnp.ones((2,))},
            "l1": {"router": jnp.ones((2, 2)), "e_up": jnp.ones((2, 2))}}
    got = dict(want, embed=jnp.full((3, 2), jnp.nan))
    gaps = reference.change_gaps(got, want)
    assert gaps["ends"] == np.inf and gaps["all"] == np.inf
    assert gaps["attention"] == 0.0 and gaps["experts"] == 0.0
    half = dict(want, l0=dict(want["l0"], wq=jnp.ones((2, 2))))
    assert reference.change_gaps(half, want) == {
        "all": pytest.approx(np.sqrt(4.0 / (6 + 2 + 6 + 16 + 2 + 4 + 4))), "ends": 0.0,
        "experts": 0.0, "attention": pytest.approx(0.5)}


def test_the_lanes_facts_are_its_models(lane_config):
    cfg = _cfg(lane_config)
    facts = D.make_sdar_eval_fn(cfg, data_seed=0).lane_facts
    assert facts.counters == lane.LANE_COUNTERS + ("diffusion_masked_share",) + (
        D.ATTENTION_COUNTERS) + ("attn_scores_in_vmem", "attn_rotation_in_vmem",
                                 "moe_combine_by_gather", "moe_products_in_vmem",
                                 "diffusion_rows_per_token")
    # the data tokens of a step; the rows are twice that
    assert facts.tokens_per_step == S and facts.traced_budget
    full = D.SdarConfig()
    assert lane._count_params(
        lambda: D.init_sdar_params(jax.random.key(0), full, 1.0)) == 456_346_624
    # one lane fits a chip, two do not
    assert 16.9e9 / 2 < D.sdar_lane_bytes(full) < 16.9e9
    # the causal lanes hand the trainer a row of ids and no entry
    assert lane.head_exit(1, 1e-6).entry is None
