"""The Laguna-XS.2 lane against the benchmark's plain reference, on the CPU at
a small size (``laguna_small.py``): the loss and every gradient leaf, three
steps, the partial rotation against the channel-by-channel formula, the
window's edge, the gate a head, two head counts in one lane (a group of query
heads that is no power of two through the fused kernels), the chip's share of
the expert layer with the shared expert counted once, the vocabulary slice's
loss, and the configuration's file.

Where a test holds the equations to the reference it sets the lanes'
matrix-product operands to float32 (``lane._OPERAND``): then only the order
of float32 sums differs, and the tolerances say so. Where it runs the lane
as the chip does (bfloat16 operands), the tolerance is bfloat16's.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.workloads import laguna as L
from hpbandster_tpu.workloads import lane

from laguna_small import BENCHMARK, SMALL, load, scatters_and_sorts, small

ROOT = os.path.dirname(BENCHMARK)
PUBLISHED = os.path.join(BENCHMARK, "configs", "laguna-xs2-sgd.json")


@pytest.fixture(scope="module")
def reference():
    return load("reference", "laguna-xs2-sgd.py")


@pytest.fixture(scope="module")
def lane_config():
    # the builders import the harness's ``program`` by that name
    sys.modules.setdefault("program", load("program.py"))
    return load("configs", "laguna-xs2-sgd.py").lane_config


@pytest.fixture
def float32_operands(monkeypatch):
    monkeypatch.setattr(lane, "_OPERAND", jnp.float32)


def _cfg(lane_config, config=SMALL):
    return lane_config(config)._replace(attn_query_block=16)


# ------------------------------------------------- the lane and the reference
def test_weights_and_tokens_come_from_the_seed_alike(reference, lane_config):
    cfg, key = _cfg(lane_config), jax.random.key(1)
    ours = L.init_laguna_params(key, cfg, 0.7)
    theirs = reference.init_params(SMALL, key, 0.7)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()), ours, theirs)))
    for a, b in zip(L.make_token_dataset(jax.random.key(0), cfg), reference.dataset(SMALL)):
        assert a.shape[1] == 65 and bool((a == b).all())


def test_one_lane_holds_two_head_counts(lane_config):
    """A full layer's leaves are 6 heads wide, a window layer's 8; layer 0
    has the dense SwiGLU and no expert, the others the experts, their router
    and the shared one; every layer has the gate, a column a head."""
    params = L.init_laguna_params(jax.random.key(1), _cfg(lane_config), 1.0)
    shapes = lambda i: {k: v.shape for k, v in params["l%d" % i].items()}
    for i, heads in enumerate([6, 8, 8, 8, 6]):
        assert shapes(i)["wq"] == (64, heads * 16) and shapes(i)["wo"] == (heads * 16, 64)
        assert shapes(i)["w_head_gate"] == (64, heads)
        assert shapes(i)["wk"] == shapes(i)["wv"] == (64, 2 * 16)
    assert {"ffn_gate", "ffn_up", "ffn_down"} <= set(shapes(0)) and "router" not in shapes(0)
    for i in range(1, 5):
        assert shapes(i)["router"] == (64, 16) and shapes(i)["e_gate"] == (4, 64, 32)
        assert shapes(i)["shared_up"] == (64, 32) and "ffn_up" not in shapes(i)
    published = L.LagunaConfig()
    assert [dict(published.heads_by_kind)[k] for k in published.layer_kinds] == [
        48, 64, 64, 64, 48]
    assert [L._heads_per_kv(published, k) for k in ("full", "sliding")] == [6, 8]


def test_loss_and_every_gradient_leaf_match_the_reference(
        reference, lane_config, float32_operands):
    cfg = _cfg(lane_config)
    params = L.init_laguna_params(jax.random.key(1), cfg, 1.0)
    tokens = L.make_token_dataset(jax.random.key(0), cfg)[0][0]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: L.laguna_loss(p, tokens, cfg)[0]))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_fn(p, tokens, SMALL)))(params)
    # float32 both sides, another order of summation (blocks of keys against
    # the whole row, grouped against masked products): 1e-5 of the loss
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want_grads)):
        worst = float(jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-12))
        assert worst < 2e-5, (jax.tree_util.keystr(path), worst)


def test_the_forward_pass_of_the_trainer_is_the_loss(lane_config, float32_operands):
    cfg = _cfg(lane_config)
    params = L.init_laguna_params(jax.random.key(1), cfg, 1.0)
    tokens = L.make_token_dataset(jax.random.key(0), cfg)[1][0]
    loss, counters = L.laguna_loss(params, tokens, cfg)
    again, same, hs = L.laguna_forward(params, tokens, cfg)
    assert float(loss) == pytest.approx(float(again), rel=1e-6)
    np.testing.assert_allclose(counters, same)
    assert len(hs) == 6 and all(h.shape == (64, 64) for h in hs)
    # the dense layer counts no choice, the four expert layers do
    assert float(counters[0].sum()) == 0 and (np.asarray(counters[1:, 0]) > 0).all()


def test_the_loss_is_over_the_vocabulary_slice(lane_config, float32_operands):
    """Ids, logits and loss are over the slice's rows: the lane's loss is the
    mean next-token cross-entropy of a softmax over ``vocab_rows`` logits,
    worked here from the last state by hand."""
    cfg = _cfg(lane_config)
    params = L.init_laguna_params(jax.random.key(3), cfg, 1.0)
    train, val = L.make_token_dataset(jax.random.key(0), cfg)
    assert int(train.max()) < cfg.vocab_rows == 96 and int(val.max()) < 96
    assert params["embed"].shape == (96, 64) and params["head"].shape == (64, 96)
    tokens = train[1]
    loss, _, hs = L.laguna_forward(params, tokens, cfg)
    logits = lane._rms(hs[-1], params["norm_f"], cfg.rms_norm_eps) @ params["head"]
    by_hand = -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(64), tokens[1:]])
    assert float(loss) == pytest.approx(float(by_hand), rel=1e-6)


@pytest.mark.parametrize("operand, limit", [
    # float32 operands: rounding of sums only, three steps amplify it little
    (jnp.float32, 1e-4),
    # as the chip runs it: bfloat16 operands (2^-8 a product) through five
    # layers and three steps
    (jnp.bfloat16, 2e-2),
])
def test_three_steps_match_the_reference(reference, lane_config, monkeypatch, operand, limit):
    monkeypatch.setattr(lane, "_OPERAND", operand)
    eval_fn = L.make_laguna_eval_fn(_cfg(lane_config), data_seed=SMALL["data_seed"])
    vec = jnp.asarray([0.75, 0.5, 0.3, 0.5])
    got = float(jax.jit(lambda v: eval_fn(v, 3.0))(vec))
    hparams = [float(x) for x in lane.decode_lane_hparams(vec)]
    start, want = reference.reference_losses(SMALL, hparams, [0, 3])
    assert want < start - 0.01  # the steps moved the loss: it is compared
    assert abs(got - want) < limit * (1 + abs(want))


def test_the_first_steps_change_is_the_references(reference, lane_config, float32_operands):
    """What the comparison on the chip reads: the trainer's ``change`` after
    one step against the reference's own first step, by group of leaves; in
    float32 the groups agree to rounding, and a state left unchanged reads 1."""
    eval_fn = L.make_laguna_eval_fn(_cfg(lane_config), data_seed=SMALL["data_seed"])
    vec = jnp.asarray([0.6, 0.5, 0.3, 0.4])
    got = jax.jit(lambda v: eval_fn.change(v, 1.0))(vec)
    hparams = [float(x) for x in lane.decode_lane_hparams(vec)]
    want = reference.first_step_change(SMALL, hparams)
    gaps = reference.change_gaps(got, want, SMALL)
    assert set(gaps) == {"attention", "dense_ffn", "experts", "embed_head"}
    assert max(gaps.values()) < 1e-4, gaps
    still = reference.change_gaps(jax.tree.map(jnp.zeros_like, want), want, SMALL)
    assert all(value == pytest.approx(1.0) for value in still.values())
    # a bfloat16 state, read from the arrays as they are stored, loses the
    # step the comparison reads: the control of ``correct``
    at_step_lr = [reference.STEP_LR] + hparams[1:]
    rough = reference.change_gaps(
        reference.first_step_change(SMALL, at_step_lr, jnp.bfloat16),
        reference.first_step_change(SMALL, at_step_lr), SMALL)
    assert max(rough.values()) > 0.5, rough
    # every leaf is of exactly one group
    holds = reference.groups(SMALL)
    for path, _ in jax.tree_util.tree_leaves_with_path(want):
        assert sum(hold([k.key for k in path]) for hold in holds.values()) == 1, path


def test_the_reference_trains_by_the_gradient_of_its_loss(reference):
    """The reference steps layer by layer (``jax.vjp`` chained by hand, so
    that layers of a kind share a compiled function): with no momentum and
    no decay the momentum after one step is ``jax.grad`` of its ``loss_fn``,
    every leaf; float32 sums in another order."""
    fns = reference.lane_functions(SMALL, jnp.float32)
    p = fns.init(jnp.float32(1.0))
    v = jax.tree.map(jnp.zeros_like, p)
    train, _ = reference.dataset(SMALL)
    want = jax.grad(reference.loss_fn)(p, train[2], SMALL)
    changed = {}
    new_p, got = fns.step(p, v, 2, jnp.float32(0.5), jnp.float32(0.0), jnp.float32(0.0), changed)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, atol=1e-5 * float(jnp.abs(w).max()) + 1e-12, err_msg=str(path))
    np.testing.assert_allclose(new_p["head"], p["head"] - 0.5 * want["head"], atol=1e-6)
    np.testing.assert_allclose(changed["head"], new_p["head"] - p["head"], atol=1e-7)


# ------------------------------------------------------- partial rotation
def _by_channel(x, inv_freq, factor, positions):
    """The rotation worked channel by channel in numpy: ``x`` [T, H, d],
    ``w = 2 len(inv_freq)`` channels turned, the rest as they are."""
    x = np.asarray(x, np.float64)
    out, half = x.copy(), len(inv_freq)
    for t in range(x.shape[0]):
        for i in range(half):
            angle = float(np.float32(positions[t]) * np.float32(inv_freq[i]))
            c, s = factor * np.cos(angle), factor * np.sin(angle)
            out[t, :, i] = x[t, :, i] * c - x[t, :, i + half] * s
            out[t, :, i + half] = x[t, :, i + half] * c + x[t, :, i] * s
    return out


@pytest.mark.parametrize("kind, turned", [("full", 8), ("sliding", 16)])
def test_the_rotation_is_the_channel_by_channel_formula(reference, lane_config, kind, turned):
    """A full layer turns the first half of each head (channel ``i`` with
    ``i + 4`` of 16) by YaRN's frequencies, cos and sin times the attention
    factor, and leaves the second half unturned and unscaled; a window layer
    the whole head by plain RoPE. The lane's tables and ``_rotate``, the
    reference's ``rotary`` and ``rope``, and numpy agree."""
    cfg = _cfg(lane_config)
    inv_freq, factor = L.rotary_inv_freq(cfg, kind)
    assert 2 * len(inv_freq) == turned
    assert factor == (pytest.approx(1.4158883083359672) if kind == "full" else 1.0)
    t = 24
    x = jax.random.normal(jax.random.key(7), (t, 3, 16))
    cos, sin = lane._rotary_tables(inv_freq, factor, t, 16)
    assert cos.shape == sin.shape == (t, 16)
    rotary = None if turned == 16 else turned
    ours = lane._rotate(x, cos, sin, rotary)
    want = _by_channel(x, inv_freq, factor, np.arange(t))
    np.testing.assert_allclose(ours, want, atol=1e-5)
    np.testing.assert_array_equal(ours[..., turned:], x[..., turned:])
    theirs = reference.rope(x, *reference.rotary(SMALL, kind, t))
    np.testing.assert_allclose(theirs, want, atol=1e-5)


def test_yarn_over_the_turned_half_against_numbers_worked_by_hand(reference, lane_config):
    cfg = L.LagunaConfig()
    plain, one = L.rotary_inv_freq(cfg, "sliding")
    yarn, factor = L.rotary_inv_freq(cfg, "full")
    assert plain.shape == (64,) and yarn.shape == (32,) and one == 1.0
    assert factor == pytest.approx(0.1 * np.log(64) + 1.0, abs=1e-12)
    np.testing.assert_allclose(plain[[0, 1, 63]], [1.0, 1e4 ** (-2 / 128), 1e4 ** (-126 / 128)])
    # c(r) = 64 ln(4096 / (2 pi r)) / (2 ln 500000): c(64) = 5.66, c(1) = 15.80
    published = json.load(open(PUBLISHED))
    assert reference.yarn_range(published["rope_parameters"]["full_attention"], 64) == (5, 16)
    base = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(yarn[:6], base[:6])
    np.testing.assert_allclose(yarn[16:], base[16:] / 64)
    ramp = (10 - 5) / (16 - 5)
    assert yarn[10] == pytest.approx((1 - ramp) * base[10] + ramp * base[10] / 64)
    # the configuration's file gives the same tables as the reference builds
    built = lane_config(published)
    for kind in ("sliding", "full"):
        inv_freq, factor = L.rotary_inv_freq(built, kind)
        ours = lane._rotary_tables(inv_freq, factor, 40)
        for a, b in zip(ours, reference.rotary(published, kind, 40)):
            half = b.shape[1]
            np.testing.assert_allclose(a[:, :half], b, atol=1e-6)
            np.testing.assert_allclose(a[:, half:], b, atol=1e-6)


@pytest.mark.parametrize("kind", ["full", "sliding"])
def test_heads_side_by_side_turn_as_heads_apart(lane_config, kind):
    """``lane._rotate_side_by_side`` on ``[T, heads x d]`` is ``_rotate`` on
    ``[T, heads, d]`` to the last bit at a rotary width of half a head (and
    of a whole one), and so is its gradient: the same products and sums an
    entry."""
    cfg = _cfg(lane_config)
    inv_freq, factor = L.rotary_inv_freq(cfg, kind)
    t, heads = 24, 6
    cos, sin = lane._rotary_tables(inv_freq, factor, t, 16)
    rotary = None if 2 * len(inv_freq) == 16 else 2 * len(inv_freq)
    x = jax.random.normal(jax.random.key(2), (t, heads * 16))
    apart = lambda x: lane._rotate(x.reshape(t, heads, 16), cos, sin, rotary).reshape(t, -1)
    beside = lambda x: lane._rotate_side_by_side(x, cos, sin, rotary, scope="lane.gqa")
    np.testing.assert_array_equal(beside(x), apart(x))
    cube = lambda turn: jax.grad(lambda x: (turn(x) ** 3).sum())(x)
    np.testing.assert_array_equal(cube(beside), cube(apart))


# ------------------------------------------------------- window and gate
def _mixer_leaves(hidden, g, r, d, seed=4):
    keys = jax.random.split(jax.random.key(seed), 6)
    shapes = {"wq": (hidden, g * r * d), "wk": (hidden, g * d), "wv": (hidden, g * d),
              "w_head_gate": (hidden, g * r), "wo": (g * r * d, hidden)}
    return {name: jax.random.normal(key, shape) * shape[0] ** -0.5
            for key, (name, shape) in zip(keys, shapes.items())}


def test_a_position_512_back_is_unseen_and_511_back_is_seen(float32_operands):
    """The published window through the mixer: move the input at ``j`` and the
    output at ``j + 511`` moves, at ``j + 512`` and beyond it does not, nor
    anywhere before ``j``."""
    cfg = L.LagunaConfig()
    t, hidden, g, r, d = 640, 32, 1, 2, 8
    p = _mixer_leaves(hidden, g, r, d)
    mixer = lambda x: lane.attention_mixer(
        x, p, kv_heads=g, heads_per_kv=r, head_dim=d,
        inv_freq=1e4 ** (-np.arange(0, d, 2) / d), factor=1.0,
        sight=L._sight(cfg, "sliding"), block=128, scope="lane.swa")
    assert L._sight(cfg, "sliding") == 512 and L._sight(cfg, "full") is None
    x = jax.random.normal(jax.random.key(0), (t, hidden))
    j = 100
    moved = np.asarray(jnp.abs(mixer(x.at[j].add(3.0)) - mixer(x)).max(axis=1) > 0)
    assert moved[j:j + 512].all() and moved[j + 511]
    assert not moved[:j].any() and not moved[j + 512:].any()


def test_a_gate_of_zero_weights_halves_the_mixers_output(float32_operands):
    """``sigmoid(0) = 1 / 2`` a head: with ``W_g = 0`` the mixer gives half of
    what it gives without the leaf; and a gate that is large on one head and
    very negative on the others passes that head alone."""
    t, hidden, g, r, d = 48, 32, 2, 3, 8
    p = _mixer_leaves(hidden, g, r, d)
    x = jax.random.normal(jax.random.key(0), (t, hidden))
    mixer = lambda p: lane.attention_mixer(
        x, p, kv_heads=g, heads_per_kv=r, head_dim=d,
        inv_freq=1e4 ** (-np.arange(0, d // 2, 2) / (d // 2)), factor=1.3,
        sight=None, block=16, scope="lane.gqa")
    ungated = mixer({k: v for k, v in p.items() if k != "w_head_gate"})
    halved = mixer(dict(p, w_head_gate=jnp.zeros_like(p["w_head_gate"])))
    np.testing.assert_allclose(halved, 0.5 * ungated, atol=1e-6)
    # x has a constant channel so that a column of the gate is a bias
    x = x.at[:, 0].set(1.0)
    one_head = jnp.full((hidden, g * r), 0.0).at[0].set(-40.0).at[0, 4].set(40.0)
    only = {k: v for k, v in p.items() if k != "w_head_gate"}
    alone = dict(only, wo=only["wo"].at[:4 * d].set(0.0).at[5 * d:].set(0.0))
    np.testing.assert_allclose(mixer(dict(p, w_head_gate=one_head)), mixer(alone), atol=1e-5)


def test_the_gate_multiplies_a_head_as_an_operand_on_the_plain_path_too():
    """The gate's sigmoid is rounded to the products' operand type before the
    paths part (the kernels' path lays it across a head's lanes by a product,
    which rounds it): the plain path gives, to the bit, what its own steps
    give with the gate rounded so, and not what they give with it whole."""
    t, hidden, g, r, d = 48, 32, 2, 3, 8
    p = _mixer_leaves(hidden, g, r, d)
    x = jax.random.normal(jax.random.key(0), (t, hidden))
    got = lane.attention_mixer(
        x, p, kv_heads=g, heads_per_kv=r, head_dim=d, inv_freq=None, factor=1.0,
        sight=None, block=16, scope="lane.gqa")
    q, k, v, logits = lane._mm_beside(x, p["wq"], p["wk"], p["wv"], p["w_head_gate"])
    out = lane.banded_attention(
        q.reshape(t, g, r, d), k.reshape(t, g, d), v.reshape(t, g, d), None, 16)
    whole = jax.nn.sigmoid(logits)
    by_steps = lambda gate: lane._mm(
        (out * gate.reshape(t, g, r, 1)).reshape(t, g * r * d), p["wo"])
    rounded = whole.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.abs(rounded - whole).max()) > 1e-4
    np.testing.assert_array_equal(got, by_steps(rounded))
    assert float(jnp.abs(got - by_steps(whole)).max()) > 0


@pytest.mark.parametrize("operand, limit", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("window, r, rotary", [
    # a full layer's group of 6 (and 3) over half-rotated heads, a window
    # layer's group of 8 over whole ones, both gated
    (None, 6, 64), (None, 3, 64), (100, 8, 128), (100, 6, 64)])
def test_the_mixer_with_the_kernels_is_the_mixer_without(
        monkeypatch, operand, limit, window, r, rotary):
    """``attention_mixer`` as the chip runs it (the rule told that Mosaic
    compiles here, the kernels in the Pallas interpreter, heads side by side
    from the projections through the rotation of part of each head and the
    gate to ``wo``) against itself in plain JAX: the output and the gradients
    with respect to its input, its four matrices and the gate, within
    bfloat16 operands' 2e-2 of the largest entry, and with float32 operands,
    where nothing is rounded and the two paths are the same sums, 2e-5."""
    from hpbandster_tpu.ops import pallas_attention, pallas_rotary

    monkeypatch.setattr(lane, "_OPERAND", operand)
    t, g, d, hidden = 256, 2, 128, 64
    p = _mixer_leaves(hidden, g, r, d)
    x = jax.random.normal(jax.random.key(0), (t, hidden))
    mixer = lambda x, p: lane.attention_mixer(
        x, p, kv_heads=g, heads_per_kv=r, head_dim=d,
        inv_freq=10000.0 ** (-np.arange(0, rotary, 2) / rotary), factor=1.4, sight=window,
        block=64, scope="lane.gqa")
    weigh = jax.random.normal(jax.random.key(5), (t, hidden))
    want, pull = jax.vjp(mixer, x, p)
    want = (want,) + tuple(jax.tree.leaves(pull(weigh)))

    monkeypatch.setattr(lane, "pallas_available", lambda: True)
    monkeypatch.setattr(lane, "_KERNEL_ROWS", 128)
    monkeypatch.setattr(lane, "_KERNEL_KEYS", 128)
    monkeypatch.setattr(lane, "_PLAIN_KEYS", 0)
    in_interpreter = pallas_attention.fused_banded_attention
    calls = []
    monkeypatch.setattr(
        pallas_attention, "fused_banded_attention",
        lambda *args: calls.append(args[3:6]) or in_interpreter(*args, True))
    # the queries' and the keys' turn is the rotation's kernel on this path
    turn_in_interpreter = pallas_rotary.rotate_side_by_side
    turns = []
    monkeypatch.setattr(
        pallas_rotary, "rotate_side_by_side",
        lambda *args: turns.append(args[3:]) or turn_in_interpreter(*args, True))
    got, pull = jax.vjp(mixer, x, p)
    # a block of queries is a power of two: 16 for 6 or 8 heads, 32 for 3
    assert calls == [((g, r, d), lane._rule(window), (32 if r == 3 else 16, 128))]
    assert turns == [(rotary // 2, operand, "lane.gqa")] * 2
    for ours, theirs in zip((got,) + tuple(jax.tree.leaves(pull(weigh))), want):
        np.testing.assert_allclose(ours, theirs, atol=limit * float(jnp.abs(theirs).max()))


@pytest.mark.parametrize("operand, limit", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("window, g, r, tiles", [
    (None, 1, 6, (128, 128)), (None, 2, 3, (64, 128)), (100, 1, 6, (64, 128)),
    (200, 2, 6, (32, 128))])
def test_the_fused_kernels_take_a_group_that_is_no_power_of_two(
        monkeypatch, operand, limit, window, g, r, tiles):
    """``ops.pallas_attention`` in the Pallas interpreter against the plain
    form at 6 and 3 query heads a key/value head (768 and 384 rows a step at
    128 queries): values and gradients."""
    from hpbandster_tpu.ops import pallas_attention

    monkeypatch.setattr(lane, "_OPERAND", operand)
    t, d = 384, 128
    keys = jax.random.split(jax.random.key(t + r), 3)
    q = jax.random.normal(keys[0], (t, g, r, d))
    k, v = (jax.random.normal(key, (t, g, d)) for key in keys[1:])
    tiles, rule = pallas_attention.Tiles(*tiles), lane._rule(window)
    assert pallas_attention.fits(t, d, r, g, tiles)
    flat = lambda x: x.reshape(t, -1)
    fused = lambda q, k, v: pallas_attention.fused_banded_attention(
        flat(q), flat(k), flat(v), (g, r, d), rule, tiles, operand, "lane.gqa", True
    ).reshape(q.shape)
    plain = lambda q, k, v: lane.banded_attention(q, k, v, window, 64)
    weigh = jax.random.normal(jax.random.key(1), q.shape)
    got, pull = jax.vjp(fused, q, k, v)
    want, pull_plain = jax.vjp(plain, q, k, v)
    for ours, theirs in zip((got,) + pull(weigh), (want,) + pull_plain(weigh)):
        np.testing.assert_allclose(
            ours, theirs, atol=limit * max(float(jnp.abs(theirs).max()), 1.0))


def test_the_tiles_of_a_group_of_six_and_the_lanes_share_of_layers(monkeypatch):
    """``lane._kernel_tiles`` sizes a block of queries as a power of two: a
    group of 6 takes 128 queries (768 rows a step) where ``1024 // 6 = 170``
    fitted no tile; every shape a cell ran before takes the tiles it took.
    The lane's ``attn_scores_in_vmem`` is a share of its layers, each at its
    own head count; its tiles are summed once a query head."""
    cfg = L.LagunaConfig()
    heads, sights = L._attention_shapes(cfg)
    assert heads == [6, 8, 8, 8, 6] and sights == [None, 512, 512, 512, None]
    assert lane.attention_counters(8192, 128, heads, 8, sights) == (
        ("attn_scores_in_vmem", 0.0), ("attn_rotation_in_vmem", 0.0))
    plain_bytes = lane.attention_alive_bytes(8192, 8, heads, 128, sights, 1024)
    assert plain_bytes == 3 * 4 * 6 * 1024 * 8192   # a full layer's widest block, six heads
    monkeypatch.setattr(lane, "pallas_available", lambda: True)
    assert lane._kernel_tiles(8192, 128, 6, 8) == (128, 512)
    assert lane._kernel_tiles(8192, 128, 8, 8, 512) == (128, 512)
    assert lane._kernel_tiles(8192, 128, 3, 8) == (256, 512)
    assert lane._kernel_tiles(8192, 128, 12, 4) == (64, 512)
    # what the accepted cells run: Mellum2 and SDAR (8 heads of 128 on 4),
    # LFM2 (pairs of heads of 64, 4 a head), Olmo-Hybrid (one a head)
    assert lane._kernel_tiles(8192, 128, 8, 4) == (128, 512)
    assert lane._kernel_tiles(8192, 128, 8, 4, 1024) == (128, 512)
    assert lane._kernel_tiles(8192, 128, 8, 4, lane.BlockDiffusion(4)) == (128, 512)
    assert lane._kernel_tiles(8192, 64, 4, 8) == (128, 512)
    assert lane._kernel_tiles(4096, 128, 1, 16) == (512, 512)
    assert lane._kernel_tiles(2048, 128, 1, 30) is None and lane._kernel_tiles(
        2048, 128, 1, 16) is None
    assert lane.attention_counters(8192, 128, heads, 8, sights) == (
        ("attn_scores_in_vmem", 1.0), ("attn_rotation_in_vmem", 1.0))
    # a lane of which some layers fit and some do not reads their share
    assert lane.attention_counters(8192, 128, [6, 8, 8, 8, 6], 8, [
        None, 512, lane.BlockDiffusion(24), 512, None]) == (
        ("attn_scores_in_vmem", 0.8), ("attn_rotation_in_vmem", 0.8))
    with pytest.raises(ValueError):
        lane.attention_counters(8192, 128, [6, 8], 8, [None, 512, 512])
    assert lane.attention_alive_bytes(8192, 8, heads, 128, sights, 1024) == (
        4 * 8192 * 64 * (128 + 128)) < plain_bytes
    tiles = [lane._kernel_tiles(8192, 128, r, 8, s) for r, s in zip(heads, sights)]
    from hpbandster_tpu.ops.pallas_attention import Tiles, tiles_visited

    band = tiles_visited(8192, lane.Causal(512), Tiles(128, 512))
    triangle = tiles_visited(8192, lane.Causal(), Tiles(128, 512))
    assert (band, triangle) == (1 + 1 + 1 + 1 + 2 * 60, 4 * sum(range(1, 17)))
    computed, square = lane.attention_key_blocks(
        8192, sights, 1024, tiles, heads=[8 * r for r in heads])
    assert computed == 2 * 48 * triangle + 3 * 64 * band
    assert square == (2 * 48 + 3 * 64) * 64 * 16
    # unweighted and one tile for all: a count a query head, as it was
    assert lane.attention_key_blocks(8192, sights, 1024, tiles[0]) == (
        2 * triangle + 3 * band, 5 * 64 * 16)


# ----------------------------------------------------------- expert layer
def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer(
        reference, lane_config, float32_operands):
    """Four chips of four experts each against the reference's layer over all
    sixteen (at the published size eight shares of 32 make the 256): the
    guide's tie of the chip's share to the model. Every chip computes the
    shared expert alike: it is counted once."""
    config = small(cut={"experts_held": list(range(16))})
    whole = reference.init_params(config, jax.random.key(2), 1.0)["l1"]
    x = jax.random.normal(jax.random.key(3), (64, 64))
    want = reference.experts(x, whole, config)
    shared = lane._swiglu(x, whole["shared_gate"], whole["shared_up"], whole["shared_down"])
    total, choices = 0.0, 0.0
    for share in range(4):
        held = list(range(4 * share, 4 * share + 4))
        cfg = _cfg(lane_config, small(cut={"experts_held": held}))
        p = dict(whole, **{k: whole[k][4 * share:4 * share + 4]
                           for k in ("e_gate", "e_up", "e_down")})
        y, counters = L.moe_held_experts(x, p, L._experts(cfg))
        # what the share's own experts give: its output less the shared one
        total = total + (y - shared)
        choices += float(counters[0])
        # and the reference given the same share agrees with the program's
        np.testing.assert_allclose(
            y, reference.experts(x, p, config, held=held),
            atol=1e-5 * float(jnp.abs(want).max()))
    assert choices == 64 * 4  # every token-choice fell on exactly one chip
    np.testing.assert_allclose(total + shared, want, atol=1e-5 * float(jnp.abs(want).max()))
    # the routed part alone, without the shared expert, as the reference sums it
    routed = sum(reference.experts(
        x, dict(whole, **{k: whole[k][4 * s:4 * s + 4] for k in ("e_gate", "e_up", "e_down")}),
        config, held=list(range(4 * s, 4 * s + 4)), shared=False) for s in range(4))
    np.testing.assert_allclose(routed + shared, want, atol=1e-5 * float(jnp.abs(want).max()))


def test_the_expert_layer_and_its_gradient_are_the_references(
        reference, lane_config, float32_operands):
    """Sigmoid scores, the top 4 of 16, renormalised and scaled by 2.5, the
    shared expert once and no bias: the one expert layer as this model states
    its router, value and gradient of every leaf."""
    cfg = _cfg(lane_config)
    facts = L._experts(cfg)
    assert (facts.score, facts.scaling, facts.top_k, facts.outputs) == ("sigmoid", 2.5, 4, 16)
    p = {k: v for k, v in reference.init_params(SMALL, jax.random.key(4), 1.0)["l2"].items()
         if k.startswith(("router", "shared_", "e_"))}
    assert "router_bias" not in p and "shared_gate" in p
    x = jax.random.normal(jax.random.key(6), (64, 64))
    y, counters = lane.moe_held_experts(x, p, facts)
    np.testing.assert_allclose(y, reference.experts(x, p, SMALL), atol=2e-5)
    ours = jax.grad(lambda p: (lane.moe_held_experts(x, p, facts)[0] ** 2).sum())(p)
    theirs = jax.grad(lambda p: (reference.experts(x, p, SMALL) ** 2).sum())(p)
    for name in p:
        np.testing.assert_allclose(
            ours[name], theirs[name], atol=2e-5 * float(jnp.abs(theirs[name]).max()) + 1e-9,
            err_msg=name)
    assert scatters_and_sorts(
        lambda x, p: lane.moe_held_experts(x, p, facts), x, p) == [("s32", "scatter")]


# ----------------------------------------------------- the configuration
def test_configuration_file_keeps_every_published_width(lane_config):
    config = json.load(open(PUBLISHED))
    entry = next(c for c in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["configs"]
                 if c["name"] == "laguna-xs2-sgd")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer"]
    assert config["published"] == {
        "num_hidden_layers": 40, "num_experts": 256, "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        len(config["cut"]["layers"]), len(config["cut"]["experts_held"]), 100352 // 8)
    assert config["layer_types"] == (
        ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"])
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert config["cut"]["router_outputs"] == 256 and config["cut"]["chips_sharing_a_layer"] == 8
    assert config["cut"]["experts_held"] == list(range(32))
    # every width as published
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "moe_intermediate_size", "shared_expert_intermediate_size",
        "num_experts_per_tok", "sliding_window", "moe_routed_scaling_factor",
        "partial_rotary_factor", "rms_norm_eps", "gating")] == [
            2048, 48, 8, 128, 8192, 512, 512, 8, 512, 2.5, 0.5, 1e-6, True]
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
            "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
    # the catalog's row, where the sandbox has it: every key but the cuts
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog)) if r["name"] == "Laguna-XS.2")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert key in config["reduced"] or config[key] == value, key
        for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
            assert config[key] == row["config"][key][:5]
    # the gate first, in those words, with the catalog row it was read from
    assert list(config["assumed"])[0] == "gate"
    assert "THE ONE INFERENCE THE CONFIGURATION RESTS ON" in config["assumed"]["gate"]
    assert "Laguna-S-2.1" in config["assumed"]["gate"]
    assert set(config["assumed"]) >= {
        "gate", "router", "router_bias", "qk_norm", "partial_rotary", "attention_factor",
        "window", "init", "tokens", "optimizer", "data_seed"}
    assert len(config["guarantees"]) == 5
    assert lane_config(config) == L.LagunaConfig()


@pytest.mark.parametrize("change, said", [
    ({"layer_types": ["full_attention", "linear_attention", "sliding_attention",
                      "sliding_attention", "full_attention"]}, "full_attention or sliding"),
    ({"mlp_layer_types": ["dense", "sparse", "sparse", "moe", "sparse"]}, "dense or sparse"),
    ({"num_attention_heads_per_layer": [6, 8, 8, 6, 6]}, "differ"),
    ({"num_attention_heads_per_layer": [6, 8, 8, 8]}, "one entry a layer"),
    ({"num_attention_heads_per_layer": [5, 8, 8, 8, 5]}, "whole groups"),
    ({"rope_parameters": {"sliding_attention": {
        "rope_type": "yarn", "rope_theta": 100, "partial_rotary_factor": 1}}}, "plain RoPE"),
    ({"gating": "per-channel"}, "a gate a head"),
    ({"tie_word_embeddings": True}, "untied"),
])
def test_the_builder_refuses_what_the_lane_does_not_implement(lane_config, change, said):
    with pytest.raises(ValueError, match=said):
        lane_config(small(**change))


def test_lane_counts_agree_with_the_lane():
    config = json.load(open(PUBLISHED))
    sys.path.insert(0, BENCHMARK)
    try:
        counts = load("lane_counts_laguna.py")
    finally:
        sys.path.remove(BENCHMARK)
    cfg = L.LagunaConfig()
    n_params = lane._count_params(lambda: L.init_laguna_params(jax.random.key(0), cfg, 1.0))
    assert n_params == counts.lane_params(config) == 691_623_936
    # by hand: attention with its gate at 48 and at 64 heads, the dense FFN,
    # an expert layer outside its experts and one expert
    assert counts.attention_params(config, 48) == 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    assert counts.attention_params(config, 64) == 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    assert counts.ffn_params(config, "dense_ffn") == 3 * 2048 * 8192
    assert counts.ffn_params(config, "moe") == 2048 * 256 + 3 * 2048 * 512 * (1 + 32)
    # one lane fits the chip beside the bracket's draw, two do not
    assert 12 * n_params < L.laguna_lane_bytes(cfg) < 16.9e9 - 4 * n_params
    assert 16.9e9 < 2 * L.laguna_lane_bytes(cfg)
