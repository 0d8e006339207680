"""The LFM2 lane (gated short convolutions to attention with a per-head
norm, sigmoid-routed experts chosen with a bias, the head tied to the
embedding) against the benchmark's plain reference, on the CPU at a small
size (``lfm2_small.py``): the forward pass and the loss, the trainer's
gradient of every leaf against ``jax.grad`` of the reference's whole loss
(the tied matrix's above all), one and three steps, the convolution, the
bias, the per-head norm, the shares of the expert layer, and the comparison
that decides the cell's ``correct`` with its planted faults.

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

from hpbandster_tpu.workloads import kimi_linear as K
from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads import lfm2 as L

import kimi_small
from lfm2_small import SMALL, load, small


@pytest.fixture(scope="module")
def reference():
    return load("reference", "lfm2-sgd.py")


@pytest.fixture(scope="module")
def lane_config():
    # the builders import the harness's ``program`` by that name
    sys.modules.setdefault("program", load("program.py"))
    return load("configs", "lfm2-sgd.py").lane_config


@pytest.fixture
def float32_operands(monkeypatch):
    monkeypatch.setattr(lane, "_OPERAND", jnp.float32)


def _cfg(lane_config, config=SMALL):
    return lane_config(config)._replace(attn_query_block=16)


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


def _trainers_gradient(params, tokens, cfg, exits=None):
    v, keep = _gradient_steps(params)
    _, got, _, _ = jax.jit(lambda p, v: lane._pass(
        p, v, tokens, jnp.bool_(True), L._visits(cfg), exits or L._exits(cfg), keep))(params, v)
    return got


def test_weights_and_tokens_come_from_the_seed_alike(reference, lane_config):
    cfg, key = _cfg(lane_config), jax.random.key(1)
    ours = L.init_lfm2_params(key, cfg, 0.7)
    theirs = reference.init_params(SMALL, key, 0.7)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()), ours, theirs)))
    # one matrix for the lookup and the head, drawn as the head it is
    assert "head" not in ours and ours["embed"].shape == (256, 64)
    assert float(ours["embed"].std()) == pytest.approx(0.7 / 8, rel=0.02)
    assert bool((ours["l1"]["q_norm"] == 1).all()) and ours["l1"]["k_norm"].shape == (16,)
    assert not float(jnp.abs(ours["l2"]["router_bias"]).max())
    assert ours["l0"]["conv"].shape == (3, 64) and "router" not in ours["l0"]
    for a, b in zip(L.make_token_dataset(jax.random.key(0), cfg), reference.dataset(SMALL)):
        assert a.shape[1] == 33 and bool((a == b).all())


def test_the_loss_and_the_forward_pass_match_the_reference(
        reference, lane_config, float32_operands):
    cfg = _cfg(lane_config)
    params = L.init_lfm2_params(jax.random.key(1), cfg, 1.3)
    tokens = L.make_token_dataset(jax.random.key(0), cfg)[0][0]
    loss, counters = jax.jit(lambda p: L.lfm2_loss(p, tokens, cfg))(params)
    want = jax.jit(lambda p: reference.loss_fn(p, tokens, SMALL))(params)
    # float32 both sides, another order of summation (blocks of keys against
    # the whole row, sorted rows against a masked loop over the experts)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    # the two expert layers count, the leading dense layer does not
    assert counters.shape == (2, 3)
    again, same, hs = L.lfm2_forward(params, tokens, cfg)
    assert float(again) == pytest.approx(float(loss), rel=1e-6)
    np.testing.assert_allclose(same, counters, rtol=1e-6)
    assert len(hs) == 4
    np.testing.assert_allclose(hs[-1], reference.hidden(params, tokens, SMALL), atol=2e-5)


def test_the_trainers_gradient_is_that_of_the_references_whole_loss(
        reference, lane_config, float32_operands):
    """Every leaf, against ``jax.grad`` of the reference's loss, which reads
    the one matrix twice (a lookup, ``E.T`` as the head) and knows nothing of
    how the trainer adds the two gradients."""
    cfg = _cfg(lane_config)
    params = L.init_lfm2_params(jax.random.key(1), cfg, 1.3)
    tokens = L.make_token_dataset(jax.random.key(0), cfg)[0][1]
    want = jax.jit(jax.grad(lambda p: reference.loss_fn(p, tokens, SMALL)))(params)
    worst = _worst(_trainers_gradient(params, tokens, cfg), want)
    assert set(worst) >= {"['embed']", "['norm_f']", "['l0']['conv']", "['l0']['w_in']",
                          "['l1']['q_norm']", "['l1']['k_norm']", "['l2']['router']",
                          "['l2']['e_down']"}
    # float32 both sides, sums in another order: 7e-7 measured
    assert max(worst.values()) < 1e-5, worst
    # the bias enters through the choice alone: no gradient, on either side
    assert not float(jnp.abs(want["l2"]["router_bias"]).max())
    # bfloat16 parameters would not pass: rounding them alone moves a leaf's
    # gradient by more than a hundred times that
    rounded = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    assert max(_worst(_trainers_gradient(rounded, tokens, cfg), want).values()) > 2e-3


_SOUND_EXITS = L._exits


def _lookup_alone(cfg):
    """Planted: the head's gradient never reaches the tied matrix (the
    exits differentiate through a copy that lets none through), so the
    matrix is stepped by the lookup's gradient alone."""
    sound = _SOUND_EXITS(cfg)

    def trained(states, leaves, tokens):
        norm_f, embed = leaves
        return sound.trained(states, (norm_f, jax.lax.stop_gradient(embed)), tokens)

    return sound._replace(trained=trained)


def test_the_tied_matrix_is_stepped_by_the_sum_of_its_two_gradients(
        reference, lane_config, float32_operands):
    cfg = _cfg(lane_config)
    params = L.init_lfm2_params(jax.random.key(1), cfg, 1.0)
    tokens = L.make_token_dataset(jax.random.key(0), cfg)[0][2]
    want = jax.jit(jax.grad(lambda p: reference.loss_fn(p, tokens, SMALL)))(params)
    faulty = _trainers_gradient(params, tokens, cfg, _lookup_alone(cfg))
    off = _worst(faulty, want)
    # every other leaf is as it was; the tied one has lost the head's half
    assert off.pop("['embed']") > 0.05
    assert max(off.values()) < 1e-5
    # rows that the sequence never looked up have the head's gradient alone:
    # the fault leaves them at zero, the sound trainer does not
    unseen = np.setdiff1d(np.arange(256), np.asarray(tokens[:-1]))
    assert len(unseen) > 100
    assert not float(jnp.abs(faulty["embed"][unseen]).max())
    sound = _trainers_gradient(params, tokens, cfg)
    assert float(jnp.abs(sound["embed"][unseen]).max()) > 0
    np.testing.assert_allclose(sound["embed"], want["embed"],
                               atol=1e-5 * float(jnp.abs(want["embed"]).max()))


def test_a_held_out_pass_leaves_the_lane_as_it_is(lane_config, float32_operands):
    cfg = _cfg(lane_config)
    params = L.init_lfm2_params(jax.random.key(1), cfg, 1.0)
    tokens = L.make_token_dataset(jax.random.key(0), cfg)[1][0]
    v, keep = _gradient_steps(params)
    p, same_v, loss, (counters, _) = jax.jit(lambda p, v: lane._pass(
        p, v, tokens, jnp.bool_(False), L._visits(cfg), L._exits(cfg), keep))(params, v)
    assert all(jax.tree.leaves(jax.tree.map(lambda a, b: bool((a == b).all()), p, params)))
    assert not any(float(jnp.abs(x).max()) for x in jax.tree.leaves(same_v))
    assert float(loss) == pytest.approx(float(L.lfm2_forward(params, tokens, cfg)[0]))
    assert counters.shape == (2, 3)


@pytest.mark.parametrize("operand, steps, limit", [
    # float32 operands: rounding of sums only, steps amplify it little
    (jnp.float32, 1, 2e-5), (jnp.float32, 3, 1e-4),
    # as the chip runs it: bfloat16 operands (2^-8 a product) through three
    # layers and three steps
    (jnp.bfloat16, 1, 5e-3), (jnp.bfloat16, 3, 2e-2),
])
def test_steps_match_the_reference(reference, lane_config, monkeypatch, operand, steps, limit):
    monkeypatch.setattr(lane, "_OPERAND", operand)
    cfg = _cfg(lane_config)
    eval_fn = L.make_lfm2_eval_fn(cfg, data_seed=SMALL["data_seed"])
    # lr 0.1 at an init scale of 0.5: a lane that learns from its first step
    vec = jnp.asarray([0.75, 0.5, 0.3, 0.35])
    got = float(jax.jit(lambda v: eval_fn(v, float(steps)))(vec))
    hparams = [float(x) for x in lane.decode_lane_hparams(vec)]
    start, want = reference.reference_losses(SMALL, hparams, [0, steps])
    assert want < start - 0.01  # the steps moved the loss: it is compared
    assert abs(got - want) < limit * (1 + abs(want))
    if operand == jnp.float32 and steps == 1:
        # the control: bfloat16 parameters and momentum fail the same limit
        coarse = reference.reference_losses(SMALL, hparams, [steps], dtype=jnp.bfloat16)[0]
        assert abs(coarse - want) > 10 * limit * (1 + abs(want))


def test_one_step_moves_the_tied_matrix_once_by_its_whole_gradient(
        reference, lane_config, float32_operands):
    """``embed`` is stepped once a step: after one step from a momentum of
    zeros with no decay, what the step changed is ``-lr`` times the whole
    gradient of the reference's loss, the tied leaf's as every other's."""
    cfg = _cfg(lane_config)
    eval_fn = L.make_lfm2_eval_fn(cfg, data_seed=SMALL["data_seed"])
    # lr 0.1, momentum 0.5, the least decay (1e-7), init scale 1
    vec = jnp.asarray([0.75, 0.5 / 0.99, 0.0, 0.5])
    lr, _, wd, init = (float(x) for x in lane.decode_lane_hparams(vec))
    change = jax.jit(eval_fn.change)(vec, jnp.float32(1.0))
    params = reference.init_params(SMALL, jax.random.key(1), jnp.float32(init))
    train, _ = reference.dataset(SMALL)
    grad = jax.grad(lambda p: reference.loss_fn(p, train[0], SMALL))(params)
    want = jax.tree.map(lambda g, p: -lr * (g + wd * p), grad, params)
    worst = _worst(change, want)
    # float32: the step is a difference of parameters, a few units of their
    # last place: 2e-5 of a leaf's largest entry at most
    assert max(worst.values()) < 1e-4, worst
    # and the reference's own step is the same, by ``jax.grad`` of the whole
    fns = reference.lane_functions(SMALL, jnp.float32)
    p0 = fns.init(jnp.float32(init))
    _, v1 = fns.step(p0, jax.tree.map(jnp.zeros_like, p0), 0, jnp.float32(lr),
                     jnp.float32(0.5), jnp.float32(0.0))
    np.testing.assert_allclose(v1["embed"], grad["embed"],
                               atol=1e-6 * float(jnp.abs(grad["embed"]).max()))


# ------------------------------------------------------------ the convolution
def test_the_convolution_is_causal_and_kimis(reference, float32_operands):
    key = jax.random.key(3)
    x = jax.random.normal(key, (32, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 64))
    y = lane._causal_conv(x, w)
    # by hand: three taps, the last on the position itself
    np.testing.assert_allclose(y[5], w[0] * x[3] + w[1] * x[4] + w[2] * x[5], rtol=1e-6)
    np.testing.assert_allclose(y[0], w[2] * x[0], rtol=1e-6)
    # a change of token t moves no output before t
    moved = lane._causal_conv(x.at[17].add(1.0), w)
    assert bool((moved[:17] == y[:17]).all()) and bool((moved[17:20] != y[17:20]).any())
    assert bool((moved[20:] == y[20:]).all())
    # the one function: the kimi lane's convolution of four taps is it
    assert K._causal_conv is lane._causal_conv
    w4 = jax.random.normal(jax.random.fold_in(key, 2), (4, 64))
    kimi_reference = kimi_small.load("reference", "kimi-linear-sgd.py")
    np.testing.assert_allclose(lane._causal_conv(x, w4), kimi_reference.conv(x, w4), rtol=1e-6)


def test_the_mixer_is_the_references_and_causal(reference, lane_config, float32_operands):
    cfg = _cfg(lane_config)
    p = L.init_lfm2_params(jax.random.key(2), cfg, 1.0)["l0"]
    x = jax.random.normal(jax.random.key(4), (32, 64))
    y = lane.short_conv_mixer(x, p, scope="lane.conv")
    np.testing.assert_allclose(y, reference.short_conv(x, p), atol=1e-5)
    moved = lane.short_conv_mixer(x.at[9].add(1.0), p, scope="lane.conv")
    assert bool((moved[:9] == y[:9]).all()) and bool((moved[12:] == y[12:]).all())
    # B, C, x in that order: the first third gates before the taps, the
    # second after them
    d = 64
    u = x @ p["w_in"]
    by_hand = (u[:, d:2 * d] * lane._causal_conv(u[:, :d] * u[:, 2 * d:], p["conv"])) @ p["w_out"]
    np.testing.assert_allclose(y, by_hand, atol=1e-5)
    text = jax.jit(lambda x: lane.short_conv_mixer(x, p, scope="lane.conv")).lower(x).as_text(
        debug_info=True)
    assert "lane.conv" in text


def _shifted_conv(x, w):
    """Planted: the taps over ``t - 1 .. t + 1``, one position late."""
    k, t = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k - 2, 1), (0, 0)))
    return sum(w[i] * padded[i:i + t] for i in range(k))


# ------------------------------------------------------------------- the bias
def _router_case(lane_config, reference, scale=3.0):
    cfg = _cfg(lane_config)
    p = dict(L.init_lfm2_params(jax.random.key(2), cfg, scale)["l2"])
    x = jax.random.normal(jax.random.key(5), (32, 64))
    return cfg, p, x


def test_the_bias_chooses_and_does_not_weigh(reference, lane_config, float32_operands):
    cfg, p, x = _router_case(lane_config, reference)
    bias = jnp.asarray([0.4, 0.5, -0.3, 0.0, 0.6, -0.5, 0.2, -0.4])
    biased = dict(p, router_bias=bias)
    plain_choice, _ = reference.router_weights(x, p, SMALL)
    chosen, weight = reference.router_weights(x, biased, SMALL)
    # the planted bias changes the choice ...
    assert float((jnp.sort(chosen, 1) != jnp.sort(plain_choice, 1)).mean()) > 0.2
    # ... and a chosen expert weighs s_e / (sum of the chosen s + 1e-6), s without the bias
    s = jax.nn.sigmoid(x @ p["router"])
    s_chosen = jnp.take_along_axis(s, chosen, 1)
    np.testing.assert_allclose(weight, s_chosen / (s_chosen.sum(1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    assert float(jnp.abs(weight.sum(1) - 1).max()) < 1e-5      # the 1e-6 takes that much
    assert float((1 - weight.sum(1)).min()) > 0
    # the program's layer is the reference's, with the bias and without
    for leaves in (p, biased):
        y, _ = lane.moe_held_experts(x, leaves, L._experts(cfg))
        np.testing.assert_allclose(y, reference.experts(x, leaves, SMALL), atol=2e-5)
    # weighed by s + bias (and no 1e-6), the layer is another one
    y, _ = lane.moe_held_experts(x, biased, L._experts(cfg))
    assert float(jnp.abs(_weighed_by_the_bias(x, biased, L._experts(cfg))[0] - y).max()) > 0.05
    # with the denominator's epsilon at zero the two lanes there were trace as they did
    assert lane.ExpertLayer(8, 2, (0,)).epsilon == 0.0


def _weighed_by_the_bias(x, p, layer):
    """Planted: the chosen experts weighed by ``s + bias``, divided by their
    sum with no epsilon. A masked loop over the held experts."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=lane._FLOAT32)) + p["router_bias"]
    s_chosen, chosen = jax.lax.top_k(s, layer.top_k)
    weight = s_chosen / s_chosen.sum(-1, keepdims=True) * layer.scaling
    y = jnp.zeros_like(x)
    for slot, expert in enumerate(layer.held):
        w_e = jnp.where(chosen == expert, weight, 0.0).sum(-1)
        y = y + w_e[:, None] * lane._swiglu(
            x, p["e_gate"][slot], p["e_up"][slot], p["e_down"][slot])
    return y, jnp.zeros((len(lane.LANE_COUNTERS),), jnp.float32)


def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer(
        reference, lane_config, float32_operands):
    """Four chips of 2 experts each over one router (8 outputs, top 2):
    what the four shares give, summed, is the reference's whole layer."""
    cfg, p, x = _router_case(lane_config, reference, scale=1.5)
    whole = small(cut={"experts_held": list(range(8))})
    key = jax.random.key(7)
    experts = {n: lane._init_leaf(key, n, (8,) + p[n].shape[1:], 1.5)
               for n in ("e_gate", "e_up", "e_down")}
    p = dict(p, router_bias=jnp.asarray([0.2, -0.1, 0.0, 0.3, -0.3, 0.1, 0.0, -0.2]), **experts)
    want = reference.experts(x, p, whole)
    total, held_choices = jnp.zeros_like(x), 0.0
    for chip in range(4):
        held = (2 * chip, 2 * chip + 1)
        share = dict(p, **{n: experts[n][jnp.asarray(held)] for n in experts})
        y, counters = lane.moe_held_experts(
            x, share, L._experts(cfg)._replace(held=held))
        np.testing.assert_allclose(y, reference.experts(x, share, whole, held=held), atol=2e-5)
        total, held_choices = total + y, held_choices + float(counters[0])
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert held_choices == 32 * 2      # every token-choice fell on exactly one chip


# ------------------------------------------------------------ the per-head norm
def test_the_per_head_norm_is_applied_before_the_rotation(
        reference, lane_config, float32_operands):
    cfg = _cfg(lane_config)
    p = dict(L.init_lfm2_params(jax.random.key(2), cfg, 1.0)["l1"])
    p["q_norm"], p["k_norm"] = (1.0 + 0.3 * jax.random.normal(jax.random.key(k), (16,))
                                for k in (8, 9))
    x = jax.random.normal(jax.random.key(6), (32, 64))
    mixer = lambda leaves: lane.attention_mixer(
        x, leaves, kv_heads=2, heads_per_kv=2, head_dim=16, inv_freq=L.rotary_inv_freq(cfg),
        factor=1.0, sight=None, block=16, scope="lane.gqa", norm_eps=cfg.norm_eps)
    np.testing.assert_allclose(mixer(p), reference.attention(x, p, SMALL), atol=2e-5)
    # by hand: normalise every head of the projections, then rotate, then attend
    bare = {n: w for n, w in p.items() if n not in ("q_norm", "k_norm")}
    cos, sin = lane._rotary_tables(L.rotary_inv_freq(cfg), 1.0, 32)

    def by_hand(normed):
        q, k, v = ((x @ p[n]).reshape(32, -1, 16) for n in ("wq", "wk", "wv"))
        if normed:
            q, k = lane._rms(q, p["q_norm"], cfg.norm_eps), lane._rms(k, p["k_norm"], cfg.norm_eps)
        out = lane.banded_attention(
            lane._rotate(q, cos, sin).reshape(32, 2, 2, 16), lane._rotate(k, cos, sin),
            v, None, 16)
        return out.reshape(32, 64) @ p["wo"]

    # a layer with the leaves differs from one without by the normalisation alone
    np.testing.assert_allclose(mixer(p), by_hand(True), atol=2e-5)
    np.testing.assert_allclose(mixer(bare), by_hand(False), atol=2e-5)
    assert float(jnp.abs(mixer(p) - mixer(bare)).max()) > 1e-2
    # without the leaves no norm is traced: the other lanes' mixer is what it was
    leafless = str(jax.make_jaxpr(mixer)(bare))
    assert "rsqrt" not in leafless and "rsqrt" in str(jax.make_jaxpr(mixer)(p))
    # rotation after the norm, not before: the two do not commute under weights
    swapped = lane.banded_attention(
        lane._rms(lane._rotate((x @ p["wq"]).reshape(32, 4, 16), cos, sin),
                  p["q_norm"], cfg.norm_eps).reshape(32, 2, 2, 16),
        lane._rms(lane._rotate((x @ p["wk"]).reshape(32, 2, 16), cos, sin),
                  p["k_norm"], cfg.norm_eps),
        (x @ p["wv"]).reshape(32, 2, 16), None, 16).reshape(32, 64) @ p["wo"]
    assert float(jnp.abs(swapped - mixer(p)).max()) > 1e-3


def test_heads_of_64_take_the_kernels_in_pairs_where_mosaic_compiles(monkeypatch):
    """Off the chip this lane's heads of 64 take the plain form; told that
    Mosaic compiles, the fused kernels take them two key/value heads side
    by side in one tile of 128 lanes (``ops/pallas_attention.py``), a block
    of queries sized from the pair's 2 x 4 query heads, and the counter
    says so. The expert layer's products stay with the plain form on the
    chip too."""
    cfg = L.Lfm2Config()
    shape = (cfg.seq_len, cfg.head_dim, cfg.num_heads // cfg.num_kv_heads, cfg.num_kv_heads)
    assert shape == (8192, 64, 4, 8)
    assert lane._kernel_tiles(*shape) is None
    assert dict(lane.attention_counters(*shape)) == {
        "attn_scores_in_vmem": 0.0, "attn_rotation_in_vmem": 0.0}
    plain_bytes = lane.attention_alive_bytes(8192, 8, 4, 64, [None], cfg.attn_query_block)
    monkeypatch.setattr(lane, "pallas_available", lambda: True)
    assert lane._kernel_tiles(8192, 128, 8, 4) is not None
    assert lane._kernel_tiles(*shape) == (128, 512)
    assert dict(lane.attention_counters(*shape)) == {
        "attn_scores_in_vmem": 1.0, "attn_rotation_in_vmem": 1.0}
    # an odd head has no pair; the output and a log-sum-exp a row in place
    # of three copies of a block's scores
    assert lane._kernel_tiles(8192, 64, 4, 7) is None
    assert lane.attention_alive_bytes(
        8192, 8, 4, 64, [None], cfg.attn_query_block) == 4 * 8192 * 32 * (64 + 128) < plain_bytes
    # experts of 1,792 under a hidden size of 2,048: gate and up side by side
    # are more than the grouped kernels hold of an expert in VMEM
    assert lane._product_rows(8192 * 4, 2048, 1792) is None
    assert dict(lane.expert_layer_counters(8192 * 4, 2048, 1792)) == {
        "moe_combine_by_gather": 1, "moe_products_in_vmem": 0.0}


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


def _planted_bias(init):
    """``init`` with every router's bias planted (the same on both sides)."""
    def planted(*args, **kwargs):
        params = init(*args, **kwargs)
        for name, layer in params.items():
            if isinstance(layer, dict) and "router_bias" in layer:
                layer["router_bias"] = (0.3 * jax.random.normal(
                    jax.random.key(40), layer["router_bias"].shape)).astype(
                        layer["router_bias"].dtype)
        return params

    return planted


@pytest.mark.parametrize("fault, shows", [
    (None, {}),
    ("unchanged", {"all": 0.999, "embed": 0.999, "experts": 0.999, "conv": 0.999}),
    ("lookup_alone", {"embed": 0.05}),
    ("weighed_by_the_bias", {"experts": 0.05}),
    ("shifted_conv", {"conv": 0.3, "all": 0.3}),
    ("control", {"all": 0.9})])
def test_the_comparison_reads_what_the_first_step_changed(
        reference, lane_config, float32_operands, monkeypatch, fault, shows):
    """``compare`` on a sweep's record whose ``lane_change`` is the lane's
    trainer (``eval_fn.change``, as the cell's builder hands it): the sound
    trainer's first step is the reference's; a step that changed nothing
    reads 1 in every group; the tied matrix stepped by the lookup's gradient
    alone shows in its group; the chosen experts weighed by ``s + bias``
    with no epsilon (a bias planted on both sides) in the expert layers'; the
    taps one position late in the convolution mixers' and everywhere; the
    control (the reference with bfloat16 parameters and momentum) loses the
    step of the lane of the smallest learning rate (2e-4) altogether."""
    cfg = _cfg(lane_config)
    config = SMALL
    if fault == "lookup_alone":
        monkeypatch.setattr(L, "_exits", _lookup_alone)
    if fault == "shifted_conv":
        monkeypatch.setattr(lane, "_causal_conv", _shifted_conv)
    if fault == "weighed_by_the_bias":
        # a configuration of its own, so that the reference's functions are
        # made anew with the planted bias
        config = small(planted_bias=40)
        monkeypatch.setattr(reference, "init_params", _planted_bias(reference.init_params))
        monkeypatch.setattr(L, "init_lfm2_params", _planted_bias(L.init_lfm2_params))
        monkeypatch.setattr(lane, "moe_held_experts", _weighed_by_the_bias)
    eval_fn = L.make_lfm2_eval_fn(cfg, data_seed=SMALL["data_seed"])
    change = jax.jit(eval_fn.change)

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
    numbers = {name: (value, limit) for name, value, limit in reference.compare(
        config, None, [rec], seed=5, control=fault == "control")}
    groups = ("all", "embed", "experts", "conv")
    assert sorted(numbers) == sorted(["change_gap_" + g for g in groups] + ["loss_gap_max"])
    for group in groups:
        value, limit = numbers["change_gap_" + group]
        if group in shows:
            assert value > shows[group], (group, value)
        elif fault is None:
            # float32 on both sides: at lr 2e-4 a step is a few float32 units
            # of a leaf's entries
            assert value < 5e-3 < limit, (group, value)
    if fault in ("unchanged", "control"):
        # what the contract asks of the limits: a state left unchanged and the
        # precision below are not correct
        assert any(numbers["change_gap_" + g][0] > numbers["change_gap_" + g][1]
                   for g in groups)
    # the record's losses are made up (10.0 ..): the net is not what is tested
    assert numbers["loss_gap_max"][1] == 0.25


def test_a_change_that_is_no_number_reads_infinity(reference):
    want = {"embed": jnp.ones((3, 2)), "norm_f": jnp.ones((2,)),
            "l0": {"w_in": jnp.full((2, 2), 2.0), "norm1": jnp.ones((2,))},
            "l1": {"router": jnp.ones((2, 2)), "wq": jnp.ones((2, 2))}}
    got = dict(want, embed=jnp.full((3, 2), jnp.nan))
    gaps = reference.change_gaps(got, want)
    assert gaps["embed"] == np.inf and gaps["all"] == np.inf
    assert gaps["conv"] == 0.0 and gaps["experts"] == 0.0
    half = dict(want, l0=dict(want["l0"], w_in=jnp.ones((2, 2))))
    assert reference.change_gaps(half, want) == {
        "all": pytest.approx(np.sqrt(4.0 / (6 + 2 + 16 + 2 + 4 + 4))), "embed": 0.0,
        "experts": 0.0, "conv": pytest.approx(0.5)}


def test_the_lanes_facts_are_its_models(lane_config):
    cfg = _cfg(lane_config)
    facts = L.make_lfm2_eval_fn(cfg, data_seed=0).lane_facts
    assert facts.counters == lane.LANE_COUNTERS + L.ATTENTION_COUNTERS + (
        "attn_scores_in_vmem", "attn_rotation_in_vmem", "moe_combine_by_gather",
        "moe_products_in_vmem"
    ) + L.LAYOUT_COUNTERS
    assert facts.tokens_per_step == 32 and facts.traced_budget
    full = L.Lfm2Config()
    assert lane._count_params(
        lambda: L.init_lfm2_params(jax.random.key(0), full, 1.0)) == 507_820_288
    # one lane fits a chip, two do not
    assert 16.9e9 / 2 < L.lfm2_lane_bytes(full) < 16.9e9
