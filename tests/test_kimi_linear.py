"""The Kimi-Linear lane against the benchmark's plain reference, on the CPU
at a small size (``kimi_small.py``), and a rung's lanes in turn against the
``vmap``.

Where a test holds the equations to the reference it sets the lane's
matrix-product operands to float32 (``kimi_linear._OPERAND``): then only the
order of float32 sums differs, and the tolerances say so. Where it runs the
lane as the chip does (bfloat16 operands), the tolerance is bfloat16's.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.ops import fused
from hpbandster_tpu.optimizers import FusedBOHB
from hpbandster_tpu.workloads import kimi_linear as K
from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

from kimi_small import BENCHMARK, SMALL, load, scatters_and_sorts, small

ROOT = os.path.dirname(BENCHMARK)


@pytest.fixture(scope="module")
def reference():
    return load("reference", "kimi-linear-sgd.py")


@pytest.fixture(scope="module")
def lane_config():
    # the builder imports the harness's ``program`` by that name
    sys.modules.setdefault("program", load("program.py"))
    return load("configs", "kimi-linear-sgd.py").lane_config


@pytest.fixture
def float32_operands(monkeypatch):
    monkeypatch.setattr(K.lane, "_OPERAND", jnp.float32)


def _cfg(lane_config, config):
    return lane_config(config)._replace(kda_chunk=16, kda_block=4, mla_heads_at_once=2)


def test_weights_and_tokens_come_from_the_seed_alike(reference, lane_config):
    cfg, key = _cfg(lane_config, SMALL), jax.random.key(1)
    ours = K.init_kimi_linear_params(key, cfg, 0.7)
    theirs = reference.init_params(SMALL, key, 0.7)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()), ours, theirs)))
    for a, b in zip(K.make_token_dataset(jax.random.key(0), cfg),
                    reference.dataset(SMALL)):
        assert a.shape[1] == 65 and bool((a == b).all())
        half = a.shape[1] // 2 + 1
        assert bool((a[:, half:] == a[:, :a.shape[1] - half]).all())


def test_loss_and_every_gradient_leaf_match_the_reference(
        reference, lane_config, float32_operands):
    cfg = _cfg(lane_config, SMALL)
    params = K.init_kimi_linear_params(jax.random.key(1), cfg, 1.0)
    tokens = K.make_token_dataset(jax.random.key(0), cfg)[0][0]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: K.kimi_linear_loss(p, tokens, cfg)[0]))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_fn(p, tokens, SMALL)))(params)
    # float32 both sides, another order of summation (chunks against the
    # recurrence, grouped against masked products): 1e-5 of the loss
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want_grads)):
        # per leaf, against the leaf's largest entry: 17e-6 measured
        worst = float(jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-12))
        assert worst < 2e-4, (jax.tree_util.keystr(path), worst)
    bias = grads["l1"]["router_bias"]
    assert not bias.any()  # top-k passes it no gradient: it stays at zero


@pytest.mark.parametrize("operand, limit", [
    # float32 operands: rounding of sums only, three steps amplify it little
    (jnp.float32, 1e-4),
    # as the chip runs it: bfloat16 operands (2^-8 a product) through five
    # layers and three steps; 4e-3 measured
    (jnp.bfloat16, 2e-2),
])
def test_three_steps_match_the_reference(reference, lane_config, monkeypatch,
                                         operand, limit):
    monkeypatch.setattr(K.lane, "_OPERAND", operand)
    cfg = _cfg(lane_config, SMALL)
    eval_fn = K.make_kimi_linear_eval_fn(cfg, data_seed=SMALL["data_seed"])
    vec = jnp.asarray([0.75, 0.5, 0.3, 0.5])
    got = float(jax.jit(lambda v: eval_fn(v, 3.0))(vec))
    hparams = [float(x) for x in K.decode_kimi_linear_hparams(vec)]
    (want,) = reference.reference_losses(SMALL, hparams, [3])
    start = reference.reference_losses(SMALL, hparams, [0])[0]
    assert want < start - 0.01  # the steps moved the loss: it is compared
    assert abs(got - want) < limit * (1 + abs(want))


def test_the_reference_trains_by_the_gradient_of_its_loss(reference):
    """The reference steps layer by layer (``jax.vjp`` chained by hand, so
    that layers of a kind share a compiled function): with no momentum and
    no decay the momentum buffer after one step is ``jax.grad`` of its
    ``loss_fn``, every leaf; float32 sums in another order."""
    init, step, _ = reference.lane_functions(SMALL, jnp.float32)
    p, v = init(jnp.float32(1.0))
    train, _ = reference.dataset(SMALL)
    want = jax.grad(reference.loss_fn)(p, train[2], SMALL)
    new_p, got = step(p, v, 2, jnp.float32(0.5), jnp.float32(0.0), jnp.float32(0.0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, atol=1e-5 * float(jnp.abs(w).max()) + 1e-12, err_msg=str(path))
    np.testing.assert_allclose(new_p["head"], p["head"] - 0.5 * want["head"], atol=1e-6)


@pytest.mark.parametrize("length", [70, 64, 9])
def test_chunked_kda_is_the_recurrence(reference, length):
    """Lengths that are and are not multiples of the chunk, decays from
    none to so strong that a quotient of cumulative decays would overflow."""
    h, dk, chunk = 3, 8, 16
    keys = jax.random.split(jax.random.key(length), 5)
    q, k = (K._l2norm(jax.random.normal(kk, (length, h, dk))) for kk in keys[:2])
    v = jax.random.normal(keys[2], (length, h, dk))
    log_a = -jnp.exp(jax.random.uniform(keys[3], (length, h, dk), minval=-9.0, maxval=4.0))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (length, h)))
    if length >= chunk:  # within one chunk: exp(100) is no float32
        assert float(log_a[:chunk].sum(0).min()) < -100
    got = K.kda_chunked(q, k, v, log_a, beta, chunk)
    want = reference.delta_rule(q, k, v, jnp.exp(log_a), beta)
    # float32 operands would give 1e-6; the chunk's products run with
    # bfloat16 operands as on the chip: 2^-8 a product, outputs of order 1
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=3e-2)


def test_chunked_kda_in_float32_and_its_gradient(reference, float32_operands):
    h, dk, length = 2, 8, 37
    keys = jax.random.split(jax.random.key(5), 5)
    q, k = (K._l2norm(jax.random.normal(kk, (length, h, dk))) for kk in keys[:2])
    v = jax.random.normal(keys[2], (length, h, dk))
    log_a = -jnp.exp(jax.random.uniform(keys[3], (length, h, dk), minval=-6.0, maxval=1.0))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (length, h)))
    ours = lambda *x: (K.kda_chunked(*x, 16) ** 2).sum()
    theirs = lambda q, k, v, g, b: (reference.delta_rule(q, k, v, jnp.exp(g), b) ** 2).sum()
    got = jax.grad(ours, argnums=(0, 1, 2, 3, 4))(q, k, v, log_a, beta)
    want = jax.grad(theirs, argnums=(0, 1, 2, 3, 4))(q, k, v, log_a, beta)
    for g, w in zip(got, want):
        # float32 sums in another order
        np.testing.assert_allclose(g, w, atol=2e-4 * float(jnp.abs(w).max()))


def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer(
        reference, lane_config, float32_operands):
    """Four chips of four experts each, the shared expert counted once,
    against the reference's layer over all sixteen: the guide's tie of the
    chip's share to the model."""
    config = small(cut={"experts_held": list(range(16))})
    whole = reference.init_params(config, jax.random.key(2), 1.0)["l1"]
    x = jax.random.normal(jax.random.key(3), (64, 64))
    want = reference.experts(x, whole, config)
    shared = reference.swiglu(
        x, whole["shared_gate"], whole["shared_up"], whole["shared_down"])
    total, choices = shared, 0.0
    for share in range(4):
        held = list(range(4 * share, 4 * share + 4))
        cfg = _cfg(lane_config, small(cut={"experts_held": held}))
        p = dict(whole, **{k: whole[k][4 * share:4 * share + 4]
                           for k in ("e_gate", "e_up", "e_down")})
        y, counters = K.moe_held_experts(x, p, cfg)
        total = total + (y - shared)
        choices += float(counters[0])
    assert choices == 64 * 4  # every token-choice fell on exactly one chip
    np.testing.assert_allclose(total, want, atol=1e-5 * float(jnp.abs(want).max()))


def test_a_full_chip_drops_no_token(reference, lane_config, float32_operands):
    """Held experts that draw several times the even load fill more than
    one tile of the grouped product; the layer and its gradient still are
    the reference's."""
    config = small(cut={"router_outputs": 64, "experts_held": [5, 9, 40, 41]})
    cfg = _cfg(lane_config, config)
    p = reference.init_params(config, jax.random.key(4), 1.0)["l2"]
    p["router"] = p["router"].at[:, jnp.asarray([5, 9, 40, 41])].mul(0.0).at[
        :, jnp.asarray([5, 9, 40])].add(1.0)   # three held experts nearly always chosen
    x = jax.random.normal(jax.random.key(6), (64, 64)) + 0.5
    rows = max(4 * 64 * 4 * 4 // 64, 8)
    (y, counters) = K.moe_held_experts(x, p, cfg)
    assert float(counters[0]) > 2 * rows  # more than two tiles' worth
    np.testing.assert_allclose(y, reference.experts(x, p, config), atol=2e-5)
    ours = jax.grad(lambda p: (K.moe_held_experts(x, p, cfg)[0] ** 2).sum())(p)
    theirs = jax.grad(lambda p: (reference.experts(x, p, config) ** 2).sum())(p)
    for name in ("e_gate", "e_down", "router", "shared_up"):
        np.testing.assert_allclose(
            ours[name], theirs[name], atol=2e-4 * float(jnp.abs(theirs[name]).max()))


def _uneven_layer(reference):
    """A layer whose held experts are one that every token chooses, one
    that nobody chooses and two that few do: most choices are not held, so
    the last tiles of the grouped product are never reached."""
    held = [5, 9, 40, 41]
    config = small(cut={"router_outputs": 64, "experts_held": held})
    p = reference.init_params(config, jax.random.key(4), 1.0)["l2"]
    p = {k: v for k, v in p.items() if k.startswith(("router", "shared_", "e_"))}
    x = jax.random.normal(jax.random.key(6), (64, 64)).at[:, 0].set(3.0)
    p["router"] = p["router"].at[0, 5].set(4.0).at[0, 9].set(-4.0)
    return config, p, x


def test_the_input_and_every_leaf_get_the_references_gradient_through_the_gathers(
        reference, lane_config, float32_operands):
    """Dispatch, combine and their transposes are gathers by the counting
    sort's two permutations (``lane._routed``); the layer, the cotangent of
    its input and of every leaf still are the reference's, which loops
    over the experts under a mask, on a layer with an expert nobody
    chooses, one every token chooses, choices that are not held and tiles
    that are skipped."""
    config, p, x = _uneven_layer(reference)
    cfg = _cfg(lane_config, config)
    chosen = jax.lax.top_k(jax.nn.sigmoid(x @ p["router"]) + p["router_bias"], 4)[1]
    assert bool((chosen == 5).any(1).all()) and not bool((chosen == 9).any())
    y, counters = K.moe_held_experts(x, p, cfg)
    rows = max(4 * 64 * 4 * 4 // 64, 8)
    assert 64 < float(counters[0]) <= 64 * 4 - 2 * rows   # two tiles of four not reached
    np.testing.assert_allclose(y, reference.experts(x, p, config), atol=2e-5)
    dy = jax.random.normal(jax.random.key(7), y.shape)
    ours = jax.grad(lambda x, p: (K.moe_held_experts(x, p, cfg)[0] * dy).sum(), (0, 1))(x, p)
    theirs = jax.grad(lambda x, p: (reference.experts(x, p, config) * dy).sum(), (0, 1))(x, p)
    assert not bool(ours[1]["e_gate"][1].any())       # nobody chose expert 9
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(ours),
                            jax.tree.leaves(theirs)):
        np.testing.assert_allclose(
            g, w, atol=2e-4 * max(float(jnp.abs(w).max()), 1e-6), err_msg=str(path))


def test_the_expert_layer_lowers_to_no_float_scatter_and_no_sort(reference, lane_config):
    """The sigmoid router with its bias and the shared expert beside the
    routed ones, bfloat16 operands as the chip runs them: the one integer
    scatter that writes the sorted order, no other scatter, no sort."""
    config, p, x = _uneven_layer(reference)
    cfg = _cfg(lane_config, config)
    assert scatters_and_sorts(
        lambda x, p: K.moe_held_experts(x, p, cfg), x, p) == [("s32", "scatter")]


# ------------------------------------------------------- lanes in turn
def _toy_eval(lane_bytes, traced_budget=False):
    def with_counters(vec, budget):
        loss = branin_from_vector(vec, budget) + 0.01 * jnp.sin(37.0 * vec.sum())
        return loss, jnp.stack([vec[0], budget * vec[1]])

    def eval_fn(vec, budget):
        return with_counters(vec, budget)[0]

    eval_fn.lane_facts = fused.LaneFacts(
        bytes=lane_bytes, tokens_per_step=7, counters=("first", "second"),
        with_counters=with_counters, traced_budget=traced_budget)
    return eval_fn


@pytest.mark.parametrize("memory, at_once", [(None, 9), (100, 9), (49, 4), (10, 1), (3, 1)])
def test_lanes_at_once_follows_the_footprint(monkeypatch, memory, at_once):
    monkeypatch.setattr(fused, "_device_memory_bytes", lambda: memory)
    assert fused.lanes_at_once(_toy_eval(10), 9) == at_once
    assert fused.lanes_at_once(branin_from_vector, 9) == 9  # no facts: as before


def _sweep(eval_fn):
    opt = FusedBOHB(configspace=branin_space(seed=5), eval_fn=eval_fn, run_id="turn",
                    min_budget=1, max_budget=9, eta=3, seed=5)
    result = opt.run(n_iterations=2)
    runs = sorted((r.config_id, r.budget, r.loss) for r in result.get_all_runs())
    return runs, opt.run_stats[-1], opt.last_executable.as_text()


@pytest.mark.parametrize("memory, at_once, traced_budget",
                         [(45, 4, False), (10, 1, False), (10, 1, True), (45, 4, True)])
def test_lanes_in_turn_give_the_losses_and_promotions_of_the_vmap(
        monkeypatch, memory, at_once, traced_budget):
    """A rung's lanes by ``lax.map``, and (one lane at a time, the budget
    traced) the whole bracket as one loop over its evaluations."""
    monkeypatch.setattr(fused, "_device_memory_bytes", lambda: None)
    side_by_side, stats, text = _sweep(_toy_eval(10))
    assert stats["lanes_at_once"] == 9
    monkeypatch.setattr(fused, "_device_memory_bytes", lambda: memory)
    in_turn, turn_stats, turn_text = _sweep(_toy_eval(10, traced_budget))
    assert turn_stats["lanes_at_once"] == at_once
    assert turn_text.count(" while(") > text.count(" while(")  # the lanes' loop
    one_loop = traced_budget and at_once == 1
    assert ("conditional(" in turn_text) == one_loop  # the promotions inside it
    # the same evaluations, so the same configurations promoted; the losses
    # are the same arithmetic, fused differently when batched: Branin's
    # cosine and the sine turn float32's last digit into 4e-5 of a loss
    assert [r[:2] for r in in_turn] == [r[:2] for r in side_by_side]
    np.testing.assert_allclose(
        [r[2] for r in in_turn], [r[2] for r in side_by_side], rtol=2e-4)
    for key in ("lane_steps", "lane_tokens", "first", "second"):
        assert turn_stats[key] == pytest.approx(stats[key], rel=1e-5)
    assert stats["lane_steps"] == sum(n * b for n, b in [(9, 1), (3, 3), (1, 9), (5, 3), (1, 9)])
    assert stats["lane_tokens"] == 7 * stats["lane_steps"]


def test_an_eval_fn_without_facts_is_traced_as_before():
    """The rung of a workload that states nothing is the ``vmap`` it was:
    the same jaxpr as the line the helper replaced."""
    vecs = jnp.zeros((5, 2))
    before = jax.make_jaxpr(lambda v: jax.vmap(
        lambda x: branin_from_vector(x, 3.0))(v).astype(jnp.float32))(vecs)
    after = jax.make_jaxpr(
        lambda v: fused.eval_lanes(branin_from_vector, v, 3.0))(vecs)
    assert str(before) == str(after)


def test_lanes_in_turn_refuse_a_mesh(monkeypatch):
    monkeypatch.setattr(fused, "_device_memory_bytes", lambda: 10)
    with pytest.raises(NotImplementedError, match="not sharded"):
        fused.eval_lanes(_toy_eval(10), jnp.zeros((4, 2)), 1.0, mesh=object())


# ----------------------------------------------------- the configuration
def test_configuration_file_keeps_every_published_width(lane_config):
    config = json.load(open(os.path.join(BENCHMARK, "configs", "kimi-linear-sgd.json")))
    entry = next(c for c in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["configs"]
                 if c["name"] == "kimi-linear-sgd")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        len(config["cut"]["layers"]), len(config["cut"]["experts_held"]), 163840 // 8)
    defaults = K.KimiLinearConfig()
    assert lane_config(config) == defaults
    assert defaults.layer_kinds.count(("kda", "moe")) == 3  # 3 KDA : 1 MLA with experts


def test_lane_counts_agree_with_the_lane(reference):
    config = json.load(open(os.path.join(BENCHMARK, "configs", "kimi-linear-sgd.json")))
    sys.path.insert(0, BENCHMARK)
    try:
        lane_counts = load("lane_counts.py")
    finally:
        sys.path.remove(BENCHMARK)
    shapes = jax.eval_shape(
        lambda: K.init_kimi_linear_params(jax.random.key(0), K.KimiLinearConfig(), 1.0))
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    layers, params = lane_counts.layers_of(config), lane_counts.part_params(config)
    # all but the five layers' two norms and the final one
    assert n_params - sum(params[p] * layers[p] for p in params) == 11 * 2304
    facts = K.make_kimi_linear_eval_fn(
        K.KimiLinearConfig(seq_len=64, n_train=2, n_val=1)).lane_facts
    assert facts.counters == K.LANE_COUNTERS + (
        "moe_combine_by_gather", "moe_products_in_vmem")
    assert facts.tokens_per_step == 64
    assert 12 * n_params < K.kimi_linear_lane_bytes(K.KimiLinearConfig()) < 16.9e9
