"""The Mellum2 lane against the benchmark's plain reference, on the CPU at a
small size (``mellum2_small.py``): the loss and every gradient leaf, three
steps, the chip's share of the expert layer against the uncut layer, and the
one expert layer under both routers. Its attention (the blocked form, the
fused kernels, the rotary tables) is ``tests/test_mellum2_attention.py``'s.

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

from hpbandster_tpu.workloads import kimi_linear as K
from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads import mellum2 as M

import kimi_small
from mellum2_small import BENCHMARK, SMALL, load, scatters_and_sorts, small

ROOT = os.path.dirname(BENCHMARK)


@pytest.fixture(scope="module")
def reference():
    return load("reference", "mellum2-sgd.py")


@pytest.fixture(scope="module")
def builders():
    # the builders import the harness's ``program`` by that name
    sys.modules.setdefault("program", load("program.py"))
    return {"mellum2": load("configs", "mellum2-sgd.py").lane_config,
            "kimi": load("configs", "kimi-linear-sgd.py").lane_config}


@pytest.fixture
def float32_operands(monkeypatch):
    monkeypatch.setattr(lane, "_OPERAND", jnp.float32)


def _cfg(builders, config):
    return builders["mellum2"](config)._replace(attn_query_block=16)


def test_weights_and_tokens_come_from_the_seed_alike(reference, builders):
    cfg, key = _cfg(builders, SMALL), jax.random.key(1)
    ours = M.init_mellum2_params(key, cfg, 0.7)
    theirs = reference.init_params(SMALL, key, 0.7)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()), ours, theirs)))
    for a, b in zip(M.make_token_dataset(jax.random.key(0), cfg),
                    reference.dataset(SMALL)):
        assert a.shape[1] == 65 and bool((a == b).all())
        half = a.shape[1] // 2 + 1
        assert bool((a[:, half:] == a[:, :a.shape[1] - half]).all())


def test_loss_and_every_gradient_leaf_match_the_reference(
        reference, builders, float32_operands):
    cfg = _cfg(builders, SMALL)
    params = M.init_mellum2_params(jax.random.key(1), cfg, 1.0)
    tokens = M.make_token_dataset(jax.random.key(0), cfg)[0][0]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: M.mellum2_loss(p, tokens, cfg)[0]))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_fn(p, tokens, SMALL)))(params)
    # float32 both sides, another order of summation (blocks of keys against
    # the whole row, grouped against masked products): 1e-5 of the loss
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want_grads)):
        # per leaf, against the leaf's largest entry: 1e-6 measured
        worst = float(jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-12))
        assert worst < 2e-5, (jax.tree_util.keystr(path), worst)


def test_the_forward_pass_of_the_trainer_is_the_loss(builders, float32_operands):
    cfg = _cfg(builders, SMALL)
    params = M.init_mellum2_params(jax.random.key(1), cfg, 1.0)
    tokens = M.make_token_dataset(jax.random.key(0), cfg)[1][0]
    loss, counters = M.mellum2_loss(params, tokens, cfg)
    again, same, hs = M.mellum2_forward(params, tokens, cfg)
    assert float(loss) == pytest.approx(float(again), rel=1e-6)
    np.testing.assert_allclose(counters, same)
    assert len(hs) == 5 and all(h.shape == (64, 64) for h in hs)


@pytest.mark.parametrize("operand, limit", [
    # float32 operands: rounding of sums only, three steps amplify it little
    (jnp.float32, 1e-4),
    # as the chip runs it: bfloat16 operands (2^-8 a product) through four
    # layers and three steps
    (jnp.bfloat16, 2e-2),
])
def test_three_steps_match_the_reference(reference, builders, monkeypatch,
                                         operand, limit):
    monkeypatch.setattr(lane, "_OPERAND", operand)
    cfg = _cfg(builders, SMALL)
    eval_fn = M.make_mellum2_eval_fn(cfg, data_seed=SMALL["data_seed"])
    vec = jnp.asarray([0.75, 0.5, 0.3, 0.5])
    got = float(jax.jit(lambda v: eval_fn(v, 3.0))(vec))
    hparams = [float(x) for x in lane.decode_lane_hparams(vec)]
    start, want = reference.reference_losses(SMALL, hparams, [0, 3])
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


# ---------------------------------------------------------- expert layer
def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer(
        reference, builders, float32_operands):
    """Four chips of four experts each against the reference's layer over
    all sixteen (at the published size four shares of 16 make the 64): the
    guide's tie of the chip's share to the model. No expert is shared, so
    the shares' sum is the layer."""
    config = small(cut={"experts_held": list(range(16))})
    whole = reference.init_params(config, jax.random.key(2), 1.0)["l1"]
    x = jax.random.normal(jax.random.key(3), (64, 64))
    want = reference.experts(x, whole, config)
    total, choices = 0.0, 0.0
    for share in range(4):
        held = list(range(4 * share, 4 * share + 4))
        cfg = _cfg(builders, small(cut={"experts_held": held}))
        p = dict(whole, **{k: whole[k][4 * share:4 * share + 4]
                           for k in ("e_gate", "e_up", "e_down")})
        y, counters = M.moe_held_experts(x, p, M._experts(cfg))
        total = total + y
        choices += float(counters[0])
    assert choices == 64 * 4  # every token-choice fell on exactly one chip
    np.testing.assert_allclose(total, want, atol=1e-5 * float(jnp.abs(want).max()))


def _router_case(name, reference, builders):
    """``(layer facts, leaves, the reference's experts(x, p))`` of a layer
    whose first held expert nearly every token chooses."""
    held = [5, 9, 40, 41]
    if name == "softmax":
        config = small(cut={"router_outputs": 64, "experts_held": held})
        facts = M._experts(_cfg(builders, config))
        p = reference.init_params(config, jax.random.key(4), 1.0)["l2"]
        theirs = lambda x, p: reference.experts(x, p, config)
    else:
        config = kimi_small.small(cut={"router_outputs": 64, "experts_held": held})
        facts = K._experts(builders["kimi"](config))
        kimi_reference = load("reference", "kimi-linear-sgd.py")
        p = kimi_reference.init_params(config, jax.random.key(4), 1.0)["l2"]
        theirs = lambda x, p: kimi_reference.experts(x, p, config)
    p = {k: v for k, v in p.items()
         if k.startswith(("router", "shared_", "e_"))}
    p["router"] = p["router"].at[:, jnp.asarray(held)].mul(0.0).at[
        :, jnp.asarray(held[:3])].add(1.0)
    return facts, p, theirs


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_a_full_chip_drops_no_token_under_either_router(
        reference, builders, float32_operands, router):
    """The one expert layer (``lane.moe_held_experts``) as each model states
    its router: softmax over the outputs, no bias, no scaling, no shared
    expert (Mellum2); sigmoid, a bias, a scaling factor and a shared expert
    (Kimi-Linear). Held experts that nearly every token chooses fill more
    than one tile of the grouped product; the layer and its gradient still
    are the reference's."""
    facts, p, theirs = _router_case(router, reference, builders)
    assert ("shared_gate" in p, "router_bias" in p, facts.scaling != 1.0) == (
        (router == "sigmoid",) * 3)
    x = jax.random.normal(jax.random.key(6), (64, 64)) + 0.5
    rows = max(4 * 64 * 4 * 4 // 64, 8)
    y, counters = lane.moe_held_experts(x, p, facts)
    assert float(counters[0]) > 2 * rows  # more than two tiles' worth
    assert float(counters[1]) > 1.2       # and unevenly
    np.testing.assert_allclose(y, theirs(x, p), atol=2e-5)
    ours = jax.grad(lambda p: (lane.moe_held_experts(x, p, facts)[0] ** 2).sum())(p)
    want = jax.grad(lambda p: (theirs(x, p) ** 2).sum())(p)
    for name in ("e_gate", "e_down", "router"):
        np.testing.assert_allclose(
            ours[name], want[name], atol=2e-4 * float(jnp.abs(want[name]).max()))


def test_a_held_expert_that_every_token_chooses_drops_none(
        reference, builders, float32_operands):
    config = small(cut={"experts_held": [3, 7, 8, 12]})
    cfg = _cfg(builders, config)
    p = reference.init_params(config, jax.random.key(4), 1.0)["l0"]
    # expert 7's logit is 30 for every token, far above every other's: it
    # draws four times the even load of a held expert
    x = jax.random.normal(jax.random.key(6), (64, 64)).at[:, 0].set(30.0)
    p["router"] = p["router"].at[:, 7].set(0.0).at[0, 7].set(1.0)
    y, counters = M.moe_held_experts(x, p, M._experts(cfg))
    chosen = jax.lax.top_k(jax.nn.softmax(x @ p["router"], -1), 4)[1]
    assert bool((chosen == 7).any(axis=1).all())
    assert float(counters[0]) == float((jnp.isin(chosen, jnp.asarray([3, 7, 8, 12]))).sum())
    np.testing.assert_allclose(y, reference.experts(x, p, config), atol=2e-5)


def test_the_tile_of_the_grouped_product_is_capped():
    """Four times the even load, and no more than ``_TILE_ROWS`` rows: the
    Kimi-Linear lane's tile is what it was (4,096 rows at its published
    size), the Mellum2 lane's 65,536 choices go in eight tiles of half the
    even load (PR 33: two of 32,768 rows until the rows moved by gathers)."""
    rows = lambda t, k, held, outputs: min(
        t * k, max(min(4 * t * k * held // outputs, lane._TILE_ROWS), 8))
    assert rows(4096, 8, 8, 256) == 4096 == 4 * 4096 * 8 * 8 // 256
    assert rows(8192, 8, 16, 64) == 8192 == 8192 * 8 * 16 // 64 // 2
    assert rows(64, 4, 4, 16) == 256 and rows(64, 4, 4, 64) == 64


# ------------------------------------------- rows moved by gathers alone
def _sorted_by_hand(rows, slot=None):
    """Sixteen tokens' top 2 over four held experts and "not here" (slot
    4), sorted here by ``argsort``: expert 0 nobody chooses, expert 1
    every token chooses, a quarter of the choices are not held. The 24
    held choices reach three tiles of 8 rows and not the fourth, two of 12
    and not the third (36 rows for 32 choices), and the one of 32. Or the
    choices' slots as given."""
    t, k, held = 16, 2, 4
    if slot is None:
        slot = np.stack([np.ones(t, np.int32), np.asarray([2, 3, 4, 4] * 4, np.int32)],
                        axis=1).reshape(-1)
        assert np.bincount(slot, minlength=held + 1)[:held].tolist() == [0, 16, 4, 4]
    order = np.argsort(slot, kind="stable").astype(np.int32)
    place = np.argsort(order, kind="stable").astype(np.int32)
    loads = np.bincount(slot, minlength=held + 1)[:held].astype(np.int32)
    order = np.concatenate([order, np.zeros(-(-t * k // rows) * rows - t * k, np.int32)])
    return jnp.asarray(order), jnp.asarray(place), jnp.asarray(np.cumsum(loads))


@pytest.mark.parametrize("rows", [8, 12, 32])
def test_dispatch_and_combine_and_their_transposes_are_the_plain_indexing_forms(
        float32_operands, rows):
    """``lane._routed`` (dispatch a gather by ``order``, combine a gather
    by ``place``, each one's transpose a gather by the other permutation)
    against ``x[token]`` and ``.at[token].add`` over every tile, skipped or
    not, differentiated by ``jax.vjp``: the layer's rows and the cotangent
    of the input, of the weights of the choices and of both grouped
    products' weights."""
    t, k, d, f = 16, 2, 8, 4
    order, place, ends = _sorted_by_hand(rows)
    keys = jax.random.split(jax.random.key(5), 5)
    with_rest = lambda w: jnp.concatenate([w, jnp.zeros_like(w[:1])])
    args = (jax.random.normal(keys[0], (t, d)),
            jax.random.uniform(keys[1], (t, k)),
            with_rest(jax.random.normal(keys[2], (4, d, 2 * f))),
            with_rest(jax.random.normal(keys[3], (4, f, d))))

    def plain(x, weight, e_in, e_down):
        y = jnp.zeros_like(x)
        for lo in range(0, order.shape[0], rows):
            take = order[lo:lo + rows]
            token = take // k
            rows_out = lane._tile_experts(
                x[token], e_in, e_down, lane._tile_sizes(ends, lo, rows))
            y = y.at[token].add(rows_out * weight.reshape(-1)[take][:, None])
        return y

    ours = lambda *a: lane._routed(*a, order, place, ends, None, k, rows)
    y, pull = jax.vjp(ours, *args)
    want, want_pull = jax.vjp(plain, *args)
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(y, want, atol=1e-5 * float(jnp.abs(want).max()))
    dy = jax.random.normal(keys[4], (t, d))
    for name, g, w in zip(("x", "weight", "e_in", "e_down"), pull(dy), want_pull(dy)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(
            g, w, atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)
    # the expert nobody chooses and the closing group learn nothing
    g_in = pull(dy)[2]
    assert not bool(g_in[0].any()) and not bool(g_in[-1].any()) and bool(g_in[1].any())


# ------------------------------ the experts' products as grouped kernels
def _hand_sorted_layer(rows, slot=None, d=128, f=128):
    """:func:`_sorted_by_hand`'s choices at widths of whole lanes: ``(the
    layer's differentiable inputs, order, place, ends, a cotangent)``."""
    t, k = 16, 2
    order, place, ends = _sorted_by_hand(rows, slot)
    keys = jax.random.split(jax.random.key(5), 5)
    args = (jax.random.normal(keys[0], (t, d)),
            jax.random.uniform(keys[1], (t, k)),
            jax.random.normal(keys[2], (4, d, 2 * f)) * d ** -0.5,
            jax.random.normal(keys[3], (4, f, d)) * f ** -0.5)
    return args, order, place, ends, jax.random.normal(keys[4], (t, d))


def _plain_and_kernels(rows, slot=None):
    """``(the plain form, the grouped kernels in the Pallas interpreter)``
    of ``lane._routed`` on the hand-sorted case, tiles of ``rows``."""
    from hpbandster_tpu.ops import pallas_grouped

    k = 2
    with_rest = lambda w: jnp.concatenate([w, jnp.zeros_like(w[:1])])
    order, place, ends = _sorted_by_hand(rows, slot)
    at = pallas_grouped.visits(ends, order.shape[0], rows)
    plain = lambda x, w, e_in, e_down: lane._routed(
        x, w, with_rest(e_in), with_rest(e_down), order, place, ends, None, k, rows)
    kernels = lambda x, w, e_in, e_down: lane._routed(
        x, w, e_in, e_down, order, place, ends, at, k, rows)
    return plain, kernels, at


@pytest.mark.parametrize("rows", [8, 16, 32])
def test_the_grouped_kernels_are_the_plain_form(float32_operands, rows):
    """``ops/pallas_grouped.py`` under ``lane._routed``, in the Pallas
    interpreter, against the plain form (the loop over tiles of
    ``ragged_dot`` and the ``jax.vjp`` of a tile) on the hand-sorted case
    (an expert nobody chooses, one every token chooses, a quarter of the
    choices not held): ``y``, ``dx``, ``dweight`` (the down product's sum
    taken in the other order) and both experts' gradients, to rounding. The
    interpreter leaves what no visit writes as NaN, so the tiles that are
    not visited provably reach nothing."""
    args, _, _, _, dy = _hand_sorted_layer(rows)
    plain, kernels, at = _plain_and_kernels(rows)
    # the 24 held choices: an empty group's one visit and the tiles of the
    # three others, none past the last held row
    assert int(at.count) == {8: 1 + 2 + 1 + 1, 16: 4, 32: 4}[rows]
    want, want_pull = jax.vjp(plain, *args)
    y, pull = jax.vjp(kernels, *args)
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(y, want, atol=1e-5 * float(jnp.abs(want).max()))
    for name, g, w in zip(("dx", "dweight", "g_in", "g_down"), pull(dy), want_pull(dy)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.abs(w).max()), err_msg=name)
    # the expert nobody chooses learns nothing: written once, as zeros
    g_in = pull(dy)[2]
    assert not bool(g_in[0].any()) and bool(g_in[1].any())


def test_what_no_held_choice_owns_reaches_nothing_through_the_kernels(float32_operands):
    """A diverged lane's NaN in rows that no held choice owns reaches
    neither ``y`` nor any gradient: four tokens of sixteen choose no held
    expert and their inputs are NaN, so the sorted rows 24 to 31, the
    second half of the last visited tile, hold NaN in the kernels'
    operands. The first kernel stores by a select, the transposed one masks
    both operands, and the combine and its transpose select too."""
    rows = 16
    slot = np.stack([np.asarray([1] * 12 + [4] * 4, np.int32),
                     np.asarray([2, 3] * 6 + [4] * 4, np.int32)], axis=1).reshape(-1)
    args, _, _, ends, dy = _hand_sorted_layer(rows, slot)
    assert ends.tolist() == [0, 12, 18, 24]
    _, kernels, at = _plain_and_kernels(rows, slot)
    assert int(at.count) == 1 + 1 + 2 + 1    # the last one holds rows 16 to 31
    nowhere = jnp.arange(16)[:, None] >= 12
    poisoned = (jnp.where(nowhere, jnp.nan, args[0]),) + args[1:]
    want, want_pull = jax.vjp(kernels, *args)
    y, pull = jax.vjp(kernels, *poisoned)
    np.testing.assert_array_equal(y, want)
    assert not bool(y[12:].any()) and bool(y[:12].all())
    for name, g, w in zip(("dx", "dweight", "g_in", "g_down"), pull(dy), want_pull(dy)):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("mosaic, choices, d, f, rows", [
    # on the CPU the plain form, whatever the shape
    (False, 8192 * 8, 2304, 896, None), (False, 4096 * 8, 2304, 1024, None),
    # told that Mosaic compiles here: both lanes' published shapes (1,024
    # choices an expert if routing is even, and 128)
    (True, 8192 * 8, 2304, 896, "tile"), (True, 4096 * 8, 2304, 1024, "tile"),
    # fewer choices than a tile: one tile of them all
    (True, 128, 256, 128, 128),
    # widths that are no whole lanes (the tests' lanes), rows that are no
    # whole tiles, an expert whose weights do not fit VMEM
    (True, 64 * 4, 64, 16, None), (True, 8192 * 8 + 8, 2304, 896, None),
    (True, 8192 * 8, 8192, 4096, None),
])
def test_the_rule_for_the_experts_products_reads_the_backend_and_the_shapes(
        monkeypatch, mosaic, choices, d, f, rows):
    """``lane._product_rows``: the grouped kernels' tile of rows, or None
    where the plain form runs; ``moe_products_in_vmem`` says which."""
    monkeypatch.setattr(lane, "pallas_available", lambda: mosaic)
    rows = lane._KERNEL_TILE_ROWS if rows == "tile" else rows
    assert lane._product_rows(choices, d, f) == rows
    assert lane.expert_layer_counters(choices, d, f) == lane.MOE_COUNTERS + (
        ("moe_products_in_vmem", float(rows is not None)),)


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_the_expert_layer_lowers_to_no_float_scatter_and_no_sort(
        reference, builders, router):
    """A scatter-add sorts its indices on the chip, and autodiff makes one
    of every gather: the lowered forward and backward pass of the layer
    holds the one integer scatter that writes ``order`` and nothing else
    that scatters or sorts, under either router (bfloat16 operands, as the
    chip runs it)."""
    facts, p, _ = _router_case(router, reference, builders)
    x = jax.random.normal(jax.random.key(6), (64, 64)) + 0.5
    assert scatters_and_sorts(
        lambda x, p: lane.moe_held_experts(x, p, facts), x, p) == [("s32", "scatter")]
    # what the same reading shows of the plain indexing form
    plain = lambda x, p: (jnp.zeros_like(x).at[jnp.arange(64) // 2].add(x[::-1]),)
    assert ("f32", "scatter") in scatters_and_sorts(plain, x, p)


# ----------------------------------------------------- the configuration
def test_configuration_file_keeps_every_published_width(builders):
    config = json.load(open(os.path.join(BENCHMARK, "configs", "mellum2-sgd.json")))
    entry = next(c for c in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["configs"]
                 if c["name"] == "mellum2-sgd")
    assert entry["source"] == config["source"] and len(entry["source"]) == 81
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types", "mlp_layer_types"]
    assert config["published"] == {
        "num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304}
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        len(config["cut"]["layers"]), len(config["cut"]["experts_held"]), 98304 // 4)
    assert config["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert config["cut"]["router_outputs"] == 64 and config["cut"]["chips_sharing_a_layer"] == 4
    # every width as published
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "moe_intermediate_size", "num_experts_per_tok", "sliding_window",
        "intermediate_size", "rms_norm_eps")] == [
            2304, 32, 4, 128, 896, 8, 1024, 7168, 1e-6]
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
            "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    # the catalog's row, where the sandbox has it: every key but the cuts
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog) if "Mellum2-12B" in line)
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert key in config["reduced"] or config[key] == value, key
    assert set(config["assumed"]) >= {
        "qk_norm", "rotary_pairing", "router", "window", "aux_loss", "mtp_head",
        "init", "tokens", "optimizer", "data_seed"}
    assert builders["mellum2"](config) == M.Mellum2Config()


def test_lane_counts_agree_with_the_lane():
    config = json.load(open(os.path.join(BENCHMARK, "configs", "mellum2-sgd.json")))
    sys.path.insert(0, BENCHMARK)
    try:
        counts = load("lane_counts_mellum2.py")
    finally:
        sys.path.remove(BENCHMARK)
    cfg = M.Mellum2Config()
    n_params = lane._count_params(
        lambda: M.init_mellum2_params(jax.random.key(0), cfg, 1.0))
    assert n_params == counts.lane_params(config) == 595_153_152
    layers, params = counts.layers_of(config), counts.part_params(config)
    # all but the four layers' two norms and the final one
    assert n_params - sum(params[p] * layers[p] for p in params) == 9 * 2304
    # one lane fits the chip, two do not: a rung's lanes go in turn
    assert 12 * n_params < M.mellum2_lane_bytes(cfg) < 16.9e9 < 2 * M.mellum2_lane_bytes(cfg)
