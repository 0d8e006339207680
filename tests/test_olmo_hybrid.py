"""The Olmo-Hybrid lane (three Gated-DeltaNet layers, whose delta rule is
gated once a head, to one full-attention layer without positions; the norm
after the sub-layer) against the benchmark's plain reference, on the CPU at a
small size (``olmo_hybrid_small.py``): loss, the trainer's gradient of every
leaf against ``jax.grad`` of the reference's whole loss, one and three steps,
``lane.attention_mixer`` without rotation and under a norm over the whole
width, the comparison that decides the cell's ``correct``, and the
configuration's file.

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

from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads import olmo_hybrid as OH

from olmo_hybrid_small import BENCHMARK, SMALL, load

ROOT = os.path.dirname(BENCHMARK)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def reference():
    return load("reference", "olmo-hybrid-sgd.py")


@pytest.fixture(scope="module")
def lane_config():
    # the builder imports the harness's ``program`` by that name
    sys.modules.setdefault("program", load("program.py"))
    return load("configs", "olmo-hybrid-sgd.py").lane_config


@pytest.fixture
def float32_operands(monkeypatch):
    monkeypatch.setattr(lane, "_OPERAND", jnp.float32)


def _cfg(lane_config, config=SMALL):
    return lane_config(config)._replace(attn_query_block=16, gdn_chunk=16)


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


def test_weights_and_tokens_come_from_the_seed_alike(reference, lane_config):
    cfg, key = _cfg(lane_config), jax.random.key(1)
    ours = OH.init_olmo_hybrid_params(key, cfg, 0.7)
    theirs = reference.init_params(SMALL, key, 0.7)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()), ours, theirs)))
    # the gate's leaves are not drawn, and there is one of each a head
    np.testing.assert_allclose(jnp.exp(ours["l0"]["A_log"]), jnp.linspace(1.0, 16.0, 4),
                               rtol=1e-6)
    np.testing.assert_allclose(jax.nn.softplus(ours["l1"]["dt_bias"])[jnp.asarray([0, 3])],
                               [0.001, 0.1], rtol=1e-4)
    # a linear layer's leaves (d_k 8 beside d_v 16), a full layer's norms
    # over the projection's whole width
    assert ours["l2"]["wq"].shape == (64, 32) and ours["l2"]["wv"].shape == (64, 64)
    assert ours["l2"]["o_norm"].shape == (16,) and ours["l2"]["wa"].shape == (64, 4)
    assert ours["l3"]["q_norm"].shape == (64,) and "conv_q" not in ours["l3"]
    for a, b in zip(OH.make_token_dataset(jax.random.key(0), cfg),
                    reference.dataset(SMALL)):
        assert a.shape[1] == 33 and bool((a == b).all())


def test_loss_and_every_gradient_leaf_match_the_reference(
        reference, lane_config, float32_operands):
    cfg = _cfg(lane_config)
    params = OH.init_olmo_hybrid_params(jax.random.key(1), cfg, 1.0)
    tokens = OH.make_token_dataset(jax.random.key(0), cfg)[0][0]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: OH.olmo_hybrid_loss(p, tokens, cfg)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_fn(p, tokens, SMALL)))(params)
    # float32 both sides, another order of summation (chunks and a solve
    # against the step-by-step recurrence, blocks of keys against the square)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    # per leaf, against the leaf's largest entry: 2.5e-5 measured. A norm
    # after a sub-layer divides by its output's size and takes the
    # cotangent's part along it out again: the reference's own gradient
    # moves by 5e-5 between its compiled and its eager form here
    worst = _worst(grads, want_grads)
    assert set(worst) >= {"['l0']['A_log']", "['l0']['dt_bias']", "['l1']['wa']",
                          "['l2']['conv_v']", "['l3']['q_norm']", "['embed']"}
    assert max(worst.values()) < 2e-4, worst
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads))
    # the forward pass as the trainer runs it: the same loss, every layer's input
    again, hs = OH.olmo_hybrid_forward(params, tokens, cfg)
    assert float(again) == pytest.approx(float(loss), rel=1e-6) and len(hs) == 5


def test_the_trainers_gradient_is_that_of_the_references_whole_loss(
        reference, lane_config, float32_operands):
    """The lanes' trainer (``lane._pass``: a layer at a time, its inside
    computed again, the scan's gradient by its own rule) against ``jax.grad``
    of the reference's loss; a held-out pass leaves the lane as it is."""
    cfg = _cfg(lane_config)
    params = OH.init_olmo_hybrid_params(jax.random.key(1), cfg, 1.0)
    tokens = OH.make_token_dataset(jax.random.key(0), cfg)[0][1]
    v, keep = _gradient_steps(params)
    one_pass = jax.jit(lambda p, v, training: lane._pass(
        p, v, tokens, training, *OH._model(cfg), keep))
    _, got, loss, _ = one_pass(params, v, jnp.bool_(True))
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_fn(p, tokens, SMALL)))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert max(_worst(got, want).values()) < 2e-4
    # bfloat16 parameters would not pass: rounding them alone moves a leaf's
    # gradient by more than ten times that
    rounded = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    _, coarse, _, _ = one_pass(rounded, v, jnp.bool_(True))
    assert max(_worst(coarse, want).values()) > 2e-3
    p, same_v, held, _ = one_pass(params, v, jnp.bool_(False))
    assert all(jax.tree.leaves(jax.tree.map(lambda a, b: bool((a == b).all()), p, params)))
    assert not any(float(jnp.abs(x).max()) for x in jax.tree.leaves(same_v))
    assert float(held) == pytest.approx(float(loss), rel=1e-6)


@pytest.mark.parametrize("operand, steps, limit", [
    # float32 operands: rounding of sums only, steps amplify it little
    (jnp.float32, 1, 2e-5), (jnp.float32, 3, 1e-4),
    # as the chip runs it: bfloat16 operands (2^-8 a product) through four
    # layers and three steps: 8e-4 and 3.0e-2 measured. A mixer here reads
    # the stream as it is, with no norm before it, so what a rounding in one
    # layer does to the layers after it grows with the init scale (a first
    # gradient 1 % off at an init scale of 0.1, 13 % at 0.3, 37 % at 1, at
    # this size): the lane of the test is one of a small init scale, 0.32
    (jnp.bfloat16, 1, 5e-3), (jnp.bfloat16, 3, 5e-2),
])
def test_steps_match_the_reference(reference, lane_config, monkeypatch, operand, steps, limit):
    monkeypatch.setattr(lane, "_OPERAND", operand)
    cfg = _cfg(lane_config)
    eval_fn = OH.make_olmo_hybrid_eval_fn(cfg, data_seed=SMALL["data_seed"])
    vec = jnp.asarray([0.75, 0.5, 0.3, 0.25])
    got = float(jax.jit(lambda v: eval_fn(v, float(steps)))(vec))
    hparams = [float(x) for x in lane.decode_lane_hparams(vec)]
    start, want = reference.reference_losses(SMALL, hparams, [0, steps])
    assert want < start - 0.01  # the steps moved the loss: it is compared
    assert abs(got - want) < limit * (1 + abs(want))
    if operand == jnp.float32 and steps == 1:
        # the control: bfloat16 parameters and momentum fail the same limit
        coarse = reference.reference_losses(SMALL, hparams, [steps], dtype=jnp.bfloat16)[0]
        assert abs(coarse - want) > 10 * limit * (1 + abs(want))


# ------------------------------------------------- attention without positions
def _attention_inputs(reference, d=64, t=32):
    keys = jax.random.split(jax.random.key(7), 3)
    shapes = reference.layer_shapes(SMALL, "attention")
    p = {n: reference.init_leaf(keys[0], "l3/" + n, s, 1.0) for n, s in shapes.items()}
    p["q_norm"] = 1.0 + 0.3 * jax.random.normal(keys[1], (d,))
    p["k_norm"] = 1.0 - 0.2 * jax.random.normal(keys[1], (d,))
    return jax.random.normal(keys[2], (t, d)), p


def _mixer(x, p, inv_freq):
    return lane.attention_mixer(
        x, p, kv_heads=4, heads_per_kv=1, head_dim=16, inv_freq=inv_freq, factor=1.0,
        sight=None, block=16, scope="lane.gqa", norm_eps=1e-6)


def test_attention_without_rotation_under_a_whole_width_norm(reference, float32_operands):
    """``inv_freq=None``: nothing is turned, which is what tables of angle
    zero give, and what the reference's attention (no positions, an RMSNorm
    over the whole 64 of ``q`` and of ``k`` before the heads are split)
    computes; no cosine and no sine is in the program."""
    x, p = _attention_inputs(reference)
    got = _mixer(x, p, None)
    np.testing.assert_allclose(got, reference.attention(x, p, SMALL), atol=2e-5)
    np.testing.assert_allclose(got, _mixer(x, p, np.zeros(8)), atol=1e-6)
    text = jax.jit(lambda x: _mixer(x, p, None)).lower(x).as_text()
    assert "cosine" not in text and "sine" not in text
    assert "cosine" in jax.jit(lambda x: _mixer(x, p, np.ones(8))).lower(x).as_text()
    # positions matter where there are tables
    assert float(jnp.abs(got - _mixer(x, p, np.ones(8))).max()) > 1e-2


def test_the_norms_span_is_its_leafs_shape(reference, float32_operands):
    """f32[heads x head_dim]: one norm over the projection's whole width;
    f32[head_dim]: every head through its own norm with the one weight (the
    LFM2 and SDAR lanes'). With equal weights the two differ by each head's
    size against the whole row's."""
    x, p = _attention_inputs(reference)
    rms = lambda y, w: y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6) * w
    q = x @ p["wq"]
    whole = dict(p, q_norm=jnp.ones((64,)), k_norm=jnp.ones((64,)))
    per_head = dict(p, q_norm=jnp.ones((16,)), k_norm=jnp.ones((16,)))
    assert float(jnp.abs(_mixer(x, whole, None) - _mixer(x, per_head, None)).max()) > 1e-3
    # by hand: a softmax over each head's scores of normed q and k
    for params, normed in ((whole, lambda y: rms(y, 1.0)),
                           (per_head, lambda y: rms(y.reshape(32, 4, 16), 1.0).reshape(32, 64))):
        qh, kh = (normed(y).reshape(32, 4, 16) for y in (q, x @ p["wk"]))
        scores = jnp.einsum("qhd,khd->hqk", qh, kh) / 4.0
        att = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((32, 32), bool)), scores, -jnp.inf), -1)
        want = jnp.einsum("hqk,khd->qhd", att, (x @ p["wv"]).reshape(32, 4, 16))
        np.testing.assert_allclose(
            _mixer(x, params, None), want.reshape(32, 64) @ p["wo"], atol=2e-5)


# ------------------------------------------------------------ the comparison
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


GROUPS = ("gdn", "attention", "ffn", "embed_head")


def test_the_comparison_picks_the_top_lane_and_the_lane_of_the_telling_init_scale(reference):
    """The lane that reached the top rung, with its loss at its first two
    rungs; of the others, promoted once or not, the one whose init scale is
    nearest 0.18 by ratio (a first step's reading does not depend on the
    learning rate), with its loss at the first rung. Every sweep has both."""
    lrs = [3e-4, 2e-4, 0.05, 2.5e-3, 1e-3, 0.9, 2.9e-3, 0.02, 0.4]
    picked = reference.sample_lanes(
        _sweep_record(lrs, [0.08, 2.5, 0.18, 1.2, 0.4, 0.35, 5.0, 0.6, 3.5]))
    assert sorted(picked) == ["step", "top"]
    top, top_rungs = picked["top"]
    assert top[0] == 0.05 and sorted(top_rungs) == [1, 3]     # lane 2, though its init is 0.18
    step, step_rungs = picked["step"]
    assert (step[0], step[3]) == (0.9, 0.35) and sorted(step_rungs) == [1]   # 0.35 / 0.18 < 0.18 / 0.08
    # a sweep that drew none near it: the nearest there is, from either side
    picked = reference.sample_lanes(
        _sweep_record(lrs, [2.0, 2.5, 0.3, 1.9, 4.0, 1.7, 5.0, 1.6, 3.5]))
    assert picked["step"][0][3] == 1.6 and picked["top"][0][0] == 0.05
    picked = reference.sample_lanes(
        _sweep_record(lrs, [0.1, 2.5, 0.3, 0.11, 4.0, 1.7, 5.0, 1.6, 3.5]))
    assert picked["step"][0][3] == 0.11


@pytest.mark.parametrize("fault, shows", [
    (None, {}), ("unchanged", dict.fromkeys(GROUPS, 0.999)),
    ("beta_to_one", {"gdn": 0.05}), ("rotated", {"attention": 0.05}),
    ("control", {"ffn": 0.8, "gdn": 0.5})])
def test_the_comparison_reads_what_the_first_step_changed(
        reference, lane_config, float32_operands, monkeypatch, fault, shows):
    """``compare`` on a sweep's record whose ``lane_change`` is the lane's
    trainer (``eval_fn.change``, as the cell's builder hands it): the sound
    trainer's first step is the reference's in every group; a step that
    changed nothing reads 1 in every group; ``beta`` left in (0, 1) shows in
    the linear mixers' group and positions in the full layer in the
    attention's; the control (the reference with bfloat16 parameters and
    momentum) loses the step of the chosen lane, whose learning rate is 2e-4
    here, altogether."""
    cfg = _cfg(lane_config)
    if fault == "beta_to_one":
        cfg = cfg._replace(linear_allow_neg_eigval=False)
    if fault == "rotated":
        sound = lane.attention_mixer
        monkeypatch.setattr(lane, "attention_mixer", lambda *a, **k: sound(
            *a, **dict(k, inv_freq=10000.0 ** (-np.arange(0, 16, 2) / 16))))
    eval_fn = OH.make_olmo_hybrid_eval_fn(cfg, data_seed=SMALL["data_seed"])
    change = jax.jit(eval_fn.change)

    def lane_change(hparams, steps):
        lr, momentum, wd, init = hparams
        vec = jnp.asarray([(np.log10(lr) + 4) / 4, momentum / 0.99, (np.log10(wd) + 7) / 5,
                           (np.log10(init) + 1) / 2], jnp.float32)
        tree = change(vec, jnp.float32(steps))
        return jax.tree.map(jnp.zeros_like, tree) if fault == "unchanged" else tree

    # lanes 2 (top) and 0 (of the others, the init scale nearest 0.18)
    rec = _sweep_record([2e-4, 0.3, 0.05, 2.5e-3, 1e-3, 0.9, 2.9e-3, 0.02, 0.4],
                        [0.2, 0.5, 0.3, 1.2, 0.4, 0.35, 5.0, 0.6, 3.5])
    rec["lane_change"] = lane_change
    numbers = {name: (value, limit) for name, value, limit in reference.compare(
        SMALL, None, [rec], seed=5, control=fault == "control")}
    assert sorted(numbers) == sorted(["change_gap_" + g for g in GROUPS] + ["loss_gap_max"])
    for group in GROUPS:
        value, limit = numbers["change_gap_" + group]
        if group in shows:
            assert value > shows[group], (group, value)
        elif fault is None:
            # float32 on both sides; at lr 2e-4 a step is a few float32 units
            # of the embedding's entries
            assert value < 5e-3 < limit, (group, value)
    if fault in ("unchanged", "control"):
        # what the contract asks of the limits: a state left unchanged and the
        # precision below are not correct
        assert any(numbers["change_gap_" + g][0] > numbers["change_gap_" + g][1]
                   for g in GROUPS)
    # the record's losses are made up (10.0 ..): the net is not what is tested
    assert numbers["loss_gap_max"][1] == 0.25


def test_every_leaf_is_of_one_group_and_a_change_that_is_no_number_reads_infinity(
        reference):
    params = reference.init_params(SMALL, jax.random.key(0), 1.0)
    paths = [[k.key for k in path] for path, _ in jax.tree_util.tree_leaves_with_path(params)]
    held = {g: [p for p in paths if holds(p)] for g, holds in reference.groups(SMALL).items()}
    assert sum(len(v) for v in held.values()) == len(paths) == 3 * 18 + 11 + 3
    assert {p[-1] for p in held["gdn"]} == {
        "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "wa", "A_log", "dt_bias", "wb",
        "wg", "o_norm", "wo", "norm1"} and {p[0] for p in held["gdn"]} == {"l0", "l1", "l2"}
    assert {tuple(p) for p in held["attention"]} == {
        ("l3", n) for n in ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "norm1")}
    assert len(held["ffn"]) == 16 and {p[0] for p in held["embed_head"]} == {
        "embed", "norm_f", "head"}
    ones = jax.tree.map(jnp.ones_like, params)
    assert set(reference.change_gaps(ones, ones, SMALL).values()) == {0.0}
    bad = dict(ones, l3=dict(ones["l3"], wq=jnp.full_like(ones["l3"]["wq"], jnp.nan)))
    gaps = reference.change_gaps(bad, ones, SMALL)
    assert gaps["attention"] == np.inf and gaps["gdn"] == gaps["ffn"] == 0.0
    still = reference.change_gaps(jax.tree.map(jnp.zeros_like, params), ones, SMALL)
    assert set(still.values()) == {1.0}


def test_the_reference_trains_a_layer_at_a_time_by_the_gradient_of_its_whole_loss(reference):
    """With no momentum and no decay the momentum buffer after one step is
    ``jax.grad`` of ``loss_fn``, every leaf; what the step ``changed`` is the
    parameters after it less the parameters before."""
    fns = reference.lane_functions(SMALL, jnp.float32)
    p = fns.init(jnp.float32(1.0))
    v = jax.tree.map(jnp.zeros_like, p)
    train, _ = reference.dataset(SMALL)
    want = jax.grad(reference.loss_fn)(p, train[2], SMALL)
    changed = {}
    new_p, got = fns.step(p, v, 2, jnp.float32(0.5), jnp.float32(0.0), jnp.float32(0.0),
                          changed)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        # float32 both sides, a layer at a time against the whole: 1e-4 measured
        # (the norms after the sub-layers: see the lane's gradient test)
        np.testing.assert_allclose(
            g, w, atol=5e-4 * float(jnp.abs(w).max()) + 1e-12, err_msg=str(path))
    np.testing.assert_allclose(new_p["head"], p["head"] - 0.5 * want["head"], atol=1e-5)
    assert jax.tree.structure(changed) == jax.tree.structure(p)
    np.testing.assert_allclose(changed["l1"]["wa"], new_p["l1"]["wa"] - p["l1"]["wa"])


def test_the_references_delta_rule_is_a_scan_over_positions_and_solves_nothing(reference):
    """Step by step: scans whose bodies hold one position (blocks of 64 of
    them, for what is kept), no triangular solve and no cumulative sum."""
    x = jnp.zeros((32, 64))
    p = {n: jnp.ones(s) for n, s in reference.layer_shapes(SMALL, "gdn").items()}
    jaxpr = jax.make_jaxpr(lambda x: reference.gated_delta_net(x, p, SMALL))(x)

    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    eqns = list(primitives(jaxpr.jaxpr))
    names = {e.primitive.name for e in eqns}
    assert "scan" in names and not names & {"triangular_solve", "cumsum", "custom_vjp_call"}
    assert sorted(e.params["length"] for e in eqns if e.primitive.name == "scan") == [1, 64]
    assert "hpbandster_tpu" not in open(reference.__file__).read().replace(
        "of ``hpbandster_tpu``", "")


# ------------------------------------------------------- facts, configuration
def test_the_lanes_facts_are_its_models(lane_config):
    facts = OH.make_olmo_hybrid_eval_fn(_cfg(lane_config)).lane_facts
    assert facts.counters == (
        "gdn_gate_per_head", "gdn_backward_by_rule", "delta_solve_in_vmem",
        "attn_scores_in_vmem", "attn_rotation_in_vmem")
    assert facts.traced_budget and facts.tokens_per_step == 32
    # the published lane: 928,862,196 parameters, 8 bytes each and the
    # largest layer's gradient: one fits the chip's 16.9 GB, two do not
    published = OH.OlmoHybridConfig()
    shapes = jax.eval_shape(
        lambda: OH.init_olmo_hybrid_params(jax.random.key(0), published, 1.0))
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))
    assert count(shapes) == 928_862_196
    assert [count(shapes["l%d" % i]) for i in range(4)] == [215_570_172] * 3 + [185_809_920]
    assert 16.9e9 / 2 < OH.olmo_hybrid_lane_bytes(published) < 16.9e9
    assert OH.olmo_hybrid_lane_bytes(published) > 8 * 928_862_196 + 4 * 215_570_172
    visits, exits = OH._model(published)
    assert [v.leaf for v in visits] == ["l0", "l1", "l2", "l3"] and exits.after == (4,)
    assert exits.leaves == ("norm_f", "head")


def test_configuration_file_keeps_every_published_width(lane_config):
    config = json.load(open(os.path.join(BENCHMARK, "configs", "olmo-hybrid-sgd.json")))
    entry = next(c for c in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["configs"]
                 if c["name"] == "olmo-hybrid-sgd")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "vocab_size", "layer_types"]
    assert entry["source"] == config["source"] and entry["file"].endswith("olmo-hybrid-sgd.json")
    assert config["published"] == {"num_hidden_layers": 32, "vocab_size": 100352}
    assert config["cut"]["layers"] == [0, 1, 2, 3]
    assert (config["num_hidden_layers"], config["vocab_size"]) == (4, 100352 // 8)
    assert config["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    assert (config["hidden_size"], config["num_attention_heads"], config["intermediate_size"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"], config["rms_norm_eps"]) == (
        3840, 30, 11008, 96, 192, 4, 1e-6)
    assert {"norm_after_the_sublayer", "qk_norm_span", "no_positions", "gdn_init",
            "output_gate", "gdn_scale_and_norms", "init", "tokens", "optimizer"} <= set(
        config["assumed"])
    assert config["cut"]["chips_sharing_a_layer"] == 1
    assert config["cut"]["chips_sharing_the_vocabulary"] == 8
    assert lane_config(config) == OH.OlmoHybridConfig()
    if os.path.exists(CATALOG):
        # every key of the catalog's entry, unchanged but for ``reduced``
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "Olmo-Hybrid-7B")
        assert entry["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if config.get(k) != v]
        assert sorted(differs) == sorted(config["reduced"])
        assert config["layer_types"] == row["config"]["layer_types"][:4]


def test_lane_counts_agree_with_the_lane():
    config = json.load(open(os.path.join(BENCHMARK, "configs", "olmo-hybrid-sgd.json")))
    sys.path.insert(0, BENCHMARK)
    try:
        counts = load("lane_counts_olmo_hybrid.py")
    finally:
        sys.path.remove(BENCHMARK)
    assert counts.lane_params(config) == 928_862_196
    assert counts.layers_of(config) == {
        "gdn": 3, "gqa": 1, "dense_ffn": 4, "head": 1, "update": 1}
    flops = counts.part_forward_flops(config)
    # a linear layer's products (88.5 M weights in matrices) and, a token and
    # head, the scan's 7 d_k d_v
    assert flops["gdn"] == 2 * (2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30) \
        + 7 * 30 * 96 * 192
    assert flops["gqa"] == 2 * 4 * 3840 * 3840 + 4 * 30 * 128 * (2048 * 2049 // 2) / 2048
