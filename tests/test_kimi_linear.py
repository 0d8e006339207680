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
import re
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


def _plain_chunk_products(q, k, g, sub):
    """``K._chunk_products`` as it stood, with nothing held back from JAX's
    gradient: where a block's decay is split takes its two cotangents."""
    c, d = q.shape[-2:]
    r = c // sub
    blocks = lambda x: x.reshape(x.shape[:-2] + (r, sub, d))
    qb, kb, gb = blocks(q), blocks(k), blocks(g)
    tri = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None]
    decay = jnp.exp(jnp.where(
        tri, gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
    kd = kb[..., None, :, :] * decay
    a_diag = jnp.sum(kb[..., :, None, :] * kd, -1)
    p_diag = jnp.sum(qb[..., :, None, :] * kd, -1)
    if r == 1:
        return a_diag[..., 0, :, :], p_diag[..., 0, :, :]
    g_star = jnp.concatenate(
        [jnp.zeros_like(gb[..., :1, -1, :]), gb[..., :-1, -1, :]], -2)
    left = jnp.exp(gb - g_star[..., :, None, :])
    below = jnp.tril(jnp.ones((r, r), bool), -1)[:, :, None, None]
    right = kb[..., None, :, :, :] * jnp.exp(jnp.where(
        below, g_star[..., :, None, None, :] - gb[..., None, :, :, :], -jnp.inf))
    off = jnp.einsum(
        "...bic,...bdjc->...bidj", jnp.concatenate([kb * left, qb * left], -2), right,
        precision=K._FLOAT32)
    eye = jnp.eye(r, dtype=jnp.float32)[:, None, :, None]
    whole = lambda diag, off: (
        diag[..., :, :, None, :] * eye + off).reshape(q.shape[:-2] + (c, c))
    return (whole(a_diag, off[..., :sub, :, :]), whole(p_diag, off[..., sub:, :, :]))


def _plain_kda_chunked(q, k, v, log_a, beta, chunk, sub=None):
    """``K.kda_chunked`` as it stood before its backward rule, for JAX to
    differentiate: the oracle of the rule's tests, which no workload calls.
    The scan and the solve are linearised and transposed by their own
    rules, the blocks' products sit under ``jax.checkpoint``."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    sub = sub or max(chunk // 4, 1)
    pad = -t % chunk
    if pad:
        q, k, v, log_a = (jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
                          for x in (q, k, v, log_a))
        beta = jnp.pad(beta, ((0, pad), (0, 0)))
    n = (t + pad) // chunk
    split = lambda x: x.reshape((n, chunk) + x.shape[1:]).swapaxes(1, 2)
    q, k, v, log_a, beta = (split(x) for x in (q, k, v, log_a, beta))
    g = jnp.cumsum(log_a, axis=2)
    a, p = jax.checkpoint(_plain_chunk_products, static_argnums=(3,))(q, k, g, sub)
    strictly = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    system = (jnp.eye(chunk, dtype=jnp.float32)
              + beta[..., None] * jnp.where(strictly, a, 0.0))
    from_start = jnp.exp(g)
    rhs = beta[..., None] * jnp.concatenate([v, k * from_start], -1)
    solved = jax.scipy.linalg.solve_triangular(system, rhs, lower=True)
    w_v, w_k = solved[..., :dv], solved[..., dv:]
    q_start = q * from_start
    k_end = k * jnp.exp(g[:, :, -1:, :] - g)
    keep = from_start[:, :, -1, :, None]

    def one_chunk(state, xs):
        w_v, rows, p, k_end, keep = xs
        from_state = K._einsum("hic,hcv->hiv", rows, state)
        u = w_v - from_state[:, :chunk]
        out = from_state[:, chunk:] + K._einsum("hij,hjv->hiv", p, u)
        return keep * state + K._einsum("hic,hiv->hcv", k_end, u), out

    _, out = jax.lax.scan(
        one_chunk, jnp.zeros((h, dk, dv), jnp.float32),
        (w_v, jnp.concatenate([w_k, q_start], 2), p, k_end, keep))
    return out.swapaxes(1, 2).reshape((t + pad, h, dv))[:t]


def _kda_inputs(length, h=3, dk=8, chunk=16):
    """Decays from none (``log a = 0``: a channel in four) to so strong
    that a chunk's sum passes -100, ``beta`` at exactly 0 and 1 too."""
    keys = jax.random.split(jax.random.key(100 + length), 7)
    q, k = (K._l2norm(jax.random.normal(kk, (length, h, dk))) for kk in keys[:2])
    v = jax.random.normal(keys[2], (length, h, dk))
    log_a = -jnp.exp(jax.random.uniform(keys[3], (length, h, dk), minval=-9.0, maxval=4.0))
    log_a = jnp.where(jax.random.uniform(keys[4], (length, h, dk)) < 0.25, 0.0, log_a)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (length, h)))
    ends = jax.random.uniform(keys[6], (length, h))
    beta = jnp.where(ends < 0.1, 0.0, jnp.where(ends > 0.9, 1.0, beta))
    if length >= chunk:
        assert float(log_a[:chunk].sum(0).min()) < -100
    assert bool((log_a == 0).any() and (beta == 0).any() and (beta == 1).any())
    return q, k, v, log_a, beta


@pytest.mark.parametrize("operand, limit", [
    # float32 operands: the same products in the same order, sums in another
    (jnp.float32, 2e-4),
    # as the chip runs it: the rule rounds a cotangent to bfloat16 where
    # JAX's transposes round another one; the forward test's tolerance
    (jnp.bfloat16, 3e-2),
])
@pytest.mark.parametrize("oracle", ["plain_body", "recurrence"])
@pytest.mark.parametrize("length", [70, 64, 9, 37])
def test_the_rules_five_gradients(reference, monkeypatch, length, oracle, operand, limit):
    """``q, k, v, log_a, beta`` through the backward rule against what JAX
    makes of the plain body (the same operands) and against the
    token-by-token recurrence (float32), over lengths padded and whole, of
    one chunk and of several; each within ``limit`` of the gradient's
    largest entry. The values are the plain body's within float32 rounding
    (four units in the last place of the largest: the plain body solves a
    chunk's system by ``solve_triangular``'s substitution, the rule inverts
    it by products, ``delta_rule._inverse_and_solved``)."""
    monkeypatch.setattr(K.lane, "_OPERAND", operand)
    chunk = 16
    x = _kda_inputs(length, chunk=chunk)
    weights = jax.random.normal(jax.random.key(length), x[2].shape)
    ours = lambda *x: (K.kda_chunked(*x, chunk) * weights).sum()
    if oracle == "plain_body":
        plain = _plain_kda_chunked(*x, chunk)
        np.testing.assert_allclose(
            K.kda_chunked(*x, chunk), plain, atol=2.0 ** -21 * float(jnp.abs(plain).max()))
        theirs = lambda *x: (_plain_kda_chunked(*x, chunk) * weights).sum()
    else:
        theirs = lambda q, k, v, g, b: (
            reference.delta_rule(q, k, v, jnp.exp(g), b) * weights).sum()
    got = jax.jit(jax.grad(ours, argnums=(0, 1, 2, 3, 4)))(*x)
    want = jax.jit(jax.grad(theirs, argnums=(0, 1, 2, 3, 4)))(*x)
    for name, g, w in zip(("q", "k", "v", "log_a", "beta"), got, want):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(
            g, w, atol=limit * float(jnp.abs(w).max()), err_msg=name)


def _scans(jaxpr):
    """Every ``scan`` of a jaxpr, however deep."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def _mechanism_args(t=256, h=2, d=16):
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return (shape(t, h, d),) * 4 + (shape(t, h),)


def _through(kda):
    """``kda`` in the cell's chunks of 64 and blocks of 16."""
    return lambda *x: kda(*x, 64, 16)


def test_the_gradient_of_kda_is_the_rules_and_no_transposed_scan():
    """What ``jax.grad`` makes of ``kda_chunked``: its value holds the
    rule's call; its gradient two scans that were written (the rule's
    forward, and one from the last chunk to the first), where the plain
    body's second scan is the one JAX transposed (it carries linear
    arguments)."""
    args = _mechanism_args()
    value = jax.make_jaxpr(_through(K.kda_chunked))(*args)
    assert "custom_vjp_call" in {e.primitive.name for e in value.jaxpr.eqns}
    grad = lambda kda: jax.make_jaxpr(jax.grad(
        lambda *x: (_through(kda)(*x) ** 2).sum(), argnums=(0, 1, 2, 3, 4)))(*args)
    ours, plain = (list(_scans(grad(kda).jaxpr)) for kda in (K.kda_chunked, _plain_kda_chunked))
    assert [any(e.params["linear"]) for e in plain] == [False, True]
    assert [any(e.params["linear"]) for e in ours] == [False, False]
    assert [e.params["reverse"] for e in ours] == [False, True]


def test_the_rule_keeps_less_than_half_of_what_jax_kept():
    """What the forward hands the backward (``jax.eval_shape`` of
    ``jax.vjp``'s pull-back, every array counted): the inputs, the systems'
    inverses (since PR 49: 16 KB a chunk and head, 34 MB a layer at the
    cell's shape), the solved rows, the chunks' starting states and ``u``,
    under half the bytes that ``jax.vjp`` of the plain body keeps (24 arrays:
    1.05 MB here, 1.14 GB a layer at the cell's shape, where the rule's nine
    are 0.63 GB)."""
    def kept(kda):
        _, pull = jax.eval_shape(lambda *x: jax.vjp(_through(kda), *x), *_mechanism_args())
        return [int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(pull)]

    ours, plain = kept(K.kda_chunked), kept(_plain_kda_chunked)
    assert len(plain) == 24 and len(ours) == 9
    assert sum(ours) < 0.5 * sum(plain)


def test_a_step_computes_no_more_exp_arrays_than_the_plain_body():
    """The plain body's gradient computes 8 ``exp`` arrays (the forward's 5
    and the 3 of the blocks' products again, behind ``jax.checkpoint``'s
    barrier). The rule's backward writes the chunk-local part again from
    the inputs with no barrier, so the compiler is free to keep it from the
    rule's forward: the compiled program computes the forward's 5."""
    def programs(kda):
        lowered = jax.jit(jax.grad(
            lambda *x: (_through(kda)(*x) ** 2).sum(), argnums=(0, 1, 2, 3, 4))
        ).lower(*_mechanism_args())
        return (len(re.findall(r"stablehlo\.exponential\b", lowered.as_text())),
                len(re.findall(r" exponential\(", lowered.compile().as_text())))

    forward = len(re.findall(r"stablehlo\.exponential\b", jax.jit(
        _through(K.kda_chunked)).lower(*_mechanism_args()).as_text()))
    assert forward == 5
    assert programs(_plain_kda_chunked)[0] == 8
    written, computed = programs(K.kda_chunked)
    assert written == 2 * forward and computed == forward <= 8


@pytest.mark.parametrize("length", [70, 64, 9])
def test_the_rules_forward_is_the_value(length):
    """Under ``jax.vjp`` the forward hands over what it kept and the value
    bit for bit, padded or whole."""
    x = _kda_inputs(length)
    out, _ = jax.vjp(lambda *x: K.kda_chunked(*x, 16), *x)
    assert bool((out == K.kda_chunked(*x, 16)).all())


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
        "moe_combine_by_gather", "moe_products_in_vmem", "kda_backward_by_rule",
        "delta_solve_in_vmem")
    assert facts.tokens_per_step == 64
    assert 12 * n_params < K.kimi_linear_lane_bytes(K.KimiLinearConfig()) < 16.9e9
