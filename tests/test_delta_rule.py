"""The gated delta rule's one chunked scan (``workloads/delta_rule.py``) in
both forms of its gate: a gate a head (``log_a`` f32[T, H], Gated DeltaNet)
against the same gate broadcast over the channels through the per-channel form
(KDA's), and both against the recurrence written step by step here, a
``lax.scan`` over positions; the backward rule against ``jax.grad`` of that
recurrence. ``d_k != d_v``, ``beta`` up to 2, lengths that are no multiple of
the chunk, decays so strong that they underflow. KDA's own tests (a gate a
channel, ``kimi_linear.kda_chunked``) stay in ``test_kimi_linear.py``.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.workloads import delta_rule as D
from hpbandster_tpu.workloads import kimi_linear as K
from hpbandster_tpu.workloads import lane


def recurrence(q, k, v, log_a, beta):
    """``S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, one position at a time, float32; ``log_a`` f32[T, H]
    or f32[T, H, d_k]."""
    t, h, dk = q.shape
    a = jnp.exp(log_a if log_a.ndim == 3 else log_a[..., None])

    def position(state, x):
        qt, kt, vt, at, bt = x
        state = at[:, :, None] * state
        u = bt[:, None] * (vt - jnp.einsum(
            "hkv,hk->hv", state, kt, precision=jax.lax.Precision.HIGHEST))
        state = state + kt[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=jax.lax.Precision.HIGHEST)

    _, out = jax.lax.scan(
        position, jnp.zeros((h, dk, v.shape[-1]), jnp.float32), (q, k, v, a, beta))
    return out


def _inputs(length, h=3, dk=8, dv=12, strongest=6.0):
    """``d_k != d_v``; ``beta`` in [0, 2], at exactly 0 and 2 too; gates from
    none (``log a = 0``: a position in four) to so strong that a chunk's sum
    passes -100 (``exp`` of it is no float32 but zero)."""
    keys = jax.random.split(jax.random.key(300 + length), 7)
    q, k = (D._l2norm(jax.random.normal(kk, (length, h, dk))) for kk in keys[:2])
    v = jax.random.normal(keys[2], (length, h, dv))
    log_a = -jnp.exp(jax.random.uniform(keys[3], (length, h), minval=-9.0, maxval=strongest))
    log_a = jnp.where(jax.random.uniform(keys[4], (length, h)) < 0.25, 0.0, log_a)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[5], (length, h)))
    ends = jax.random.uniform(keys[6], (length, h))
    beta = jnp.where(ends < 0.1, 0.0, jnp.where(ends > 0.9, 2.0, beta))
    return q, k, v, log_a, beta


def _per_head(*x, chunk=16):
    return D.delta_rule_chunked(*x, chunk, scope="lane.gdn")


def _per_channel(q, k, v, log_a, beta, chunk=16):
    """The same gate fed to KDA's form, broadcast over the channels."""
    wide = jnp.broadcast_to(log_a[..., None], q.shape)
    return D.delta_rule_chunked(q, k, v, wide, beta, chunk, scope="lane.kda")


@pytest.mark.parametrize("operand, limit", [
    # float32 operands: the same sums in another order
    (jnp.float32, 1e-4),
    # as the chip runs it: the state's products take bfloat16 operands, 2^-8
    # a product, outputs of order 1 (KDA's forward test's tolerance)
    (jnp.bfloat16, 3e-2),
])
@pytest.mark.parametrize("length", [70, 64, 9, 37])
def test_a_gate_a_head_is_the_per_channel_form_and_the_recurrence(
        monkeypatch, length, operand, limit):
    """Lengths padded and whole, of one chunk and of several."""
    monkeypatch.setattr(lane, "_OPERAND", operand)
    x = _inputs(length)
    if length >= 16:
        assert float(x[3][:16].sum(0).min()) < -100
    assert bool((x[3] == 0).any() and (x[4] == 0).any() and (x[4] == 2).any())
    got, wide, want = _per_head(*x), _per_channel(*x), recurrence(*x)
    assert got.shape == want.shape == (length, 3, 12)
    np.testing.assert_allclose(got, want, atol=limit)
    np.testing.assert_allclose(wide, want, atol=limit)
    np.testing.assert_allclose(got, wide, atol=limit)


@pytest.mark.parametrize("operand, limit", [
    (jnp.float32, 2e-4),
    # the rule rounds a cotangent to bfloat16 where the recurrence rounds none
    (jnp.bfloat16, 3e-2),
])
@pytest.mark.parametrize("length", [70, 64, 9, 37])
def test_the_rules_five_gradients_with_a_gate_a_head(monkeypatch, length, operand, limit):
    """``q, k, v, log_a, beta`` through the backward rule against
    ``jax.grad`` of the recurrence; ``log_a``'s gradient is f32[T, H], what
    the per-channel form's sums to over the channels; each within ``limit``
    of the gradient's largest entry."""
    monkeypatch.setattr(lane, "_OPERAND", operand)
    x = _inputs(length)
    weights = jax.random.normal(jax.random.key(length), x[2].shape)
    grad = lambda f: jax.jit(jax.grad(
        lambda *x: (f(*x) * weights).sum(), argnums=(0, 1, 2, 3, 4)))(*x)
    got, wide, want = grad(_per_head), grad(_per_channel), grad(recurrence)
    for name, g, c, w in zip(("q", "k", "v", "log_a", "beta"), got, wide, want):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all()), name
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(g, w, atol=limit * scale, err_msg=name)
        np.testing.assert_allclose(c, w, atol=limit * scale, err_msg=name)


@pytest.mark.parametrize("length", [70, 64, 9])
def test_the_rules_forward_is_the_value_in_either_form(length):
    """Under ``jax.vjp`` the forward hands over what it kept and the value
    bit for bit, padded or whole."""
    x = _inputs(length)
    for form in (_per_head, _per_channel):
        out, _ = jax.vjp(form, *x)
        assert bool((out == form(*x)).all())


def test_a_decay_that_underflows_forgets_and_stays_a_number():
    """Every gate at -200 a step: ``exp`` of a chunk's sum is zero, a
    quotient of cumulative decays would be 0 / 0. The state then holds the
    last position alone, ``o_t = beta_t (k_t . q_t) v_t``, values and
    gradients numbers."""
    q, k, v, _, beta = _inputs(40)
    log_a = jnp.full(beta.shape, -200.0)
    got = _per_head(q, k, v, log_a, beta)
    want = (beta * (q * k).sum(-1))[..., None] * v
    np.testing.assert_allclose(got, want, atol=3e-2)
    grads = jax.grad(lambda *x: (_per_head(*x) ** 2).sum(), argnums=(0, 1, 2, 3, 4))(
        q, k, v, log_a, beta)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


def _scans(jaxpr):
    """Every ``scan`` of a jaxpr, however deep."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def _shapes(t=256, h=2, dk=16, dv=32, per_head=True):
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return (shape(t, h, dk), shape(t, h, dk), shape(t, h, dv),
            shape(t, h) if per_head else shape(t, h, dk), shape(t, h))


def test_the_gradient_of_either_form_is_the_rules_and_no_transposed_scan():
    """The two forms share the rule: the value holds its call, the gradient
    two scans that were written (the rule's forward, and one from the last
    chunk to the first), neither carrying linear arguments."""
    for per_head in (True, False):
        through = lambda *x: D.delta_rule_chunked(*x, 64, 16, scope="lane.gdn")
        args = _shapes(per_head=per_head)
        value = jax.make_jaxpr(through)(*args)
        assert "custom_vjp_call" in {e.primitive.name for e in value.jaxpr.eqns}
        grad = jax.make_jaxpr(jax.grad(
            lambda *x: (through(*x) ** 2).sum(), argnums=(0, 1, 2, 3, 4)))(*args)
        scans = list(_scans(grad.jaxpr))
        assert [any(e.params["linear"]) for e in scans] == [False, False]
        assert [e.params["reverse"] for e in scans] == [False, True]


def test_a_gate_a_head_takes_one_masked_product_and_no_blocks():
    """What the form saves: its chunk-local part computes 3 ``exp`` arrays
    (the ``C x C`` decays, ``exp G``, ``exp(G_C - G)``) where the per-channel
    form computes 5 (the blocks on the diagonal, the two sides of ``g*``
    besides), none of them wider than ``[n, H, C, C]``; and of its two
    float32-operand products the per-channel form's 5-dimensional one is
    gone."""
    def lowered(per_head):
        return jax.jit(lambda *x: D.delta_rule_chunked(
            *x, 64, 16, scope="lane.gdn")).lower(*_shapes(per_head=per_head)).as_text()

    exps = lambda text: re.findall(r"stablehlo\.exponential %\S+ : tensor<([\dx]+)xf32>", text)
    head, channel = lowered(True), lowered(False)
    assert len(exps(channel)) == 5 and len(exps(head)) == 3
    # n = 4 chunks, H = 2, C = 64
    assert sorted(exps(head)) == ["4x2x64x1", "4x2x64x1", "4x2x64x64"]
    assert any(shape.count("x") >= 5 for shape in exps(channel))


def test_kda_chunked_is_the_scan_under_kdas_scope():
    """``kimi_linear.kda_chunked`` is one line over this module: the same
    values bit for bit, and the backward rule named ``lane.kda`` there,
    ``lane.gdn`` where the Olmo-Hybrid lane calls it."""
    q, k, v, log_a, beta = _inputs(37)
    wide = jnp.broadcast_to(log_a[..., None], q.shape)
    assert bool((K.kda_chunked(q, k, v, wide, beta, 16)
                 == _per_channel(q, k, v, log_a, beta)).all())

    def scopes(f, *x):
        text = jax.jit(jax.grad(lambda *x: (f(*x) ** 2).sum())).lower(*x).as_text(
            debug_info=True)
        return set(re.findall(r"lane\.(?:kda|gdn)", text))

    assert scopes(lambda q: K.kda_chunked(q, k, v, wide, beta, 16), q) == {"lane.kda"}
    assert scopes(lambda q: _per_head(q, k, v, log_a, beta), q) == {"lane.gdn"}
