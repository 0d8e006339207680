"""The delta rule's chunk systems solved by products
(``ops/pallas_triangular.py``, chosen by ``workloads/delta_rule.py``
``_inverse_and_solved``): the kernel in the Pallas interpreter against the
plain batched products, and both against forward substitution in float64 on
the host, at both cells' shapes, at every chunk length the rule's tests use
and at one that is no power of two; worst cases (``beta`` at 2 less a
rounding, keys nearly parallel) against ``solve_triangular``'s own error; a
padded tail to the bit; the solve's pull-back against ``jax.vjp`` of
``solve_triangular``; and what a kimi and an Olmo-Hybrid lane's program
holds of it.
"""

import functools
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.scipy.linalg import solve_triangular

from hpbandster_tpu.ops import pallas_triangular as T
from hpbandster_tpu.workloads import delta_rule as D
from hpbandster_tpu.workloads import kimi_linear as K
from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads import olmo_hybrid as OH

import kimi_small
import olmo_hybrid_small

#: (chunk, width of the right-hand side): the kimi cell's (``d_k + d_v`` =
#: 128 + 128) and the Olmo-Hybrid cell's (96 + 192), the rule's tests' (chunks
#: of 16 with 8 + 12 and 8 + 8, of 64 with 16 + 32), a chunk that is no power
#: of two, one of a whole tile of lanes and the smallest
SHAPES = [(64, 256), (64, 288), (16, 20), (16, 16), (64, 48), (48, 40), (128, 128), (8, 8),
          (2, 3), (1, 4)]
SYSTEMS = 16


def _systems(chunk, width, seed=0, beta_top=2.0, d=16, count=SYSTEMS):
    """Systems as a chunk makes them, ``I + diag(beta) tril(A, -1)`` with
    ``A_ij = (k_i . k_j) exp(G_i - G_j)`` of unit keys, ``beta`` up to
    ``beta_top``, and right-hand sides of order 1."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(count, chunk, d))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = np.cumsum(-rng.uniform(0, 0.3, size=(count, chunk)), -1)
    a = np.einsum("sic,sjc->sij", k, k) * np.exp(
        np.minimum(g[:, :, None] - g[:, None, :], 0.0))
    beta = rng.uniform(0, beta_top, size=(count, chunk, 1))
    system = np.eye(chunk) + beta * np.tril(a, -1)
    return (jnp.asarray(system, jnp.float32),
            jnp.asarray(rng.normal(size=(count, chunk, width)), jnp.float32))


def _worst(chunk, width, signs, count=SYSTEMS):
    """Where the inverse's entries are largest: ``beta`` at 2 less a
    rounding and the keys nearly parallel, so ``|A_ij|`` is near 1; ``signs``
    ``"parallel"`` (every ``A_ij`` near 1) or ``"alternating"`` (``k_i`` near
    ``(-1)^i k``: ``A_ij`` near ``(-1)^(i - j)``)."""
    rng = np.random.default_rng(7)
    k = 1.0 + 0.05 * rng.normal(size=(count, chunk, 16))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    if signs == "alternating":
        k = k * ((-1.0) ** np.arange(chunk))[None, :, None]
    beta = np.nextafter(np.float32(2), np.float32(0))
    system = np.eye(chunk) + beta * np.tril(np.einsum("sic,sjc->sij", k, k), -1)
    return (jnp.asarray(system, jnp.float32),
            jnp.asarray(rng.normal(size=(count, chunk, width)), jnp.float32))


def _substitution(system, rhs):
    """Forward substitution, row by row, in float64 on the host."""
    m, x = np.asarray(system, np.float64), np.array(rhs, np.float64)
    for i in range(m.shape[-1]):
        x[:, i] -= np.einsum("sj,sjr->sr", m[:, i, :i], x[:, :i])
    return x


def _plain(system, rhs):
    """The rule's own solve off the chip: the plain batched products."""
    inverse, solved = D._inverse_and_solved(system[None], rhs[None])
    return inverse[0], solved[0]


_kernel = functools.partial(T.inverse_and_solved, interpret=True)
_error = lambda got, want: float(np.abs(np.asarray(got, np.float64) - want).max())


@pytest.mark.parametrize("chunk, width", SHAPES)
def test_the_kernel_is_the_plain_products_and_both_are_substitution(chunk, width):
    """Inverse and solved rows of kernel and plain products within float32
    rounding of one another, and each no further from float64 substitution
    than four times ``solve_triangular`` is on the same systems."""
    system, rhs = _systems(chunk, width, seed=chunk + width)
    eye = np.broadcast_to(np.eye(chunk), system.shape)
    want_inverse, want = _substitution(system, eye), _substitution(system, rhs)
    theirs = _error(solve_triangular(system, rhs, lower=True), want)
    theirs_inverse = _error(solve_triangular(system, jnp.asarray(eye, jnp.float32), lower=True),
                            want_inverse)
    rounding = 2.0 ** -22
    plain, kernel = _plain(system, rhs), _kernel(system, rhs)
    for name, (inverse, solved) in (("plain", plain), ("kernel", kernel)):
        assert inverse.shape == system.shape and solved.shape == rhs.shape, name
        assert _error(inverse, want_inverse) <= max(
            4 * theirs_inverse, rounding * np.abs(want_inverse).max()), name
        assert _error(solved, want) <= max(4 * theirs, rounding * np.abs(want).max()), name
    np.testing.assert_allclose(kernel[0], plain[0], atol=1e-6 * np.abs(want_inverse).max())
    np.testing.assert_allclose(kernel[1], plain[1], atol=2e-6 * np.abs(want).max())


@pytest.mark.parametrize("form", ["plain", "kernel"])
@pytest.mark.parametrize("signs", ["parallel", "alternating"])
@pytest.mark.parametrize("chunk, width", [(64, 256), (64, 288), (48, 40)])
def test_a_worst_case_is_no_worse_than_four_times_solve_triangulars(chunk, width, signs, form):
    """``beta`` at 2 less a rounding, ``|A_ij|`` near 1: the inverse holds
    its largest entries, and the error against float64 substitution stays
    within four times ``solve_triangular``'s on the same inputs."""
    system, rhs = _worst(chunk, width, signs)
    want = _substitution(system, rhs)
    assert np.abs(_substitution(system, np.broadcast_to(np.eye(chunk), system.shape))).max() > 1.9
    theirs = _error(solve_triangular(system, rhs, lower=True), want)
    _, solved = (_plain if form == "plain" else _kernel)(system, rhs)
    assert _error(solved, want) <= 4 * theirs


@pytest.mark.parametrize("form", ["plain", "kernel"])
@pytest.mark.parametrize("chunk, width", [(64, 256), (16, 20)])
def test_a_padded_tail_is_solved_to_the_bit(chunk, width, form):
    """Padding steps have ``beta = 0``: their rows of the system are the
    identity's. A system of them alone is inverted to the identity and its
    right-hand side handed back bit for bit; a chunk whose last rows are
    padding keeps those rows of the inverse the identity's."""
    system, rhs = _systems(chunk, width)
    tail = chunk // 4
    padded = system.at[:, -tail:, :].set(jnp.eye(chunk)[-tail:])
    padded = padded.at[-2:].set(jnp.eye(chunk))
    inverse, solved = (_plain if form == "plain" else _kernel)(padded, rhs)
    assert bool((inverse[-2:] == jnp.eye(chunk)).all())
    assert bool((solved[-2:] == rhs[-2:]).all())
    assert bool((inverse[:, -tail:, :] == jnp.eye(chunk)[-tail:]).all())
    assert bool((solved[:, -tail:, :] == rhs[:, -tail:, :]).all())
    # what stands on and above a system's diagonal is not read
    above = padded + jnp.triu(jnp.full((chunk, chunk), 3.0))
    again, _ = (_plain if form == "plain" else _kernel)(above, rhs)
    assert bool((again == inverse).all())


@pytest.mark.parametrize("chunk, width, beta_top", [(64, 256, 1.0), (64, 288, 2.0), (16, 20, 2.0)])
def test_the_solves_pull_back_is_solve_triangulars(chunk, width, beta_top):
    """``_solve_pulled_back`` (a product with the kept inverse, then ``-d rhs
    solved^T``) against ``jax.vjp`` of ``solve_triangular``: ``d rhs`` whole,
    ``d system`` under the diagonal (the rule's system is constant on and
    above it)."""
    system, rhs = _systems(chunk, width, seed=3, beta_top=beta_top, count=6)
    system, rhs = system.reshape(2, 3, chunk, chunk), rhs.reshape(2, 3, chunk, width)
    cotangent = jax.random.normal(jax.random.key(5), rhs.shape)
    solved, pull = jax.vjp(lambda m, r: solve_triangular(m, r, lower=True), system, rhs)
    want_system, want_rhs = pull(cotangent)
    inverse, ours = D._inverse_and_solved(system, rhs)
    np.testing.assert_allclose(ours, solved, atol=1e-5 * float(jnp.abs(solved).max()))
    d_system, d_rhs = D._solve_pulled_back(inverse, ours, cotangent)
    np.testing.assert_allclose(d_rhs, want_rhs, atol=2e-5 * float(jnp.abs(want_rhs).max()))
    np.testing.assert_allclose(jnp.tril(d_system, -1), jnp.tril(want_system, -1),
                               atol=2e-5 * float(jnp.abs(want_system).max()))


@pytest.mark.parametrize("chunk", [1, 2, 3, 16, 48, 64, 100, 128])
def test_the_inverse_takes_two_products_a_doubling_but_the_first(chunk):
    """The number of blocks follows the chunk's length, whatever it is."""
    jaxpr = jax.make_jaxpr(lambda m: T.blocked_inverse(m, chunk))(
        jax.ShapeDtypeStruct((4, chunk, chunk), jnp.float32))
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2 * max(int(np.ceil(np.log2(chunk))) - 1, 0)
    assert all(e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2 for e in dots)
    assert "triangular_solve" not in str(jaxpr)


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_the_inverse_is_applied_with_float32_operands_in_both_passes(monkeypatch, form):
    """Where the inverse meets the right-hand side (the forward's solved
    rows, plain and in the kernel) and the cotangent (the backward's ``d
    rhs``), the product is ``Precision.HIGHEST``'s as those that make the
    inverse are: on the chip ``lane._FLOAT32``'s three bfloat16 passes leave
    those rows a hundred times further from float64 than ``solve_triangular``
    stood (PERF.md section 6, PR 49), and on a CPU no reading tells the two
    apart, so the program's text is what is held. ``d system = -d rhs
    solved^T`` keeps ``lane._FLOAT32`` as before the rule had an inverse."""
    if form == "kernel":
        monkeypatch.setattr(lane, "pallas_available", lambda: True)
        monkeypatch.setattr(T, "inverse_and_solved", _kernel)
    shapes = (jax.ShapeDtypeStruct((4, 4, 64, 64), jnp.float32),
              jax.ShapeDtypeStruct((4, 4, 64, 256), jnp.float32))
    precisions = lambda f, *x: [
        e.params["precision"] for e in _equations(jax.make_jaxpr(f)(*x).jaxpr)
        if e.primitive.name == "dot_general"]
    exact, float32 = (jax.lax.Precision.HIGHEST,) * 2, (lane._FLOAT32,) * 2
    forward = precisions(D._inverse_and_solved, *shapes)
    assert len(forward) == 11 and set(forward) == {exact}     # ten make it, one applies it
    assert precisions(D._solve_pulled_back, shapes[0], shapes[1], shapes[1]) == [exact, float32]


def _equations(jaxpr):
    """Every equation of a jaxpr and of what it calls (a kernel's body, a
    loop's, a jitted function's)."""
    for e in jaxpr.eqns:
        yield e
        for value in e.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_the_kernel_takes_both_cells_shapes_and_refuses_what_does_not_tile():
    assert T.fits(64 * 32, 64, 256) and T.fits(32 * 30, 64, 288)
    assert T.fits(16, 128, 128)
    assert not T.fits(64 * 32 + 8, 64, 256)      # no whole tiles of systems
    assert not T.fits(960, 48, 40)               # side by side no whole tile of lanes
    assert not T.fits(960, 4, 8)                 # rows no whole sublanes


@pytest.mark.parametrize("per_head", [True, False])
def test_the_rule_through_the_kernel_is_the_rule_through_plain_products(monkeypatch, per_head):
    """Values and the five gradients of ``delta_rule_chunked`` with the
    kernel where ``fits`` allows (chunks of 64, sixteen systems) against the
    plain products' within float32 rounding."""
    monkeypatch.setattr(lane, "_OPERAND", jnp.float32)
    t, h, dk, dv = 256, 4, 8, 24
    keys = jax.random.split(jax.random.key(11), 6)
    q, k = (D._l2norm(jax.random.normal(kk, (t, h, dk))) for kk in keys[:2])
    v = jax.random.normal(keys[2], (t, h, dv))
    log_a = -jnp.exp(jax.random.uniform(
        keys[3], (t, h) if per_head else (t, h, dk), minval=-6.0, maxval=1.0))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (t, h)))
    weights = jax.random.normal(keys[5], v.shape)

    def both(*x):
        rule = lambda *x: D.delta_rule_chunked(*x, 64, scope="lane.gdn")
        out, pull = jax.vjp(rule, *x)
        return out, pull(weights)

    x = (q, k, v, log_a, beta)
    assert D.solve_counters(t, h, dk, dv, 64) == (("delta_solve_in_vmem", 0.0),)
    plain, plain_grads = both(*x)
    # the rule with the kernel, interpreted: what the chip's path computes
    monkeypatch.setattr(lane, "pallas_available", lambda: True)
    monkeypatch.setattr(T, "inverse_and_solved", _kernel)
    assert D.solve_counters(t, h, dk, dv, 64) == (("delta_solve_in_vmem", 1.0),)
    assert "pallas_call" in str(jax.make_jaxpr(both)(*x))
    got, grads = both(*x)
    np.testing.assert_allclose(got, plain, atol=1e-5 * float(jnp.abs(plain).max()))
    for name, g, w in zip(("q", "k", "v", "log_a", "beta"), grads, plain_grads):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


# ------------------------------------------------------------ the lanes' programs
def _small_lane(name):
    """``(eval_fn, published configuration)`` of a lane at the tests' size,
    built as the benchmark builds it."""
    load = kimi_small.load
    # the builder imports the harness's ``program`` by that name
    sys.modules.setdefault("program", load("program.py"))
    if name == "kimi":
        config = load("configs", "kimi-linear-sgd.py").lane_config(kimi_small.SMALL)._replace(
            kda_chunk=16, kda_block=4, mla_heads_at_once=2)
        return K.make_kimi_linear_eval_fn(config), K.KimiLinearConfig()
    config = load("configs", "olmo-hybrid-sgd.py").lane_config(
        olmo_hybrid_small.SMALL)._replace(attn_query_block=16, gdn_chunk=16)
    return OH.make_olmo_hybrid_eval_fn(config), OH.OlmoHybridConfig()


@pytest.mark.parametrize("name", ["kimi", "olmo"])
def test_a_lanes_program_holds_no_triangular_solve_and_counts_where_the_solve_runs(
        monkeypatch, name):
    """The lowered text of a lane's evaluation (its training steps and its
    held-out pass) holds no ``triangular_solve`` operation; the lane's
    static counter ``delta_solve_in_vmem`` reads 0 on a CPU and, where Mosaic
    compiles, what ``fits`` says of the published shapes: 1 in both cells."""
    eval_fn, published = _small_lane(name)
    text = jax.jit(eval_fn).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32), jax.ShapeDtypeStruct((), jnp.float32)).as_text()
    assert "dot_general" in text
    assert not re.search(r"triangular[_-]solve", text)
    facts = eval_fn.lane_facts
    assert "delta_solve_in_vmem" in facts.counters
    if name == "kimi":
        shape = (published.seq_len, published.num_heads, published.kda_head_dim,
                 published.kda_head_dim, published.kda_chunk)
        assert shape == (4096, 32, 128, 128, 64)
    else:
        shape = (published.seq_len, published.linear_num_heads, published.linear_key_head_dim,
                 published.linear_value_head_dim, published.gdn_chunk)
        assert shape == (2048, 30, 96, 192, 64)
    assert D.solve_counters(*shape) == (("delta_solve_in_vmem", 0.0),)
    monkeypatch.setattr(lane, "pallas_available", lambda: True)
    t, h, dk, dv, chunk = shape
    assert T.fits(t // chunk * h, chunk, dk + dv)
    assert D.solve_counters(*shape) == (("delta_solve_in_vmem", 1.0),)
