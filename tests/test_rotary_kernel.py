"""The rotation's kernel (``ops/pallas_rotary.py``) in the Pallas interpreter
against ``lane._rotate`` on the ``[T, heads, d]`` view: small shapes, the
whole file well under a minute on a CPU.

The kernel's arithmetic is ``_rotate``'s, an entry. The CPU's compiler makes
one instruction of a product and the sum it feeds (the chip has none such),
and not the same way in the interpreter's program as in the plain form's:
float32 results agree to that instruction's one rounding, and after the
rounding to bfloat16 an entry in a few thousand differs in its last bit.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.ops import pallas_attention, pallas_rotary
from hpbandster_tpu.workloads import lane

#: a float32's last bit at the largest entries these cases hold (under 16)
_LAST_BIT = 2.0 ** -20

_two_rows_a_position = lambda t: jnp.arange(t, dtype=jnp.float32) % (t // 2)

CASES = {
    "a whole head of 128": (32, 4, 128, 128, None),
    "64 channels of 128": (32, 6, 128, 64, None),
    "heads of 64 in pairs": (48, 4, 64, 64, None),
    "half a head of 64": (32, 6, 64, 32, None),
    "two rows a position": (64, 4, 128, 128, _two_rows_a_position),
    "blocks of rows and of heads": (512, 12, 128, 64, None),
}


def _case(name):
    """``(x, cos, sin, rotary, heads apart)`` of a case: YaRN-like tables
    (a factor on both) at the rows' positions."""
    t, heads, d, rotary, positions = CASES[name]
    inv_freq = 1e4 ** (-np.arange(0, rotary, 2) / rotary)
    cos, sin = lane._rotary_tables(
        inv_freq, 1.3, t if positions is None else positions(t), d)
    x = jax.random.normal(jax.random.key(2), (t, heads * d))
    apart = lambda x: lane._rotate(x.reshape(t, heads, d), cos, sin, rotary).reshape(t, -1)
    return x, cos, sin, rotary, apart


def _kernel(cos, sin, rotary, operand):
    return lambda x: pallas_rotary.rotate_side_by_side(
        x, cos, sin, rotary // 2, operand, "lane.swa", True)


@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_turns_as_heads_apart_are_turned(name):
    """Float32 out of the kernel is ``_rotate`` on the heads apart, and the
    operand it hands the attention kernels is that rounded once."""
    x, cos, sin, rotary, apart = _case(name)
    t, heads, d, _, _ = CASES[name]
    assert pallas_rotary.fits(t, heads * d, d, rotary // 2)
    want = apart(x)
    np.testing.assert_allclose(
        _kernel(cos, sin, rotary, jnp.float32)(x), want, rtol=0, atol=_LAST_BIT)
    rounded = _kernel(cos, sin, rotary, jnp.bfloat16)(x)
    assert rounded.dtype == jnp.bfloat16
    theirs = want.astype(jnp.bfloat16)
    assert float((rounded != theirs).mean()) < 2e-3
    np.testing.assert_allclose(
        rounded.astype(jnp.float32), theirs.astype(jnp.float32), rtol=2.0 ** -7, atol=1e-6)


@pytest.mark.parametrize("name", list(CASES))
def test_the_kernels_gradient_is_the_plain_forms(name):
    """The backward rule (the same kernel, the turn transposed) against
    ``jax.grad`` of ``_rotate``: float32 to the last bit, and the channels
    that are not turned get back their cotangent times the cosine alone."""
    x, cos, sin, rotary, apart = _case(name)
    weigh = jax.random.normal(jax.random.key(3), x.shape)
    pulled = lambda turn: jax.grad(lambda x: (turn(x) * weigh).sum())(x)
    ours = pulled(_kernel(cos, sin, rotary, jnp.float32))
    assert ours.dtype == jnp.float32
    np.testing.assert_allclose(ours, pulled(apart), rtol=0, atol=_LAST_BIT)
    d = cos.shape[1]
    if rotary < d:
        still = (jnp.arange(x.shape[1]) % d) >= rotary
        np.testing.assert_array_equal(ours[:, still], weigh[:, still])


def test_a_cotangent_comes_back_through_the_rounding_unrounded():
    """The kernel hands the attention kernels bfloat16 and they hand its
    backward rule a float32 cotangent: the queries' and keys' gradients are
    those of the plain rotation before the same kernels, which round their
    operands themselves, and not a bfloat16's 2^-9 away."""
    t, g, r, d = 128, 2, 2, 128
    keys = jax.random.split(jax.random.key(6), 4)
    q = jax.random.normal(keys[0], (t, g * r * d))
    k, v = (jax.random.normal(key, (t, g * d)) for key in keys[1:3])
    weigh = jax.random.normal(keys[3], q.shape)
    cos, sin = lane._rotary_tables(1e4 ** (-np.arange(0, d, 2) / d), 1.0, t)
    apart = lambda x: lane._rotate(x.reshape(t, -1, d), cos, sin).reshape(t, -1)
    tiles = pallas_attention.Tiles(64, 128)

    def pulled(turn):
        loss = lambda q, k: (pallas_attention.fused_banded_attention(
            turn(q), turn(k), v, (g, r, d), lane.Causal(None), tiles, jnp.bfloat16,
            "lane.gqa", True) * weigh).sum()
        return jax.grad(loss, (0, 1))(q, k)

    for ours, theirs in zip(pulled(_kernel(cos, sin, d, jnp.bfloat16)), pulled(apart)):
        assert ours.dtype == jnp.float32
        # (the few entries further off lie in the rows of an operand whose
        # last bit the CPU's fused instruction moved; a rounded cotangent
        # would put most entries there)
        off = jnp.abs(ours - theirs) > 1e-5 * float(jnp.abs(theirs).max())
        assert float(off.mean()) < 0.01


@pytest.mark.parametrize("t, heads, d, rotary", [
    (24, 6, 16, 16), (32, 4, 96, 96), (32, 3, 64, 64), (40, 4, 128, 128)])
def test_a_shape_the_kernel_refuses_is_turned_by_the_plain_form(monkeypatch, t, heads, d, rotary):
    """Heads that make no whole tiles of lanes (16, 96, an odd number of
    64) and rows that are no whole chunks: ``fits`` says no, and
    ``lane._rotate_side_by_side`` answers in plain JAX, float32, to the last
    bit of ``_rotate``, on a backend where Mosaic compiles too."""
    assert not pallas_rotary.fits(t, heads * d, d, rotary // 2)
    monkeypatch.setattr(lane, "pallas_available", lambda: True)
    inv_freq = 1e4 ** (-np.arange(0, rotary, 2) / rotary)
    cos, sin = lane._rotary_tables(inv_freq, 1.0, t, d)
    x = jax.random.normal(jax.random.key(2), (t, heads * d))
    got = lane._rotate_side_by_side(x, cos, sin, rotary, scope="lane.swa")
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(
        got, lane._rotate(x.reshape(t, heads, d), cos, sin, rotary).reshape(t, -1))


def test_the_lane_takes_the_kernel_where_mosaic_compiles_and_says_so(monkeypatch):
    """``lane._rotate_side_by_side`` is the plain form on a CPU and the
    kernel where the backend compiles Mosaic and the shapes fit (here told
    so, the kernel in the interpreter): it then hands back the products'
    operand type. ``attn_rotation_in_vmem`` follows the same rule, layer by
    layer, beside ``attn_scores_in_vmem``: the turn is the kernel only on
    the fused kernels' path, and a layer without positions is not counted."""
    x, cos, sin, rotary, apart = _case("64 channels of 128")
    plain = lane._rotate_side_by_side(x, cos, sin, rotary, scope="lane.gqa")
    assert plain.dtype == jnp.float32
    np.testing.assert_array_equal(plain, apart(x))
    mellum2, lfm2, ouro, odd = (8192, 128, 8, 4), (8192, 64, 4, 8), (2048, 128, 1, 16), (8192, 96, 4, 4)
    for shape in (mellum2, lfm2, ouro, odd):
        assert dict(lane.attention_counters(*shape))["attn_rotation_in_vmem"] == 0.0

    monkeypatch.setattr(lane, "pallas_available", lambda: True)
    monkeypatch.setattr(
        pallas_rotary, "rotate_side_by_side",
        functools.partial(pallas_rotary.rotate_side_by_side, interpret=True))
    turned = lane._rotate_side_by_side(x, cos, sin, rotary, scope="lane.gqa")
    assert turned.dtype == lane._OPERAND
    np.testing.assert_allclose(
        turned.astype(jnp.float32), plain, rtol=2.0 ** -7, atol=1e-6)
    for shape, share in ((mellum2, 1.0), (lfm2, 1.0), (ouro, 0.0), (odd, 0.0)):
        assert dict(lane.attention_counters(*shape))["attn_rotation_in_vmem"] == share
    # the Laguna lane's five layers at their own heads, windows and turned
    # channels; one layer without positions is left out of the share, and a
    # lane that turns nothing reads 0
    laguna = (8192, 128, [6, 8, 8, 8, 6], 8, [None, 512, 512, 512, None])
    assert lane.attention_counters(*laguna, [64, 128, 128, 128, 64]) == (
        ("attn_scores_in_vmem", 1.0), ("attn_rotation_in_vmem", 1.0))
    assert lane.attention_counters(*laguna, [64, 128, 0, 128, 64])[1][1] == 1.0
    assert lane.attention_counters(*mellum2, rotary=0)[1] == ("attn_rotation_in_vmem", 0.0)
