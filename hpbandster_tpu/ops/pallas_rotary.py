"""Pallas TPU kernel for the lanes' rotary turn of heads that lie side by
side (``workloads/lane.py`` ``_rotate_side_by_side``): ``x`` f32[T, heads x
d] as a projection leaves it, turned by ``cos, sin`` f32[T, d], the
rotate-half form (channel ``i`` of a head with ``i + half``), every head by
the same tables.

In plain JAX the halves change places by two turns of the whole row, each
a slice that the compiler copies out, and the tables are written out across
the heads: some six passes over ``[T, heads x d]`` where one read and one
write are owed. Here a grid step holds a block of rows and of whole heads in
VMEM and the rows' tables as ``[rows, d]``, once for all the heads of the
rows (the table's block does not move from one block of heads to the next,
so it is fetched once a block of rows): no table ``[T, heads x d]`` exists
anywhere. A head's halves change places in registers: a **group** is the
least run of whole heads that is whole tiles of 128 lanes (a head of 128;
two heads of 64), it is turned by ``half`` lanes each way
(``pltpu.roll``), and an entry takes the turn that stayed in its head
(``lane % d < half``: the one from above, else the one from below); what a
turn wraps round the group's edge is never selected. Where the whole head
is turned and is a group by itself the two turns are the one.

The arithmetic is ``_rotate``'s, an entry: ``x cos + turned sin`` in
float32, ``turned`` being ``-x[i + half]``, ``x[i - half]`` or, past the
``2 half`` channels that are turned, an entry that meets a sine of 0. The
sign is put on the sine (``(-a) s`` and ``a (-s)`` are the same float). The
result is rounded once, in the kernel, to the dtype the caller names: the
attention kernels' operand, which is the rounding they would apply first
thing.

The transposed turn (the backward pass) is the same kernel: ``dx = g cos +
turn^T(g sin)``, the product taken before the halves change places, and
zeros past the turned channels (there the forward pass selects an entry
only to meet a sine of 0; its transpose has nothing to hand back).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fits", "rotate_side_by_side"]

_LANE = 128
#: rows and lanes a grid step: ``[256, 1024]`` float32 is 1 MB, with its
#: result and the pipeline's second buffers well inside VMEM's default share
_ROWS = 256
_COLUMNS = 1024
#: rows that the kernel's body takes at a time (a loop inside the step):
#: two registers of float32 a tile of lanes, one of bfloat16
_CHUNK = 16


def _block(t: int, width: int, d: int):
    """``(rows, columns)`` of a grid step over ``[t, width]``, heads of ``d``
    side by side: a block of rows of whole chunks and the most whole groups
    (a head of a multiple of 128 lanes; as many heads of a divisor of 128 as
    fill a tile) within :data:`_COLUMNS` that divide the width. None for a
    shape the kernel does not take."""
    group = d if d % _LANE == 0 else _LANE if d > 0 and _LANE % d == 0 else 0
    rows = min(_ROWS, t)
    if not group or width <= 0 or width % group or t % rows or rows % _CHUNK:
        return None
    return rows, max(c for c in range(group, max(_COLUMNS, group) + 1, group) if width % c == 0)


def fits(t: int, width: int, d: int, half: int) -> bool:
    """Whether the kernel turns ``[t, width]``, heads of ``d`` side by side,
    by ``half``: heads that make whole tiles of lanes (a multiple of 128, or
    a divisor of it: heads of 64 in pairs), a width of whole groups, rows in
    whole blocks of whole chunks, and no more than the head turned."""
    return 0 < 2 * half <= d and _block(t, width, d) is not None


def _changed_places(y, first, half: int):
    """``y`` [rows, group] with every head's entry ``j`` taking ``j + half``
    where ``first`` (``j`` in the head's first ``half`` channels) and ``j -
    half`` elsewhere."""
    group = y.shape[1]
    below = pltpu.roll(y, half, axis=1)
    if 2 * half == group:
        return below            # a head a group, all of it turned: one turn is both
    return jnp.where(first, pltpu.roll(y, group - half, axis=1), below)


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, d: int, half: int, transposed: bool):
    group = max(d, _LANE)
    channel = lax.broadcasted_iota(jnp.int32, (_CHUNK, group), 1) % d
    first = channel < half
    across = lambda table: jnp.tile(table, (1, group // d)) if group > d else table

    def chunk(i, _):
        rows = pl.ds(pl.multiple_of(i * _CHUNK, _CHUNK), _CHUNK)
        cos, sin = across(cos_ref[rows, :]), across(sin_ref[rows, :])
        sin = jnp.where(first, -sin, sin)
        for lo in range(0, x_ref.shape[1], group):
            x = x_ref[rows, lo:lo + group].astype(jnp.float32)
            if transposed:
                turned = _changed_places(x * sin, first, half)
                if 2 * half < d:
                    turned = jnp.where(channel < 2 * half, turned, 0.0)
            else:
                turned = _changed_places(x, first, half) * sin
            o_ref[rows, lo:lo + group] = (x * cos + turned).astype(o_ref.dtype)
        return _

    lax.fori_loop(0, x_ref.shape[0] // _CHUNK, chunk, None)


@functools.partial(jax.jit, static_argnames=("half", "out", "transposed", "interpret"))
def _turn(x, cos, sin, *, half: int, out, transposed: bool, interpret: bool):
    """The kernel over ``x`` [T, heads x d] and ``cos, sin`` f32[T, d] ->
    ``out``[T, heads x d]. Jitted, so that a program that turns at several
    sites (a lane's layers, queries and keys, forward and backward) traces
    and lowers the kernel once a shape, each site under its own scope
    (``pallas_triangular.inverse_and_solved``: why)."""
    t, width = x.shape
    d = cos.shape[1]
    rows, columns = _block(t, width, d)
    table = pl.BlockSpec((rows, d), lambda i, j: (i, 0))
    block = pl.BlockSpec((rows, columns), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_kernel, d=d, half=half, transposed=transposed),
        out_shape=jax.ShapeDtypeStruct(x.shape, out),
        grid=(t // rows, width // columns),
        in_specs=[block, table, table], out_specs=block,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="rotary_turn",
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def rotate_side_by_side(x, cos, sin, half: int, operand, scope: str,
                        interpret: bool = False):
    """``x`` f32[T, heads x d] turned by ``cos, sin`` f32[T, d] (a head's
    first ``2 half`` channels; the tables hold 1 and 0 for the rest), the
    result rounded once to ``operand``: the shapes are :func:`fits`'s to
    approve. The gradient is the transposed turn of the cotangent, float32
    as ``x`` is, whatever precision the cotangent arrives in; the tables
    get none (they are made of positions, not of parameters). The device
    operations of both rules are named ``scope`` (the backward rule is
    traced where its caller's scope is no longer open)."""
    return _rotate_forward(x, cos, sin, half, operand, scope, interpret)[0]


def _rotate_forward(x, cos, sin, half, operand, scope, interpret):
    with jax.named_scope(scope):
        turned = _turn(x, cos, sin, half=half, out=operand, transposed=False,
                       interpret=interpret)
    return turned, (cos, sin)


def _rotate_backward(half, operand, scope, interpret, kept, g):
    cos, sin = kept
    with jax.named_scope(scope):
        dx = _turn(g, cos, sin, half=half, out=jnp.float32, transposed=True,
                   interpret=interpret)
    return dx, None, None


rotate_side_by_side.defvjp(_rotate_forward, _rotate_backward)
