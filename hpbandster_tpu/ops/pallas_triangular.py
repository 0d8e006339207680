"""A batch of small unit lower triangular systems, inverted by products: the
chunked delta rule's ``I + N`` (``workloads/delta_rule.py``: ``N = diag(beta)
tril(A, -1)``, one ``C x C`` system a chunk and head, ``C`` 64 in the lanes).

**The form** (:func:`blocked_inverse`) is the block inverse, doubled: with
``D`` the inverse of the system's diagonal blocks of ``b`` rows and ``Y`` the
blocks just under them that make blocks of ``2 b``, ``[[X, 0], [-Z (Y X), Z]] =
D - D (Y D)``, from ``b = 1`` (where ``D`` is the identity and the step costs
no product) to the chunk: ``2 (ceil(log2 C) - 1)`` products of float32
operands and sums (``Precision.HIGHEST``), associated as substitution is
(the block under the diagonal is ``Z`` applied to ``-Y X``, the solution of
``T_22 B = -Y X``; ``(Z Y) X`` reads half as far again from float64 where the
inverse's entries are largest). Every block is a mask over the
whole matrix, so the products are ``C x C`` whatever the level and the chunk
need be no power of two. It is forward substitution's arithmetic in another
order: nothing is truncated, nothing iterated to a tolerance, and no power
of ``N`` is ever formed, so ``beta`` near 2 grows nothing that substitution
would not.

**The kernel** (:func:`inverse_and_solved`) holds a tile of systems and their
right-hand sides in VMEM, forms each inverse there and writes ``inverse`` and
``solved = inverse @ rhs`` once: the system is read once and nothing between
the products leaves the chip. Systems narrower than a tile of 128 lanes go
through the products side by side (two of 64: ``[Y1 | Y2]`` against ``[[D1,
0], [0, D2]]``, then ``[D1 | D2]`` against the two results on a diagonal),
so the matrix unit's columns are all in use. Off the chip
the callers run :func:`blocked_inverse` on the whole batch as plain
products, which is also what the kernel is tested against.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["blocked_inverse", "fits", "inverse_and_solved"]

_LANE = 128
#: the most of the chip's 128 MiB of VMEM that the kernel asks for; it asks
#: for what its shapes need (:func:`_vmem_bytes`)
_VMEM_LIMIT = 100 * 2 ** 20
#: systems a visit: 2 MB of right-hand sides 256 wide, in and out
_TILE = 16

_dot = functools.partial(
    jnp.matmul, precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _side_by_side(chunk: int) -> int:
    """How many systems of ``chunk`` rows share a tile of lanes."""
    return _LANE // chunk if _LANE % chunk == 0 else 1


def _diagonal(x, chunk: int):
    """``[[x1, 0], [0, x2], ...]`` of systems side by side ``[x1 | x2 |
    ...]`` (``[..., C, P C] -> [..., P C, P C]``); one system is itself."""
    side = x.shape[-1] // chunk
    if side == 1:
        return x
    of = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) // chunk
    return jnp.concatenate(
        [jnp.where(of == p, x, 0.0) for p in range(side)], axis=-2)


def blocked_inverse(system, chunk: int):
    """The inverses of unit lower triangular systems f32[..., C, P C], ``P``
    of them side by side (``P = 1``: f32[..., C, C]), in the same layout:
    the doubled block inverse of the module's text. What a system holds on
    and above its diagonal is not read (the diagonal is taken as 1)."""
    row = lax.broadcasted_iota(jnp.int32, system.shape[-2:], 0)
    col = lax.broadcasted_iota(jnp.int32, system.shape[-2:], 1) % chunk
    inverse = jnp.broadcast_to(
        jnp.where(row == col, 1.0, 0.0).astype(jnp.float32), system.shape)
    b = 1
    while b < chunk:
        # the blocks under the diagonal that make blocks of 2 b out of b
        joins = ((row // b) % 2 == 1) & (col // b == row // b - 1)
        under = jnp.where(joins, system, 0.0)
        if b == 1:
            inverse = inverse - under
        else:
            inverse = inverse - _dot(
                inverse, _diagonal(_dot(under, _diagonal(inverse, chunk)), chunk))
        b *= 2
    return inverse


def _vmem_bytes(chunk: int, width: int) -> int:
    """What a visit holds in VMEM: the systems and the right-hand sides of
    a tile, in and out and twice over for the pipeline, and a group's
    matrices between the products."""
    side = _side_by_side(chunk) * chunk
    lanes = lambda n: -(-n // _LANE) * _LANE
    return (4 * _TILE * chunk * (lanes(chunk) + lanes(width)) * 4
            + (8 * side * lanes(side) + 2 * side * lanes(width)) * 4)


def fits(systems: int, chunk: int, width: int) -> bool:
    """Whether the kernel takes ``systems`` systems of ``chunk`` rows against
    right-hand sides ``width`` wide: whole tiles of systems, the systems side
    by side whole tiles of lanes, the rows whole sublanes, and a visit
    within the kernel's share of VMEM."""
    side = _side_by_side(chunk)
    return (systems % _TILE == 0 and _TILE % side == 0 and chunk % 8 == 0
            and (side * chunk) % _LANE == 0
            and _vmem_bytes(chunk, width) <= _VMEM_LIMIT)


def _kernel(system_ref, rhs_ref, inverse_ref, solved_ref, *, chunk: int, side: int):
    width = rhs_ref.shape[-1]

    def group(g, _):
        first = g * side
        wide = jnp.concatenate([system_ref[first + p] for p in range(side)], axis=-1)
        inverse = blocked_inverse(wide, chunk)
        for p in range(side):
            inverse_ref[first + p] = inverse[:, p * chunk:(p + 1) * chunk]
        rhs = rhs_ref[pl.ds(first, side)].reshape(side * chunk, width)
        solved_ref[pl.ds(first, side)] = _dot(
            _diagonal(inverse, chunk), rhs).reshape(side, chunk, width)
        return _

    lax.fori_loop(0, system_ref.shape[0] // side, group, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def inverse_and_solved(system, rhs, *, interpret: bool = False):
    """``system`` f32[S, C, C] unit lower triangular, ``rhs`` f32[S, C, R]
    -> ``(inverse f32[S, C, C], solved f32[S, C, R])``, ``solved = inverse @
    rhs``: a visit takes ``_TILE`` systems, a tile of lanes of them at a
    time (the shapes are :func:`fits`'s to approve). Jitted, so that a program that calls it at several sites (a lane's
    layers, its forward pass and its step) traces and lowers the kernel once
    for them all, each site under its own scope: six sites lowered one by one
    were 4 s of every set-up of the Olmo-Hybrid cell."""
    systems, chunk, _ = system.shape
    width = rhs.shape[-1]
    side = math.gcd(_side_by_side(chunk), _TILE)
    by_tile = lambda last: pl.BlockSpec((_TILE, chunk, last), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, side=side),
        out_shape=(jax.ShapeDtypeStruct(system.shape, jnp.float32),
                   jax.ShapeDtypeStruct(rhs.shape, jnp.float32)),
        grid=(systems // _TILE,),
        in_specs=[by_tile(chunk), by_tile(width)],
        out_specs=(by_tile(chunk), by_tile(width)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=min(_VMEM_LIMIT, max(_vmem_bytes(chunk, width), 2 ** 24))),
        interpret=interpret, name="delta_inverse_and_solved",
    )(system, rhs)
