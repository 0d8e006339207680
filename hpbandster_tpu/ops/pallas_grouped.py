"""Pallas TPU kernels for the lanes' grouped matrix products
(``workloads/lane.py`` ``_routed``): the rows of an array sorted by group
(an expert's token-choices side by side, the groups in order) against each
group's own weights, over the whole array at once.

Two shapes of kernel, both over a grid of **visits**, one a (group, tile of
rows) pair that holds a row of the group (:func:`visits`, from the groups'
ends): a tile that two groups share is visited once for each, and a tile
past the last group's end is never visited, so the work follows the rows
that count and the grid's length is a number of the device's.

* :func:`rows_by_group`: ``out[rows of g] = f(lhs[rows of g] @ rhs[g])``
  (``rhs[g]`` read transposed for a backward product). A visit holds its
  group's weights whole in VMEM (they are fetched once a group: the block
  does not move from one tile of the group to the next), computes the
  tile's product, hands it to ``epilogue`` (what stands between two
  products, with further arrays of rows beside it: it never leaves VMEM)
  and stores the rows that are its group's; a tile's other rows are what
  the tile's earlier visits stored, zeros on its first.
* :func:`groups_by_rows`: ``out[g] = lhs[rows of g]^T @ rhs[rows of g]``,
  the transpose of the first: summed in a float32 block that stays in VMEM
  over the group's tiles and is written once, at its last. A group with no
  row is visited once and written as zeros.

Operands arrive in the dtype the caller casts them to (the lanes:
bfloat16), every sum is float32. What a row holds that is no visited
group's never reaches a product: both operands of the transposed kernel
are masked, and the first stores by a select, so whatever a diverged lane
left in such a row stays there.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["Visits", "fits", "groups_by_rows", "rows_by_group", "visits"]

_LANE = 128
#: the most of the chip's 128 MiB of VMEM that a kernel asks for; it asks
#: for what its shapes need (:func:`_vmem_bytes`)
_VMEM_LIMIT = 100 * 2 ** 20

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


class Visits(NamedTuple):
    """The grid of both kernels: visit ``i`` takes tile ``tile[i]`` of
    rows for group ``group[i]``, whose rows are ``starts[g] : ends[g]``;
    ``count`` visits, the others (the arrays are as long as the most there
    can be) are never made."""

    group: jax.Array
    tile: jax.Array
    starts: jax.Array
    ends: jax.Array
    count: jax.Array


def visits(ends, m: int, rows: int) -> Visits:
    """The visits of ``m`` sorted rows in tiles of ``rows``, the groups'
    cumulative ``ends`` i32[G] (group ``g`` holds ``ends[g - 1] : ends[g]``;
    the rows past ``ends[-1]`` are no group's). Groups in order, a group's
    tiles in order, so a tile's visits follow one another and so do a
    group's; a group with no row gets one visit, of the tile where it would
    start. By comparisons and sums alone: nothing here sorts or scatters."""
    g, tiles = ends.shape[0], m // rows
    ends = ends.astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    first = jnp.minimum(starts // rows, tiles - 1)
    last = jnp.where(ends > starts, -(-ends // rows), first + 1)
    each = last - first
    # the visits before each group's: a sum over the groups before it
    before = (each[None, :] * (jnp.arange(g)[None, :] < jnp.arange(g)[:, None])).sum(1)
    i = jnp.arange(tiles + g, dtype=jnp.int32)
    group = jnp.minimum((i[:, None] >= (before + each)[None, :]).sum(1, dtype=jnp.int32), g - 1)
    tile = jnp.minimum(first[group] + i - before[group], tiles - 1)
    return Visits(group, tile, starts, ends, each.sum())


def _vmem_bytes(rows: int, k: int, n: int, operand_bytes: int) -> int:
    """What the larger kernel of a product ``[rows, k] x [k, n]`` holds in
    VMEM: the transposed one's float32 sum and its output block twice over
    for the pipeline, both kernels' tiles of rows twice over, and the
    product before it is stored."""
    return (3 * k * n * 4 + 2 * k * n * operand_bytes
            + 2 * rows * (k + n) * (operand_bytes + 4) + rows * n * 4)


def fits(m: int, widths: Sequence[Tuple[int, int]], rows: int,
         operand_bytes: int = 2) -> bool:
    """Whether the kernels take ``m`` sorted rows in tiles of ``rows`` and
    products between the ``widths`` (``(k, n)`` pairs): whole tiles of whole
    lanes, and a group's weights and their gradient's sum within the
    kernels' share of VMEM."""
    return (rows % 16 == 0 and m % rows == 0
            and all(k % _LANE == 0 and n % _LANE == 0
                    and _vmem_bytes(rows, k, n, operand_bytes) <= _VMEM_LIMIT
                    for k, n in widths))


def _by_tile(rows: int, width: int):
    """The block of visit ``i``'s tile of an array ``[M, width]``."""
    return pl.BlockSpec((rows, width), lambda i, group, tile, *_: (tile[i], 0))


def _own(i, group_ref, tile_ref, starts_ref, ends_ref, shape, rows: int):
    """Which rows of visit ``i``'s tile are its group's, as wide as
    ``shape``."""
    g = group_ref[i]
    row = tile_ref[i] * rows + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= starts_ref[g]) & (row < ends_ref[g])


def _rows_kernel(group_ref, tile_ref, starts_ref, ends_ref, lhs_ref, rhs_ref, *refs,
                 rows: int, transpose_rhs: bool, epilogue, n_beside: int):
    beside, outs = refs[:n_beside], refs[n_beside:]
    i = pl.program_id(0)
    acc = lax.dot_general(lhs_ref[...], rhs_ref[...], _NT if transpose_rhs else _NN,
                          preferred_element_type=jnp.float32)
    results = (acc,) if epilogue is None else epilogue(acc, *[ref[...] for ref in beside])
    first = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != tile_ref[i])
    for out_ref, result in zip(outs, results):
        own = _own(i, group_ref, tile_ref, starts_ref, ends_ref, out_ref.shape, rows)
        result = result.astype(out_ref.dtype)

        @pl.when(first)
        def start(out_ref=out_ref, own=own, result=result):
            out_ref[...] = jnp.where(own, result, jnp.zeros_like(result))

        @pl.when(jnp.logical_not(first))
        def add(out_ref=out_ref, own=own, result=result):
            out_ref[...] = jnp.where(own, result, out_ref[...])


def rows_by_group(lhs, rhs, at: Visits, rows: int, out: Sequence[Tuple[int, jnp.dtype]], *,
                  transpose_rhs: bool = False, epilogue=None, beside=(),
                  interpret: bool = False):
    """``lhs [M, K]`` sorted rows in tiles of ``rows``, ``rhs [G, K, N]``
    (``[G, N, K]`` with ``transpose_rhs``) -> one array ``[M, width]`` of ``dtype`` for each
    ``(width, dtype)`` of ``out``: ``epilogue(lhs[tile] @ rhs[g] f32[rows,
    N], *tiles of beside) -> arrays [rows, width]`` (none: the product
    itself), the rows of a group from its weights. ``beside``: arrays ``[M,
    c]`` that the epilogue reads a tile of. Rows of a visited tile that no
    group holds are zeros; the tiles that are not visited are as the device
    left them."""
    m, k = lhs.shape
    by_tile = functools.partial(_by_tile, rows)
    weights = pl.BlockSpec((None,) + rhs.shape[1:], lambda i, group, *_: (group[i], 0, 0))
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return pl.pallas_call(
        functools.partial(_rows_kernel, rows=rows, transpose_rhs=transpose_rhs,
                          epilogue=epilogue, n_beside=len(beside)),
        out_shape=tuple(jax.ShapeDtypeStruct((m, width), dtype) for width, dtype in out),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(at.count,),
            in_specs=[by_tile(k), weights] + [by_tile(b.shape[1]) for b in beside],
            out_specs=tuple(by_tile(width) for width, _ in out)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_VMEM_LIMIT, _vmem_bytes(rows, k, n, lhs.dtype.itemsize))),
        interpret=interpret, name="grouped_rows_by_group",
    )(*at[:4], lhs, rhs, *beside)


def _groups_kernel(group_ref, tile_ref, starts_ref, ends_ref, lhs_ref, rhs_ref, out_ref,
                   acc_ref, *, rows: int):
    i = pl.program_id(0)
    g = group_ref[i]

    @pl.when((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g))
    def start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def owned(ref):
        own = _own(i, group_ref, tile_ref, starts_ref, ends_ref, ref.shape, rows)
        return jnp.where(own, ref[...], jnp.zeros_like(ref))

    acc_ref[...] += lax.dot_general(
        owned(lhs_ref), owned(rhs_ref), _TN, preferred_element_type=jnp.float32)

    @pl.when((i == pl.num_programs(0) - 1)
             | (group_ref[jnp.minimum(i + 1, pl.num_programs(0) - 1)] != g))
    def store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def groups_by_rows(lhs, rhs, at: Visits, rows: int, dtype, *, interpret: bool = False):
    """``lhs [M, K]``, ``rhs [M, N]`` sorted rows -> ``out [G, K, N]`` of
    ``dtype``: ``out[g] = lhs[rows of g]^T @ rhs[rows of g]``, a float32 sum
    over the group's tiles that is written once."""
    k, n = lhs.shape[1], rhs.shape[1]
    groups = at.starts.shape[0]
    return pl.pallas_call(
        functools.partial(_groups_kernel, rows=rows),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(at.count,),
            in_specs=[_by_tile(rows, k), _by_tile(rows, n)],
            out_specs=pl.BlockSpec((None, k, n), lambda i, group, *_: (group[i], 0, 0)),
            scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_VMEM_LIMIT, _vmem_bytes(rows, k, n, lhs.dtype.itemsize))),
        interpret=interpret, name="grouped_groups_by_rows",
    )(*at[:4], lhs, rhs)
