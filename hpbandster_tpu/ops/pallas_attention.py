"""Pallas TPU kernels for the lanes' grouped-query softmax attention under
a rule of sight (``workloads/lane.py`` ``banded_attention``: causal,
optionally banded, or the block-diffusion rule over a clean and a masked
copy of the rows): a tile's scores live in VMEM and nowhere else.

One forward and one backward kernel, both over a grid of (key/value head,
block of queries). A step holds its head's keys and values whole in VMEM
(they are fetched once a head: the block index does not change from one
block of queries to the next) and walks the tiles of keys that its
queries may see, in loops inside the kernel: a tile in which the rule
shows the block nothing is never visited, and only the tiles that hold
hidden pairs beside seen ones are masked (loops of their own for them, so
that no loop's body branches).

What a block of queries walks, and what a masked tile hides, is the rule's
to say and the kernels' static argument: ``rule.tile_loops(lo, tiles,
rows)`` gives ``[(first, end, width, seen)]`` for the queries ``lo : lo +
block_q`` (``lo`` a traced number in the kernels, a Python one in
:func:`tiles_visited`), each a loop over the tiles ``first .. end - 1`` of
``width`` keys (tile ``j`` starts at key ``j x width``; a loop may take
tiles narrower than ``block_k``, whole tiles of lanes), ``seen`` None where
every pair of such a tile is seen, else ``seen(klo) -> bool[block_q,
width]`` for the tile that starts at key ``klo``. The loops need not be
contiguous nor in the keys' order (the online softmax takes any), but
between them they hold every seen pair exactly once; a row whose first
walked tile shows it nothing is sound (see ``_MASKED``), one that sees
nothing at all is not. The rules are ``lane.Causal`` (the triangle's or the
band's tiles, the diagonal's and the band's edge masked) and
``lane.BlockDiffusion`` (a clean block of queries the triangle of its
half; a masked one the clean tiles before it, the clean tile that holds its
own positions masked, then its own keys of the masked copy); nothing here
knows either by name.

Widths taken (:func:`fits`): heads of a multiple of 128 lanes, a key/value
head a step; and heads of 64 where the key/value heads pair up (an even
number of them), a PAIR a step, side by side in one tile of 128 lanes:
the step's keys and values are the pair's 128 columns of ``[T, G x 64]``
(a whole tile of lanes, which one head's 64 are not), and a query head's
rows hold its 64 lanes in the half where its key/value head lies and
zeros in the other (:func:`_rows`). ``q k^T`` over the 128 lanes is then
the head's own scores (the zeros add exactly 0 to a float32 sum), ``p v``
gives 128 lanes of which the head's half is kept at the store, and in the
backward kernel ``dv += p^T do``, ``dp = do v^T``, ``dk += ds^T q`` and
``sum(do o)`` are right as they stand because a row's other half is zero;
``dq = ds k`` is cut to its half at the store. The kernels' bodies are
the same for both widths; the products are those of a 128-wide head at
the same number of query heads, which a 64-deep contraction costs a 128 x
128 MXU anyway. Nothing else (32, 96, an odd number of heads of 64) is
taken: the lanes run the plain form there.

* rows are (query head, query) pairs: the ``R`` query heads of a key/value
  head share every tile of keys and every product (no head is repeated in
  memory, and a product has ``R x block_q`` rows however few queries);
* forward: the online softmax (running max, running sum, the output
  rescaled as the max moves), float32; it keeps the output and one
  log-sum-exp a row;
* backward: ONE kernel, five products a tile: the scores again from
  ``q``, ``k`` and the log-sum-exp, ``dv += p^T do``, ``dp = do v^T``,
  ``dk += ds^T q``, ``dq += ds k``. ``dk`` and ``dv`` of the head stay in
  VMEM across its blocks of queries (the output block does not move) and
  each tile's share is added where it lies;
* both products' operands arrive in the dtype the caller casts them to
  (the lanes: bfloat16), every accumulation and everything between the
  products is float32, ``1 / sqrt(d)`` multiplies the float32 scores:
  the plain form's sums in another order.

Layout: every array comes and goes as the projections leave it, heads
side by side (``[T, G x R x d]``, ``[T, G x d]``: on the chip an array
``[T, G, R, d]`` is tiled over its last two axes, so that even a reshape
between the two is a pass over it, and one the compiler makes itself,
under no scope); a step's block is its positions' ``R`` query heads (a
pair's ``2 x R``), stood on top of one another inside the kernel by moving
whole registers. A
row's log-sum-exp is kept across the 128 lanes, as the running max and sum
are (a column broadcasts to a tile of scores by reuse of registers).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["Tiles", "fits", "fused_banded_attention", "heads_a_step", "tiles_visited"]

_LANE = 128
#: what a masked score is set to, ``banded_attention``'s own value: finite,
#: so that a row whose first tile shows it nothing has a running max to
#: leave (a band's first tile; under the block-diffusion rule the clean tile
#: of a masked row of the first diffusion block): the first tile that shows
#: it something wipes what it summed (``exp(-1e30 - m)`` is exactly 0), and
#: in the backward kernel ``exp(s - lse)`` of a masked score is exactly 0
_MASKED = -1e30
#: the most of the chip's 128 MiB of VMEM that a kernel asks for. It asks
#: for what its shapes need (:func:`_vmem_bytes`) and no more: what a
#: kernel reserves, the compiler cannot keep in VMEM for the operations
#: around it
_VMEM_LIMIT = 100 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


class Tiles(NamedTuple):
    """Queries a block and keys a tile."""

    block_q: int
    block_k: int


def _vmem_bytes(t: int, rows: int, width: int, tiles: Tiles, operand_bytes: int) -> int:
    """What the backward kernel (the larger) holds in VMEM: a step's keys
    (a head's, or a pair's: ``width`` lanes), values and their float32
    gradients and a block's ``rows`` (queries, output, its gradient, the
    queries' gradient, the log-sum-exp), each twice over for the pipeline,
    and some eight float32 arrays of a tile's scores. The compiler's own
    count at the cells' shapes (chipless, PR 37): 32.7 MB where this gives
    46.7 (8,192 positions, 8 x 128 rows), 9.1 where this gives 17.0 (2,048
    positions, 512 rows)."""
    resident = 2 * 2 * t * width * (operand_bytes + 4)
    blocks = 2 * rows * (width * (operand_bytes + 3 * 4) + _LANE * 4)
    return resident + blocks + 8 * rows * tiles.block_k * 4


def _side_by_side(d: int) -> int:
    """Key/value heads of ``d`` that a grid step takes side by side in one
    tile of lanes: one head of whole tiles, a pair of 64; 0 for a width the
    kernels do not take."""
    return 1 if d % _LANE == 0 else 2 if 2 * d == _LANE else 0


def heads_a_step(d: int, heads_per_kv: int) -> int:
    """Query heads whose rows a grid step holds: a key/value head's, a
    pair's where heads of 64 pair up (what a block of queries is sized
    from; a width that is not taken counts as a head a step)."""
    return max(_side_by_side(d), 1) * heads_per_kv


def fits(t: int, d: int, heads_per_kv: int, kv_heads: int, tiles: Tiles,
         operand_bytes: int = 2) -> bool:
    """Whether the kernels take a sequence of ``t`` positions and
    ``kv_heads`` key/value heads of ``d``, ``heads_per_kv`` query heads
    each: heads of whole tiles of lanes (``d`` a multiple of 128) or of 64
    that pair up (an even ``kv_heads``: two stand side by side in one tile),
    whole tiles of keys and of queries, and what a step holds (a pair's
    rows and columns where heads pair up) within the kernels' share of
    VMEM."""
    beside = _side_by_side(d)
    return (beside > 0 and kv_heads % beside == 0
            and tiles.block_k % _LANE == 0 and tiles.block_q % 16 == 0
            and t % tiles.block_q == 0 and t % tiles.block_k == 0
            and _vmem_bytes(t, heads_a_step(d, heads_per_kv) * tiles.block_q, beside * d,
                            tiles, operand_bytes) <= _VMEM_LIMIT)


def tiles_visited(t: int, rule, tiles: Tiles):
    """Tiles of ``block_q x block_k`` scores that one (query head, pass)
    computes under ``rule`` over ``t`` rows: the kernels' own ranges
    (``rule.tile_loops``), in Python; a loop of narrower tiles counts by
    their width."""
    return sum(max(end - first, 0) * width
               for lo in range(0, t, tiles.block_q)
               for first, end, width, _ in rule.tile_loops(lo, tiles, t)) / tiles.block_k


def _scores(q, k, klo, scale: float, seen):
    """A tile's scores f32[R x block_q, width], the keys from ``klo``;
    under ``seen`` (the rule's, ``klo -> bool[block_q, width]``, of a tile
    that holds pairs the rule hides) the hidden ones are ``_MASKED``."""
    s = lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32) * scale
    if seen is None:
        return s
    # one block of queries' mask for every query head's rows; added, not
    # selected: ``s - 1e30`` is ``-1e30`` to the last bit
    bias = jnp.where(seen(klo), 0.0, _MASKED).astype(jnp.float32)
    return s + jnp.tile(bias, (s.shape[0] // bias.shape[0], 1))


def _across(column, width: int):
    """A column kept across the 128 lanes, as wide as ``width``."""
    return jnp.tile(column, (1, width // _LANE))


def _walk(lo, tiles: Tiles, rule, rows: int, tile):
    """``tile(keys, klo, seen)`` for every tile of keys that the queries
    ``lo : lo + block_q`` of ``rows`` may see under ``rule``, loop by loop
    in the rule's order; ``keys`` the tile's slice, ``klo`` its first key,
    ``seen`` its loop's mask (None: a tile wholly seen)."""
    for first, end, width, seen in rule.tile_loops(lo, tiles, rows):
        def one(j, _, width=width, seen=seen):
            klo = pl.multiple_of(j * width, width)
            tile(pl.ds(klo, width), klo, seen)

        lax.fori_loop(first, end, one, None)


def _rows(ref, d: int):
    """A block ``[block_q, heads x d]`` (a position's query heads side by
    side, as the projections leave them) as the products' rows ``[heads x
    block_q, width]``, head by head: whole registers moved, nothing
    shuffled. Heads of whole tiles as they are (``width = d``). Of a pair
    of key/value heads of 64 (``width`` 128; the block holds the first
    one's query heads, then the second's, two to a 128-lane slice) a query
    head's 64 lanes go to the half where its key/value head lies in the
    pair's tile, masked in place or turned by 64 lanes first, and the other
    half is zeros (the module's account says why that is enough)."""
    if d % _LANE == 0:
        return jnp.concatenate(
            [ref[:, h * d:(h + 1) * d] for h in range(ref.shape[1] // d)], axis=0)
    heads = ref.shape[1] // d
    low = lax.broadcasted_iota(jnp.int32, (ref.shape[0], _LANE), 1) < d
    rows = []
    for h in range(heads):
        half = h // (heads // 2)        # where its key/value head lies in the pair's tile
        both = ref[:, h // 2 * _LANE:(h // 2 + 1) * _LANE]
        mine = both.astype(jnp.float32)       # the chip turns registers of 32 bits only
        if h % 2 != half:
            mine = pltpu.roll(mine, d, axis=1)
        rows.append(jnp.where(low == (half == 0), mine, 0.0).astype(both.dtype))
    return jnp.concatenate(rows, axis=0)


def _store_rows(ref, rows, d: int):
    """:func:`_rows` undone, into ``ref``: of a pair's rows the half where
    the head's key/value head lies is kept, and stood where the head lies
    in the block."""
    bq = ref.shape[0]
    heads = ref.shape[1] // d
    head = lambda h: rows[h * bq:(h + 1) * bq]
    if d % _LANE == 0:
        for h in range(heads):
            ref[:, h * d:(h + 1) * d] = head(h)
        return
    low = lax.broadcasted_iota(jnp.int32, (bq, _LANE), 1) < d
    # head ``h``'s own half is ``h // (heads // 2)``; in the block it lies in half ``h % 2``
    placed = lambda h: (head(h) if h % 2 == h // (heads // 2)
                        else pltpu.roll(head(h), d, axis=1))
    for h in range(0, heads, 2):
        ref[:, h // 2 * _LANE:(h // 2 + 1) * _LANE] = jnp.where(low, placed(h), placed(h + 1))


def _forward_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                    d: int, tiles: Tiles, rule):
    lo = pl.program_id(1) * tiles.block_q
    width, scale = acc_ref.shape[-1], d ** -0.5
    q = _rows(q_ref, d)
    m_ref[...] = jnp.full_like(m_ref, _MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(keys, klo, seen):
        s = _scores(q, k_ref[keys, :], klo, scale, seen)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _across(m_next, s.shape[1]))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_next
        v = v_ref[keys, :]
        acc_ref[...] = _across(alpha, width) * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    _walk(lo, tiles, rule, k_ref.shape[0], tile)
    l = l_ref[...]
    _store_rows(o_ref, acc_ref[...] / _across(l, width), d)
    lse_ref[...] = m_ref[...] + jnp.log(l)


def _backward_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dq_acc, *,
                     d: int, tiles: Tiles, rule):
    lo = pl.program_id(1) * tiles.block_q
    scale = d ** -0.5

    @pl.when(pl.program_id(1) == 0)
    def start():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    q = _rows(q_ref, d)
    do = _rows(do_ref, d)
    # what the softmax's backward pass subtracts: sum_j p_ij dp_ij = do_i . o_i
    delta = (do * _rows(o_ref, d)).sum(axis=-1, keepdims=True)
    do = do.astype(q.dtype)
    lse = lse_ref[...]
    dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(keys, klo, seen):
        k, v = k_ref[keys, :], v_ref[keys, :]
        s = _scores(q, k, klo, scale, seen)
        p = jnp.exp(s - _across(lse, s.shape[1]))
        dv_ref[keys, :] += lax.dot_general(
            p.astype(do.dtype), do, _TN, preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        # the scores' gradient but for ``1 / sqrt(d)``, which multiplies the
        # two small products it enters and not the tile
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_ref[keys, :] += scale * lax.dot_general(
            ds, q, _TN, preferred_element_type=jnp.float32)
        dq_acc[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    _walk(lo, tiles, rule, k_ref.shape[0], tile)
    _store_rows(dq_ref, dq_acc[...] * scale, d)


def _step(heads):
    """``(steps, query heads, width)`` of ``heads = (G, R, d)``: the grid's
    steps over the key/value heads, the query heads a step holds and the
    lanes its keys take: a head of whole tiles a step, or a pair of 64."""
    g, r, d = heads
    beside = _side_by_side(d)
    return g // beside, heads_a_step(d, r), beside * d


def _specs(t: int, heads, tiles: Tiles):
    """Block specifications over the grid (step ``g``: a key/value head or
    a pair, block of queries ``i``): a block of positions with the step's
    query heads side by side (of ``[T, G x R x d]``), a block's rows of the
    lanes' width, the step's keys (values, their gradients) whole (of ``[T,
    G x d]``: a pair's columns are one tile of lanes, which a single head
    of 64 is not)."""
    _, r, width = _step(heads)
    return (pl.BlockSpec((tiles.block_q, r * heads[2]), lambda g, i: (i, g)),
            pl.BlockSpec((None, None, r * tiles.block_q, _LANE), lambda g, i: (g, i, 0, 0)),
            pl.BlockSpec((t, width), lambda g, i: (0, g)))


def _params(q, k, heads, tiles: Tiles):
    # blocks of queries in order: a step's keys stay, their ``dk`` and
    # ``dv`` are summed over them
    _, r, width = _step(heads)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=min(_VMEM_LIMIT, _vmem_bytes(
            k.shape[0], r * tiles.block_q, width, tiles, q.dtype.itemsize)))


def _forward(q, k, v, heads, rule, tiles: Tiles, interpret: bool):
    """``q [T, G x R x d]``, ``k, v [T, G x d]``, ``heads = (G, R, d)`` ->
    ``(out f32[T, G x R x d], lse f32[steps, blocks, rows, 128])``, ``rows``
    a step's query heads x ``block_q``."""
    steps, r, width = _step(heads)
    t, blocks, rows = q.shape[0], q.shape[0] // tiles.block_q, r * tiles.block_q
    positions, rows_lane, whole = _specs(t, heads, tiles)
    return pl.pallas_call(
        functools.partial(_forward_kernel, d=heads[2], tiles=tiles, rule=rule),
        out_shape=(jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct((steps, blocks, rows, _LANE), jnp.float32)),
        grid=(steps, blocks),
        in_specs=[positions, whole, whole],
        out_specs=(positions, rows_lane),
        scratch_shapes=[pltpu.VMEM((rows, _LANE), jnp.float32),
                        pltpu.VMEM((rows, _LANE), jnp.float32),
                        pltpu.VMEM((rows, width), jnp.float32)],
        compiler_params=_params(q, k, heads, tiles), interpret=interpret,
        name="banded_attention_forward",
    )(q, k, v)


def _backward(q, k, v, out, lse, dout, heads, rule, tiles: Tiles, interpret: bool):
    """-> ``(dq f32[T, G x R x d], dk, dv f32[T, G x d])``."""
    steps, r, width = _step(heads)
    t = q.shape[0]
    positions, rows_lane, whole = _specs(t, heads, tiles)
    return pl.pallas_call(
        functools.partial(_backward_kernel, d=heads[2], tiles=tiles, rule=rule),
        out_shape=(jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)),
        grid=(steps, t // tiles.block_q),
        in_specs=[positions, whole, whole, positions, rows_lane, positions],
        out_specs=(positions, whole, whole),
        scratch_shapes=[pltpu.VMEM((r * tiles.block_q, width), jnp.float32)],
        compiler_params=_params(q, k, heads, tiles), interpret=interpret,
        name="banded_attention_backward",
    )(q, k, v, out, lse, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def fused_banded_attention(q, k, v, heads, rule, tiles: Tiles, operand,
                           scope: str, interpret: bool = False):
    """``banded_attention``'s mathematics by the kernels above, the heads
    side by side: ``q`` f32[T, G x R x d] (query head ``g * R + r`` on
    key/value head ``g``), ``k, v`` f32[T, G x d], ``heads = (G, R, d)`` ->
    f32[T, G x R x d], under ``rule`` (a rule of sight, hashable: what the
    module's account asks of it); both products' operands are cast to
    ``operand``. The
    device operations of both rules are named ``scope`` (a
    ``jax.named_scope``; the backward rule is traced where its caller's
    scope is no longer open)."""
    return _attention_forward(q, k, v, heads, rule, tiles, operand, scope, interpret)[0]


def _attention_forward(q, k, v, heads, rule, tiles, operand, scope, interpret):
    with jax.named_scope(scope):
        q, k, v = (x.astype(operand) for x in (q, k, v))
        out, lse = _forward(q, k, v, heads, rule, tiles, interpret)
        return out, (q, k, v, out, lse)


def _attention_backward(heads, rule, tiles, operand, scope, interpret, kept, dout):
    with jax.named_scope(scope):
        return _backward(*kept, dout, heads, rule, tiles, interpret)


fused_banded_attention.defvjp(_attention_forward, _attention_backward)
