"""What the lanes of published blocks share (``kimi_linear.py``,
``mellum2.py``, ``ouro.py``, ``lfm2.py``, ``sdar.py``, ``olmo_hybrid.py``,
``laguna.py``): a lane is one chip's share of a model of layers, trained
from the configuration's key by momentum SGD, one sequence a step.

Here live the search space and its decoding, the rule for a matrix
product's operands, the norm and the SwiGLU, the draw of a leaf, the
synthetic tokens, embedding and head, rotary positions and **the one
softmax attention** (:func:`banded_attention`, under :func:`attention_mixer`;
what a row sees is its rule of sight: :class:`Causal`, with or without a
window, or :class:`BlockDiffusion`; a layer's own count of query heads, a
gate a head and the part of a head that is turned are the layer's leaves'
and arguments' to say, so a lane's attention layers need not be of one
shape),
the gated short convolution (:func:`short_conv_mixer`), **the one expert layer** (:func:`moe_held_experts`: what differs between
routers is stated as :class:`ExpertLayer`, a bias and a shared expert by
their leaves) and **the one lane trainer** (:func:`make_lane_eval_fn`: a
model hands it its init, its **visits** (which leaf each step of a pass
takes through which function: a plain stack visits every layer once, a
looped model the same layers several times over) and its **exits**
(:class:`Exits`: where a pass's state is read, the loss that is trained and
the loss that is reported, and where a sequence is a record and not a row of
ids, which ids a pass starts from), and what it counts (:class:`Counted`)). A
model's own file keeps its mixers, its configuration and its footprint.
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hpbandster_tpu.ops import pallas_attention, pallas_grouped, pallas_rotary
from hpbandster_tpu.ops.fused import LaneFacts
from hpbandster_tpu.ops.pallas_kde import pallas_available
from hpbandster_tpu.space import ConfigurationSpace, UniformFloatHyperparameter

__all__ = [
    "BlockDiffusion",
    "Causal",
    "Counted",
    "Exits",
    "ExpertLayer",
    "Init",
    "LANE_COUNTERS",
    "MOE_COUNTERS",
    "Visit",
    "attention_alive_bytes",
    "attention_counters",
    "attention_key_blocks",
    "attention_mixer",
    "banded_attention",
    "decode_lane_hparams",
    "expert_counters",
    "expert_layer_counters",
    "head_exit",
    "lane_space",
    "make_lane_eval_fn",
    "make_token_dataset",
    "moe_held_experts",
    "once_through",
    "short_conv_mixer",
]

#: what a lane with expert layers counts on the device beside its loss
#: (:func:`expert_counters`), over the expert layers of its held-out
#: passes: the share of token-choices that fell on held experts (``held /
#: outputs`` if routing is even), the fullest held expert's load over the
#: mean held load, and the rows that the grouped products computed over the
#: held choices (a visited tile pays for all its rows: 1 is no padding)
LANE_COUNTERS = (
    "moe_held_choice_share", "moe_load_max_over_mean", "moe_rows_computed_over_held")


def lane_space(seed=None) -> ConfigurationSpace:
    """lr (log), momentum, weight decay (log), init scale (log): the
    ``mlp_space`` axes and ranges."""
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameter(UniformFloatHyperparameter("lr", 1e-4, 1.0, log=True))
    cs.add_hyperparameter(UniformFloatHyperparameter("momentum", 0.0, 0.99))
    cs.add_hyperparameter(
        UniformFloatHyperparameter("weight_decay", 1e-7, 1e-2, log=True)
    )
    cs.add_hyperparameter(
        UniformFloatHyperparameter("init_scale", 0.1, 10.0, log=True)
    )
    return cs


def decode_lane_hparams(vec: jax.Array):
    """Unit-cube vector -> (lr, momentum, weight_decay, init_scale)."""
    lr = 10.0 ** (-4.0 + 4.0 * vec[0])
    momentum = 0.99 * vec[1]
    wd = 10.0 ** (-7.0 + 5.0 * vec[2])
    init_scale = 10.0 ** (-1.0 + 2.0 * vec[3])
    return lr, momentum, wd, init_scale


# ----------------------------------------------------------------- products
#: what every matrix product's operands are cast to; the accumulation is
#: float32 (``workloads/transformer.py``'s ``_mm``). The tests set float32
#: here to hold the equations to the reference without rounding in the way.
_OPERAND = jnp.bfloat16


#: the few products whose operands stay float32 (a router's, whose top k
#: is a discrete choice, and KDA's blocks under the diagonal, which feed a
#: triangular solve): three bfloat16 passes, 2^-16 of a product. The six
#: passes of ``HIGHEST`` take the chip's compiler four seconds a product
#: and there are some sixty of them in a lane.
_FLOAT32 = jax.lax.Precision.HIGH


def _mm(a, b):
    return jnp.matmul(
        a.astype(_OPERAND), b.astype(_OPERAND), preferred_element_type=jnp.float32)


def _einsum(spec, a, b):
    return jnp.einsum(
        spec, a.astype(_OPERAND), b.astype(_OPERAND),
        preferred_element_type=jnp.float32)


def _mm_beside(x, *weights):
    """``x @ w`` for several ``w`` as ONE product, the weights side by
    side, and the columns handed back apart: the same sums, and one
    product for the compiler (half a second each on the chip's) and for
    the chip in place of several."""
    out = _mm(x, jnp.concatenate([w.astype(_OPERAND) for w in weights], axis=1))
    return jnp.split(out, np.cumsum([w.shape[1] for w in weights])[:-1], axis=1)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(x, w_gate, w_up, w_down):
    gate, up = _mm_beside(x, w_gate, w_up)
    return _mm(jax.nn.silu(gate) * up, w_down)


# ---------------------------------------------------- positions, attention
class Yarn(NamedTuple):
    """YaRN's stretch of a head's frequencies, as a ``config.json``'s
    ``rope_parameters`` has it."""

    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


def yarn_correction_range(width: int, theta: float, yarn: Yarn):
    """``(low, high)`` of YaRN's ramp over ``width`` channels: the channel
    at which a turn count ``r`` over the original context is reached is
    ``width ln(L / (2 pi r)) / (2 ln theta)``; ``beta_fast``'s floor and
    ``beta_slow``'s ceiling."""
    def channel(turns):
        return (width * math.log(yarn.original_max_position / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(channel(yarn.beta_fast)), 0)
    high = min(math.ceil(channel(yarn.beta_slow)), width - 1)
    return low, (high if high != low else high + 0.001)


def rotary_inv_freq(width: int, theta: float, yarn: Optional[Yarn] = None):
    """``(inv_freq f64[width / 2], factor)`` of the ``width`` channels of a
    head that a layer turns. Plain RoPE: ``theta^(-2i / width)`` and 1.
    YaRN: channels that turn more than ``beta_fast`` times over the original
    context keep their frequency, those that turn less than ``beta_slow``
    times have it divided by ``factor``, a linear ramp between
    (:func:`yarn_correction_range`); cos and sin both carry the attention
    factor."""
    plain = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    if yarn is None:
        return plain, 1.0
    low, high = yarn_correction_range(width, theta, yarn)
    ramp = np.clip((np.arange(width // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) * plain + ramp * plain / yarn.factor, yarn.attention_factor


def _rotary_tables(inv_freq, factor, positions, head_dim: Optional[int] = None):
    """``(cos, sin)`` f32[T, head_dim] from a head's ``inv_freq``
    f64[rotary / 2]: channel ``i`` turns with ``i + rotary / 2`` (the
    rotate-half form), angles in float32, both tables times ``factor``.
    ``rotary`` is the whole head unless ``head_dim`` says the head is wider:
    the channels from ``rotary`` on are not turned, and their columns are 1
    and 0, without ``factor`` (:func:`_rotate` is told ``rotary`` too).
    ``positions``: a count ``T`` (row ``i`` stands at position ``i``) or
    the rows' own positions f32[T] (:meth:`BlockDiffusion.positions`: two
    rows a position)."""
    if isinstance(positions, int):
        positions = jnp.arange(positions, dtype=jnp.float32)
    angle = (positions[:, None]
             * jnp.asarray(inv_freq, jnp.float32)[None, :])
    angle = jnp.concatenate([angle, angle], axis=-1)
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    if head_dim is None or head_dim == angle.shape[1]:
        return cos, sin
    rest = (angle.shape[0], head_dim - angle.shape[1])
    return (jnp.concatenate([cos, jnp.ones(rest, jnp.float32)], axis=1),
            jnp.concatenate([sin, jnp.zeros(rest, jnp.float32)], axis=1))


def _rotate(x, cos, sin, rotary: Optional[int] = None):
    """``x`` f32[T, ..., d] turned by its position's angles: all of a head,
    or its first ``rotary`` channels (channel ``i`` with ``i + rotary / 2``;
    the tables hold 1 and 0 for the rest, which stay as they are)."""
    d = x.shape[-1]
    half = (rotary or d) // 2
    turned = jnp.concatenate(
        [-x[..., half:2 * half], x[..., :half]]
        + ([jnp.zeros_like(x[..., 2 * half:])] if 2 * half < d else []), axis=-1)
    lift = (slice(None),) + (None,) * (x.ndim - 2)
    return x * cos[lift] + turned * sin[lift]


def _turn_in_vmem(t: int, width: int, d: int, rotary: Optional[int] = None) -> bool:
    """Whether the turn of ``[t, width]``, heads of ``d`` side by side, is
    ``ops/pallas_rotary.py``'s kernel: where Mosaic compiles it, on a TPU
    backend, and the shapes are whole blocks of it (heads of whole tiles of
    lanes, or of 64 in pairs). The backend and the shapes decide, as they do
    for the scores (:func:`_kernel_tiles`)."""
    return pallas_available() and pallas_rotary.fits(t, width, d, (rotary or d) // 2)


def _rotate_side_by_side(x, cos, sin, rotary: Optional[int] = None, *, scope: str):
    """:func:`_rotate` for ``x`` f32[T, heads x d], the heads side by side
    as a projection leaves them: the same products and sums an entry, with
    no array of another shape between. Where :func:`_turn_in_vmem` says so,
    one kernel (``ops/pallas_rotary.py``): a head's halves change places in
    VMEM, the tables meet each head there as ``[rows, d]``, and the result
    is rounded there, once, to the products' operand type, which is what the
    fused attention kernels would do to it first thing; its device
    operations, the backward rule's too, are named ``scope`` (the caller's
    part). Elsewhere the plain form, float32, which the kernel is tested
    against: the halves of a head's ``rotary`` channels change places by two
    turns of the whole row, each entry taking the one that stayed in its
    head (what a channel that is not turned takes meets a sine of 0), and
    the tables are tiled across the heads."""
    d = cos.shape[1]
    half = (rotary or d) // 2
    if _turn_in_vmem(x.shape[0], x.shape[1], d, rotary):
        return pallas_rotary.rotate_side_by_side(x, cos, sin, half, _OPERAND, scope)
    heads = x.shape[1] // d
    first_half = jnp.arange(x.shape[1]) % d < half
    turned = jnp.where(first_half, -jnp.roll(x, -half, axis=1),
                       jnp.roll(x, half, axis=1))
    return x * jnp.tile(cos, (1, heads)) + turned * jnp.tile(sin, (1, heads))


#: how many scores (a block's queries x their heads x its keys, of several
#: key/value heads together where they fit) are alive at once. Measured on
#: the chip at the Mellum2 lane's published size (PR 32; a layer's mixer,
#: forward and backward): a window layer, whose block is 2^24 scores, takes
#: two key/value heads at a time (0.0400 s and 1,065 device events, against
#: 0.0422 s and 2,564 one at a time); a full layer, whose widest block alone
#: is 2^26, one (0.073 s; two at a time 0.601 s: a batch of wide score
#: matrices leaves the softmax's fast path)
_SCORES_AT_ONCE = 2 ** 25

#: the fused kernels' tiles: rows of a product (a block's queries x the
#: query heads of a key/value head) and keys a tile; a block of queries is
#: no wider than a tile of keys (the diagonal then crosses one tile a
#: block). Read on the chip at both cells' sizes (PR 37; one layer's scores
#: alone, forward / forward and backward, ms; the plain form 7.13 / 23.9
#: for a window layer, 19.5 / 60.6 for the full one, 0.518 / 1.479 at the
#: Ouro lane's 2,048 keys and 16 heads). 8 query heads x 128 queries
#: against 512 keys: 2.38 / 6.19 and 4.89 / 13.85; x 256 queries: 2.39 /
#: 6.14 and 4.99 / 13.80; x 512: 5.04 / 13.82 in the full layer; 128
#: queries against 256 keys 2.28 / 6.09 in a window layer, but in the full
#: one 256 x 256 read 10.6 / 25.3 where 256 x 512 read 7.80 / 19.9, and 128
#: x 1,024 8.62 / 21.9 where 128 x 512 read 7.78 / 20.2 (those four with the
#: masked tiles still under a ``cond``). One query head a key/value head at
#: 2,048 keys: 512 queries against 512 keys 0.400 / 1.028, 256 queries
#: 0.447 / 1.089, 1,024 queries 0.612 / 1.434 where 512 read 0.552 / 1.295
#: (``cond``). One size serves every shape the kernels take. **A group of 6
#: query heads (PR 50, the Laguna lane's full layers: 48 heads on 8, 8,192
#: keys; one layer's scores alone, forward / forward and backward, ms, on the
#: chip)**: 128 queries x 6 heads = 768 rows against 512 keys 6.68 / 19.16
#: (the rule's choice: the most queries, a power of two, within the rows);
#: 64 queries 6.71 / 22.04; 256 queries (1,536 rows) 6.73 / 18.87; 128
#: against 256 keys 7.92 / 23.26, against 1,024 keys 7.35 / 20.19; the plain
#: form 49.8 / 63.4 (six heads' scores of a block are a batch off the
#: softmax's fast path). The same layer at 8 heads a group (64 heads) 8.85 /
#: 25.21, so a group of 6 costs its 3 / 4 of the rows and no more. That
#: lane's window layers (64 heads, a window of 512): 128 x 512 2.98 / 7.41,
#: 256 x 512 2.92 / 7.26, 64 x 512 2.99 / 7.88, **128 x 256 2.64 / 6.78** (a
#: band half as wide as the Mellum2 lane's would rather walk narrower tiles:
#: not taken, one size serves), 128 x 1,024 3.93 / 10.08, the plain form
#: 14.3 / 33.2
_KERNEL_ROWS = 1024
_KERNEL_KEYS = 512

#: up to this many keys the plain form runs on the chip too: a block's
#: scores then stay on its softmax's fast path (PR 34: 2,048 keys a block of
#: 512 queries), and the lane as a whole read faster with it. At the Ouro
#: lane's size (2,048 keys, 16 heads of one query head each, 32 visits a
#: pass; PR 37, ``ouro-sgd.bohb-1x9``, two untraced runs and a traced one a
#: side): 10.6706 s a sweep in plain JAX, 11.0078 s with the kernels
#: (``lane.gqa`` 2.94 -> 3.33 s a sweep, every other part the same), though
#: a layer's scores alone read 0.400 / 1.028 ms with the kernels against
#: 0.518 / 1.479 ms (forward / forward and backward). One layer forward and
#: backward at that size: the mixer alone 2.804 ms plain, 2.588 ms with the
#: kernels; the mixer, its norms and the SwiGLU together 6.009 ms plain,
#: 6.266 ms with them: the kernels' calls cost the products around them
#: more than they save (why was not read)
_PLAIN_KEYS = 2048


class Causal(NamedTuple):
    """The rule of sight of a causal model: a row is a position, and
    position ``i`` sees ``j <= i`` and, with a window, ``i - j < window``.
    Where a rule is asked for, a bare number or ``None`` is this rule with
    that window.

    A rule of sight says, of ``rows`` rows: their ``positions`` (for the
    rotary tables), the pairs ``seen(at, key, rows)`` on arrays of row
    numbers, the plain form's ``spans(rows, block)`` (the runs of keys a
    block of queries goes against) and, for the fused kernels,
    ``whole_tiles(rows, tiles)`` (whether its rows fall into whole tiles)
    and ``tile_loops(lo, tiles, rows)`` (the tiles a block of queries walks
    and their masks: ``ops/pallas_attention.py`` says what they hold)."""

    window: Optional[int] = None

    def positions(self, rows: int):
        return rows

    def seen(self, at, key, rows: int):
        seen = key <= at
        if self.window is not None:
            seen = seen & (at - key < self.window)
        return seen

    def spans(self, rows: int, block: int):
        first = lambda lo: (0 if self.window is None
                            else max(0, lo - self.window + 1) // block * block)
        return [(lo, min(lo + block, rows), ((first(lo), min(lo + block, rows)),))
                for lo in range(0, rows, block)]

    def whole_tiles(self, rows: int, tiles) -> bool:
        return True

    def tile_loops(self, lo, tiles, rows: int):
        """The band's loops: the tiles its lower edge crosses, masked; those
        wholly inside; those the diagonal crosses, masked (a narrow window's
        tile may be crossed by both, and is walked once)."""
        bq, bk = tiles
        up, down = (jnp.maximum, jnp.minimum) if isinstance(lo, jax.Array) else (max, min)
        end = (lo + bq - 1) // bk + 1
        diagonal = (lo + 1) // bk

        def band(klo):
            ahead = (lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                     - klo - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
            seen = ahead >= 0
            if self.window is not None:
                seen = seen & (ahead < self.window)
            return seen

        if self.window is None:
            return [(0, diagonal, bk, None), (diagonal, end, bk, band)]
        first = up(lo - self.window + 1, 0) // bk
        clear = up(lo + bq - self.window + bk - 1, 0) // bk
        return [(first, down(clear, end), bk, band), (clear, diagonal, bk, None),
                (up(diagonal, clear), end, bk, band)]


class BlockDiffusion(NamedTuple):
    """The rule of sight of training by masked diffusion over blocks: a
    sequence of ``S`` positions in blocks of ``block_length`` goes through
    the layers as ``2 S`` rows, the clean copy (rows ``0 .. S - 1``) and
    then the masked one (rows ``S .. 2 S - 1``), row ``i`` and row ``S + i``
    both at position ``i``. With ``B(i) = i div block_length``:

    * clean query ``i`` sees clean key ``j`` iff ``B(j) <= B(i)`` (its whole
      own block, both ways inside it, and every earlier block) and no masked
      row;
    * masked query ``i`` sees clean key ``j`` iff ``B(j) < B(i)``, and masked
      key ``j`` iff ``B(j) = B(i)``.

    Not causal: about a quarter of the ``(2 S)^2`` square is seen (``S^2 +
    4 S`` pairs a head at blocks of 4, of ``4 S^2``)."""

    block_length: int

    def positions(self, rows: int):
        return (jnp.arange(rows) % (rows // 2)).astype(jnp.float32)

    def seen(self, at, key, rows: int):
        half, length = rows // 2, self.block_length
        masked_query, masked_key = at >= half, key >= half
        own, its = (at % half) // length, (key % half) // length
        return jnp.where(masked_query,
                         jnp.where(masked_key, its == own, its < own),
                         ~masked_key & (its <= own))

    def spans(self, rows: int, block: int):
        half = rows // 2
        if half % block or block % self.block_length:
            raise ValueError("a block of queries is whole diffusion blocks, a copy whole blocks")
        clean = [(lo, lo + block, ((0, lo + block),)) for lo in range(0, half, block)]

        def masked(lo, hi):
            # the clean keys up to its own block's (of the diagonal block it
            # sees the diffusion blocks before each query's: none where the
            # block is one diffusion block), then itself
            clean_to = hi if block > self.block_length else lo
            own = (half + lo, half + hi)
            return own + ((((0, clean_to), own) if clean_to else (own,)),)

        return clean + [masked(lo, hi) for lo, hi, _ in clean]

    def whole_tiles(self, rows: int, tiles) -> bool:
        bq, bk = tiles
        half = rows // 2
        return (rows % 2 == 0 and half % bq == 0 and half % bk == 0
                and bq % self.block_length == 0)

    def tile_loops(self, lo, tiles, rows: int):
        """A clean block of queries walks the causal triangle's tiles (its
        diagonal's masked: a query sees its whole diffusion block); a masked
        one the clean tiles wholly before it, the clean tile(s) that hold
        its own positions, masked (a query sees the diffusion blocks before
        its own), then its own keys in the masked copy, masked, as a tile no
        wider than the block where that is whole tiles of lanes."""
        bq, bk = tiles
        half, length = rows // 2, self.block_length
        masked = (lo >= half) * 1            # 1: a block of the masked copy
        at = lo - masked * half              # the block's first position
        reach = (1 - masked) * length        # of its own diffusion block's clean keys
        # the block's own keys as a tile of its own width (whole tiles of 128
        # lanes), not of ``bk``: the SDAR layer's scores alone read 3.13 / 8.75
        # ms so (forward / forward and backward) and 3.27 / 9.09 ms with the
        # own keys a whole tile of 512 (PR 43, on the chip)
        own = bq if bq < bk and bq % 128 == 0 else bk
        start = (lambda x: x & -length) if length & (length - 1) == 0 else (
            lambda x: x - jax.lax.rem(x, length))     # of a position's diffusion block
        iota = lambda width, axis: jax.lax.broadcasted_iota(jnp.int32, (bq, width), axis)

        def before(klo):
            return klo + iota(bk, 1) < start(at + iota(bk, 0)) + reach

        def itself(klo):
            return start(klo - half + iota(own, 1)) == start(at + iota(own, 0))

        clear = (at + reach) // bk           # the tiles before it that every query sees whole
        first = (half + at) // own
        return [(0, clear, bk, None),
                (clear, (at + bq - 1 + bk - masked * length) // bk, bk, before),
                (first, first + masked * ((half + at + bq - 1) // own + 1 - first), own, itself)]


def _rule(sight):
    """A rule of sight as its class: a bare window (or ``None``) is causal."""
    return sight if isinstance(sight, (Causal, BlockDiffusion)) else Causal(sight)


def _attention_spans(t: int, sight, block: int):
    """``[(lo, hi, runs)]``: the queries of rows ``lo:hi`` go against the
    keys of ``runs``, ``((klo, khi), ...)``, in that order and no others.
    Causal: one run ``klo:hi``, ``klo`` the start of the block of keys that
    holds the first position query ``lo`` may see; a masked block of
    :class:`BlockDiffusion` two, clean keys and its own."""
    return _rule(sight).spans(t, block)


def _kernel_tiles(t: int, d: int, heads_per_kv: int, kv_heads: int, sight=None):
    """The fused kernels' tiles for ``t`` rows and ``kv_heads`` key/value
    heads of ``d`` under the rule of sight ``sight``, or None where the
    plain form runs: the kernels (``ops/pallas_attention.py``: a tile's
    scores never leave VMEM) where Mosaic compiles them, on a TPU backend,
    the keys are more than the plain form is quick at (:data:`_PLAIN_KEYS`),
    the shapes fit the kernels' tiles (heads of whole tiles of lanes, or of
    64 in pairs) and the rule's rows are whole tiles (``whole_tiles``: any
    under :class:`Causal`; under :class:`BlockDiffusion` each copy whole
    tiles of keys and of queries, a block of queries whole diffusion
    blocks); off the chip the plain form (:func:`banded_attention`), which
    is also what the kernels are tested against. The backend and the shapes
    decide, under either rule alike. A block of queries is sized from the
    rows a step really holds (a pair's query heads where heads of 64 pair
    up): the most queries, a power of two, whose rows are within
    :data:`_KERNEL_ROWS` (a group of 8 query heads 128 queries and 1,024
    rows; of 6, 128 and 768; of 3, 256 and 768)."""
    if not pallas_available() or t <= _PLAIN_KEYS:
        return None
    keys = min(t, _KERNEL_KEYS)
    heads = pallas_attention.heads_a_step(d, heads_per_kv)
    queries = 2 ** int(math.log2(max(_KERNEL_ROWS // heads, 16)))
    tiles = pallas_attention.Tiles(min(keys, queries), keys)
    if not (_rule(sight).whole_tiles(t, tiles) and pallas_attention.fits(
            t, d, heads_per_kv, kv_heads, tiles, np.dtype(_OPERAND).itemsize)):
        return None
    return tiles


def _a_layer(value, layers: int):
    """``value`` for each of ``layers`` layers: a list is one a layer
    already, anything else (a rule of sight and the kernels' tiles are
    tuples) is every layer's."""
    if isinstance(value, list):
        if len(value) != layers:
            raise ValueError("one entry a layer: %d for %d layers" % (len(value), layers))
        return value
    return [value] * layers


def attention_key_blocks(t: int, sights, block: int, tiles=None, heads=None):
    """``(computed, square)``: blocks of ``block x block`` scores that
    :func:`banded_attention` computes over layers of the given rules of
    sight (a window or ``None`` each: causal; or a :class:`BlockDiffusion`,
    ``t`` its ``2 S`` rows), and those of their full squares; with the
    fused kernels' ``tiles`` (:func:`_kernel_tiles`; a list: each layer's
    own, None where a layer takes the plain form), tiles of ``block_q x
    block_k`` that the kernels walk under each rule (a narrower tile counts
    by its width: a masked block's own keys under :class:`BlockDiffusion`).
    A count is one query head's; ``heads`` (a number a layer) counts each
    layer as many times: a lane whose layers differ in their heads."""
    sights = list(sights)
    weights = _a_layer(1 if heads is None else list(heads), len(sights))
    computed = square = 0
    for sight, tile, weight in zip(sights, _a_layer(tiles, len(sights)), weights):
        if tile is not None:
            computed += weight * pallas_attention.tiles_visited(t, _rule(sight), tile)
            square += weight * (t // tile.block_q) * (t // tile.block_k)
        else:
            per_side = -(-t // block)
            computed += weight * sum(
                -(-(khi - klo) // block)
                for _, _, runs in _attention_spans(t, sight, block) for klo, khi in runs)
            square += weight * per_side * per_side
    return computed, square


def attention_counters(t: int, d: int, heads_per_kv, kv_heads: int, sight=None,
                       rotary=None):
    """The static facts of how a lane's attention is computed, beside its
    counted ones (``make_lane_eval_fn(static_counters=...)``):
    ``attn_scores_in_vmem``, the share of its attention layers whose scores
    stay in VMEM (the fused kernels), and ``attn_rotation_in_vmem``, the
    share of its attention layers that turn their heads whose turn, of the
    queries and of the keys, is ``ops/pallas_rotary.py``'s kernel
    (:func:`_rotate_side_by_side` on the fused kernels' path; 0 for a lane
    that turns nothing). ``heads_per_kv``, ``sight`` and ``rotary`` (the
    channels of a head that a layer turns: None the whole head, 0 a layer
    without positions) are every layer's, or a list each of one entry a
    layer where the layers differ. A lane whose layers are of one shape
    reads 1 or 0 (1 and 1 on the chip at the published sizes of the Mellum2,
    LFM2 and SDAR lanes, under :class:`Causal` and :class:`BlockDiffusion`
    alike; 0 and 0 on a CPU, at the Ouro lane's 2,048 keys and where a shape
    does not fit the kernels' tiles); the Laguna lane's five layers, 48 and
    64 heads, each answer for themselves."""
    layers = max([len(x) for x in (heads_per_kv, sight, rotary) if isinstance(x, list)] or [1])
    shapes = list(zip(_a_layer(heads_per_kv, layers), _a_layer(sight, layers),
                      _a_layer(rotary, layers)))
    in_vmem = [_kernel_tiles(t, d, r, kv_heads, rule) is not None for r, rule, _ in shapes]
    turned = [scores and all(_turn_in_vmem(t, heads * d, d, turns)
                             for heads in (kv_heads * r, kv_heads))
              for scores, (r, _, turns) in zip(in_vmem, shapes) if turns != 0]
    return (("attn_scores_in_vmem", sum(in_vmem) / len(in_vmem)),
            ("attn_rotation_in_vmem", sum(turned) / max(len(turned), 1)))


def _widest_scores(t: int, sight, block: int) -> int:
    """The most (query, key) pairs of one block of one head's scores."""
    return max((hi - lo) * sum(khi - klo for klo, khi in runs)
               for lo, hi, runs in _attention_spans(t, sight, block))


def attention_alive_bytes(t: int, kv_heads: int, heads_per_kv, d: int,
                          sights, block: int) -> int:
    """Device bytes of attention's own that are alive at once in a layer's
    backward pass, the largest over layers of the given rules of sight
    (``heads_per_kv`` every layer's, or a list of one a layer): the plain
    form's three copies of the scores alive at once; the fused kernels'
    residuals, the output and a log-sum-exp a row kept across the 128
    lanes."""
    def alive(sight, r):
        if _kernel_tiles(t, d, r, kv_heads, sight) is not None:
            return 4 * t * kv_heads * r * (d + 128)
        widest = r * _widest_scores(t, sight, block)
        return 3 * 4 * widest * max(min(_SCORES_AT_ONCE // widest, kv_heads), 1)

    sights = list(sights)
    return max(alive(sight, r)
               for sight, r in zip(sights, _a_layer(heads_per_kv, len(sights))))


def banded_attention(q, k, v, sight, block: int,
                     scores_at_once: int = _SCORES_AT_ONCE):
    """Softmax attention with grouped queries under the rule of sight
    ``sight``. A number or ``None`` (:class:`Causal`): position ``i`` sees
    ``j <= i`` and, with a window, ``i - j < window``. A
    :class:`BlockDiffusion`: the ``T = 2 S`` rows are a clean and a masked
    copy of ``S`` positions, and a row sees what that rule says, one softmax
    a query over all of it. ``q`` f32[T, G, R, d] (query head ``g * R +
    r`` on key/value head ``g``), ``k, v`` f32[T, G, d]; returns f32[T, G,
    R, d]. Scores are ``q . k / sqrt(d)``, the softmax float32. The plain
    form (:func:`attention_mixer` takes the fused kernels where
    :func:`_kernel_tiles` says so: the same mathematics, a tile's scores
    never out of VMEM).

    Queries go in blocks of ``block``, each against the keys of
    :func:`_attention_spans` and no others; a key/value head is not
    repeated for its ``R`` query heads (one product over them); the groups
    go as many at a time as keep a block's scores under ``scores_at_once``
    (one where not even two do) and a block's scores are recomputed in the
    backward pass, so what is alive at once is one block's scores of those
    groups."""
    t, d = q.shape[0], q.shape[-1]
    scale = d ** -0.5
    rule = _rule(sight)
    spans = rule.spans(t, block)

    def one_block(qb, kb, vb, lo, runs):
        # rows are (query, head) pairs: the R query heads of a key/value
        # head share one product, and everything between the two products
        # is two-dimensional (a [block, R, keys] array of scores costs the
        # chip eight times the time: its softmax leaves the fast path)
        nq, r = qb.shape[0], qb.shape[1]
        s = _mm(qb.reshape(nq * r, d), kb.T) * scale
        at = lo + jnp.arange(nq * r)[:, None] // r
        key = _beside([klo + jnp.arange(khi - klo)[None, :] for klo, khi in runs], axis=1)
        att = jax.nn.softmax(jnp.where(rule.seen(at, key, t), s, -1e30), axis=-1)
        return _mm(att, vb).reshape(qb.shape)

    # a Python loop over the blocks, each traced where it starts: a
    # ``lax.scan`` over a window layer's seven blocks of one shape built
    # 12 s sooner on the chip's host and ran a sweep 8 % slower (PR 32)
    one_block = jax.checkpoint(one_block, static_argnums=(3, 4))

    def one_group(qkv):
        qg, kg, vg = qkv
        return jnp.concatenate([
            one_block(qg[lo:hi], _beside([kg[klo:khi] for klo, khi in runs]),
                      _beside([vg[klo:khi] for klo, khi in runs]), lo, runs)
            for lo, hi, runs in spans], axis=0)

    groups = (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1))
    widest = _widest_scores(t, sight, block) * q.shape[2]
    at_once = scores_at_once // widest
    out = jax.lax.map(one_group, groups, batch_size=at_once if at_once > 1 else None)
    return out.swapaxes(0, 1)


def _across_a_head(gate, d: int):
    """``gate`` [T, heads] (an operand: :func:`attention_mixer` has rounded
    it) with each head's number across its ``d`` lanes, f32[T, heads x d],
    the heads side by side: a product with the heads' 0 / 1 matrix, exact,
    so that its transpose, the sum of a head's lanes, is a product too and
    no array ``[T, heads, d]`` stands between. On the
    chip the repeat and the sum as such read 8.8 ms a window layer and step
    for the sum alone (PR 50: a twelfth of the sweep over the five layers),
    and the repeat is a broadcast to three axes and a change of layout."""
    a_head = jnp.repeat(jnp.eye(gate.shape[1], dtype=_OPERAND), d, axis=1)
    return _mm(gate, a_head)


def _beside(runs, axis: int = 0):
    """The runs of keys one after the other (one run: as it is)."""
    return runs[0] if len(runs) == 1 else jnp.concatenate(runs, axis=axis)


def attention_mixer(x, p, *, kv_heads: int, heads_per_kv: int, head_dim: int,
                    inv_freq, factor: float, sight, block: int,
                    scope: str, norm_eps: Optional[float] = None):
    """An attention layer's mixer, from the norm's output to ``W_o``: the
    projections ``wq``, ``wk``, ``wv`` as one product, queries and keys
    turned by the rotary tables of ``inv_freq`` and ``factor`` at the rows'
    positions (``inv_freq`` None: a layer without positions, nothing is
    turned and no tables are built), softmax attention under the rule of
    sight ``sight`` (a window or ``None``: causal, banded where it is a
    number; a :class:`BlockDiffusion`: ``x`` is the clean and the masked
    copy, two rows a position), ``wo``. A layer whose leaves hold ``q_norm``
    and ``k_norm`` puts its queries and its keys through an RMSNorm of those
    weights and ``norm_eps``, between the projection and the rotation; the
    leaf's shape says over what: f32[head_dim], every head through its own
    norm with the one weight; f32[heads x head_dim], the projection's whole
    width through one norm, before the heads are split. A layer whose leaves
    hold ``w_head_gate`` f32[D, heads] gates its heads: ``sigmoid(x
    w_head_gate)``, one number a head and position (one more block of columns
    of the projections' product), multiplies the head's attention output
    before ``wo`` (not ``w_gate``: that is a dense SwiGLU's leaf, and a layer
    hands its leaves here whole). The gate's sigmoid is float32 and its
    value is rounded to the products' operand type once, before the two
    paths below part, as ``wo``'s operand is: on the kernels' path it goes
    across a head's lanes by a product (:func:`_across_a_head`), and both
    paths multiply by the same numbers. An ``inv_freq`` shorter than half a head
    turns the head's first ``2 len(inv_freq)`` channels alone (channel ``i``
    with ``i + len(inv_freq)``); the rest pass unturned and without
    ``factor``.

    The attention is :func:`banded_attention` in plain JAX or, where
    :func:`_kernel_tiles` says so, the fused kernels of
    ``ops/pallas_attention.py``, with their own backward pass: the heads
    then stay side by side from the projections to ``wo`` (the kernels take
    them so, and :func:`_rotate_side_by_side` turns them so: one kernel
    where its shapes fit, which hands the attention kernels their operands
    already rounded), so that no array changes layout on the way. ``scope``
    is the caller's part (``lane.swa``, ``lane.gqa``, ``lane.bda``: the
    ``jax.named_scope`` it calls this under), which the kernels' backward
    rules have to be told: they are traced where the caller's scope is no
    longer open."""
    t = x.shape[0]
    g, r, d = kv_heads, heads_per_kv, head_dim
    gate = p.get("w_head_gate")
    if gate is None:
        q, k, v = _mm_beside(x, p["wq"], p["wk"], p["wv"])
    else:
        # the gate's 48 or 64 columns would leave the product no whole tiles
        # of lanes wide, and the chip's compiler then holds its result with
        # the rows in the lanes: every part of it that a kernel takes is
        # copied out row-major first (1 ms a window layer's queries, under no
        # scope). Columns of zeros make it whole
        heads = gate.shape[1]
        q, k, v, gate = _mm_beside(
            x, p["wq"], p["wk"], p["wv"], jnp.pad(gate, ((0, 0), (0, -heads % 128))))
        gate = jax.nn.sigmoid(gate[:, :heads]).astype(_OPERAND)
    q_norm, k_norm = p.get("q_norm"), p.get("k_norm")
    if q_norm is not None:
        # once, before the two paths part, so that both have it
        normed = lambda y, w: _rms(y.reshape(t, -1, w.shape[0]), w, norm_eps).reshape(y.shape)
        q, k = normed(q, q_norm), normed(k, k_norm)
    rule = _rule(sight)
    if inv_freq is None:
        turn = turn_side_by_side = lambda y: y
    else:
        cos, sin = _rotary_tables(inv_freq, factor, rule.positions(t), d)
        tables = dict(cos=cos, sin=sin, rotary=2 * len(inv_freq))
        turn = functools.partial(_rotate, **tables)
        turn_side_by_side = functools.partial(_rotate_side_by_side, **tables, scope=scope)
    tiles = _kernel_tiles(t, d, r, g, rule)
    if tiles is not None:
        out = pallas_attention.fused_banded_attention(
            turn_side_by_side(q), turn_side_by_side(k), v,
            (g, r, d), rule, tiles, _OPERAND, scope)
        if gate is not None:
            out = out * _across_a_head(gate, d)
    else:
        out = banded_attention(
            turn(q.reshape(t, g, r, d)), turn(k.reshape(t, g, d)),
            v.reshape(t, g, d), sight, block)
        if gate is not None:
            out = out * gate.reshape(t, g, r, 1)
        out = out.reshape(t, g * r * d)
    return _mm(out, p["wo"])


# ------------------------------------------------------- short convolution
def _causal_conv(x, w):
    """Depthwise causal convolution: ``y_t = sum_i w[i] x[t - K + 1 + i]``
    with zeros before the sequence; ``x`` f32[T, C], ``w`` f32[K, C]."""
    k, t = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(w[i] * padded[i:i + t] for i in range(k))


def short_conv_mixer(x, p, *, scope: str):
    """A gated short convolution's mixer, from the norm's output to
    ``W_out``, under the caller's part ``scope``: ``B, C, u`` the three
    thirds of ``x W_in`` in that order, ``y = C * conv(B * u)`` with the
    depthwise causal convolution of ``p["conv"]`` f32[K, D] (no activation,
    no bias), ``y W_out``. The two products take the lanes' operands; the
    gates and the convolution are float32. Plain JAX, differentiated by
    JAX."""
    with jax.named_scope(scope):
        b, c, u = jnp.split(_mm(x, p["w_in"]), 3, axis=1)
        return _mm(c * _causal_conv(b * u, p["conv"]), p["w_out"])


# --------------------------------------------------------------- parameters
def _unit_draw(key, name: str, shape):
    """A leaf's unit normals from the key and its own name, so that the draw
    does not depend on which other leaves exist."""
    # drawn as a matrix and folded: the same numbers in the same order (the
    # chip's compiler takes fourteen seconds over a three-dimensional draw)
    return jax.random.normal(
        jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
        (int(np.prod(shape[:-1])), shape[-1]), jnp.float32).reshape(shape)


class Init:
    """A lane's initial weights in the two halves a bracket takes apart, of
    ``params(key, *args, init_scale)``, a model's ``init_<model>_params``,
    which draws through :func:`_init_leaf` alone: ``shared()``, the unit
    draws by the leaves' names, the same numbers whatever the configuration;
    ``scale(shared, init_scale)``, the parameters from them, with the leaves
    that are not drawn; ``init(init_scale)``, both in one program, as it was
    before there were halves. A leaf is the same expression of the same
    draw either way, and the same number to its last bit or the one before:
    where the draw and its scaling are one fusion the compiler multiplies
    the normal's own last factor, the square root of two, into the scale
    first, and where the draw is an operand it cannot. Both halves are
    ``params`` itself, handed where its key goes a ``draw(name, shape)``
    that keeps what it draws, or one that hands out what was kept: so
    ``params`` hands its key to :func:`_init_leaf` as it got it, and
    neither splits it nor folds anything into it."""

    def __init__(self, params, key, *args):
        self._params, self._key, self._args = params, key, args

    def __call__(self, init_scale):
        return self._params(self._key, *self._args, init_scale)

    def shared(self) -> dict:
        held = {}

        def draw(name, shape):
            held[name] = _unit_draw(self._key, name, shape)
            return held[name]

        self._params(draw, *self._args, 1.0)    # the scaling is dead code in a trace
        return held

    def scale(self, shared: dict, init_scale):
        return self._params(lambda name, shape: shared[name], *self._args, init_scale)


def _init_leaf(key, name: str, shape, init_scale):
    """One leaf from the key and its own name (:func:`_unit_draw`). ``key``
    is a PRNG key or, from :class:`Init`, a ``draw(name, shape)`` that
    answers with the leaf's unit normals: a model's ``init_<model>_params``
    passes it on untouched. Matrices (and depthwise convolutions) are
    ``init_scale / sqrt(fan_in) * N(0, 1)``, the embedding ``init_scale *
    N(0, 1)`` (a lookup's fan-in is one: a smaller embedding only has the
    first norm multiply its gradient up), norm weights one, a bias
    (``*_bias``) zero."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf.startswith("norm") or leaf.endswith("_norm"):
        return jnp.ones(shape, jnp.float32)
    if leaf.endswith("_bias"):
        return jnp.zeros(shape, jnp.float32)
    draw = key(name, shape) if callable(key) else _unit_draw(key, name, shape)
    fan_in = 1 if leaf == "embed" else shape[-2]
    return (init_scale * fan_in ** -0.5) * draw


def _init_params(key, cfg, layer_shapes, init_scale, init_leaf=_init_leaf,
                 tied: bool = False) -> dict:
    """Embedding, final norm, head and ``l<i>`` for each of ``layer_shapes``
    (a ``{leaf: shape}`` a layer). ``tied``: the head is the embedding,
    transposed, and there is no leaf ``head``; the one matrix is then drawn
    as the head it also is, ``init_scale / sqrt(hidden_size) * N(0, 1)`` (the
    same draw, scaled by the head's fan-in: the first norm takes a lookup's
    scale out again, the logits keep it). ``key`` goes to ``init_leaf`` as
    it came: it may be :class:`Init`'s ``draw(name, shape)`` and no key."""
    d = cfg.hidden_size
    shapes = {"embed": (cfg.vocab_rows, d), "norm_f": (d,)}
    if not tied:
        shapes["head"] = (d, cfg.vocab_rows)
    scales = {"embed": init_scale * d ** -0.5} if tied else {}
    params = {n: init_leaf(key, n, s, scales.get(n, init_scale)) for n, s in shapes.items()}
    for i, layer in enumerate(layer_shapes):
        params[f"l{i}"] = {
            n: init_leaf(key, f"l{i}/{n}", s, init_scale) for n, s in layer.items()}
    return params


def _count_params(init) -> int:
    """Parameters of ``init() -> params``, from its shapes alone."""
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(jax.eval_shape(init)))


# ------------------------------------------------------------ expert layer
class ExpertLayer(NamedTuple):
    """What the expert layer is told of its router and of this chip's
    share. Whether the router has a balancing bias and whether a shared
    expert stands beside the routed ones is stated by the layer's leaves
    (``router_bias``; ``shared_gate``, ``shared_up``, ``shared_down``)."""

    #: the router's outputs: all the model's routed experts
    outputs: int
    top_k: int
    #: which of them this chip holds, in the order of the leaves' first axis
    held: Tuple[int, ...]
    #: ``"sigmoid"`` or ``"softmax"`` (over all the outputs, before the top k)
    score: str = "sigmoid"
    #: the chosen scores, renormalised to sum to one, times this
    scaling: float = 1.0
    #: added to the sum of the chosen scores before they are divided by it
    epsilon: float = 0.0


#: the plain form's alone since PR 39 (the CPU path, and what the grouped
#: kernels are tested against: :func:`_product_rows`): a tile of its
#: grouped product is four times the even load, and no more rows than
#: this. Measured on the chip at 65,536 token-choices (PR 33, the rows
#: moved by gathers; a layer's forward and backward pass, with
#: 16,087 / 17,450 choices held: just under and just over the even load of
#: 16,384, which the lane's measured share of 25.1 % straddles): tiles of
#: 4,096 rows 45 / 52 ms, 8,192: 41 / 52, 16,384: 39 / 58, 32,768: 58 / 58,
#: one of 65,536: 87; a whole sweep of the Mellum2 lane 15.06 s at 4,096,
#: 14.79 s at 8,192, 16.53 s at 32,768 (all read while the loop's buffers
#: were still filled with zeros first: 0.2 s a sweep whatever the tile;
#: 14.55 s at 8,192 without the fill). A reached tile pays for all its
#: rows, the closing group's too (the last reached tile is half empty on
#: average), and per tile for a pass over the experts' gradient and a
#: change of layout of their weights (1.9 ms at these widths). With the
#: scatter-adds of PR 32 a tile cost a pass over the layer's output besides,
#: and 32,768 rows read best (67 ms against 70 at 8,192)
_TILE_ROWS = 8192

#: the grouped kernels' tile of sorted rows (``ops/pallas_grouped.py``): a
#: tile that two experts share is visited once for each, so a tile's rows
#: over an expert's load is the padding. Read on the chip (PR 39; one
#: layer's forward and backward pass, router and sort included, ms): the
#: Mellum2 lane's 65,536 choices, 16,271 held: the plain form 38.04, the
#: kernels in tiles of 128 rows 16.34 (1.12 rows computed a held one), 256
#: rows 16.44 (1.24), 512 rows 17.52; the kimi lane's 32,768 choices, 976
#: held: 12.12 plain, 7.10 at 128 (1.97), 7.17 at 256 (2.89). Each of the
#: seven kernels reads the same at 128 and at 256 rows (0.57-1.05 ms at the
#: Mellum2 size: 63-65 % of the bfloat16 peak on the held rows)
_KERNEL_TILE_ROWS = 128

#: how the expert layer moves its rows, beside a lane's counted facts
#: (``make_lane_eval_fn(static_counters=...)``): 1 where dispatch, combine
#: and their transposes are gathers by the counting sort's two permutations
MOE_COUNTERS = (("moe_combine_by_gather", 1),)


def _product_rows(choices: int, d: int, f: int):
    """The grouped kernels' tile of rows for ``choices`` sorted
    token-choices through experts ``d -> 2 f -> d``, or None where the plain
    form runs: the kernels (``ops/pallas_grouped.py``: the products over the
    whole array of sorted rows, what lies between them never out of VMEM)
    where Mosaic compiles them, on a TPU backend, and the shapes fit (widths
    whole lanes, the rows whole tiles, an expert's weights and their
    gradient's sum within the kernels' share of VMEM); off the chip the
    plain form (the loop over tiles of ``jax.lax.ragged_dot``), which is
    also what the kernels are tested against. Nothing here reads a model."""
    if not pallas_available():
        return None
    rows = min(_KERNEL_TILE_ROWS, choices)
    fits = pallas_grouped.fits(
        choices, ((d, 2 * f), (f, d)), rows, np.dtype(_OPERAND).itemsize)
    return rows if fits else None


def expert_layer_counters(choices: int, d: int, f: int):
    """The static facts of how a lane's expert layers are computed, beside
    its counted ones: :data:`MOE_COUNTERS` and whether the grouped products
    are the kernels' (1 on the chip at the published sizes, 0 on a CPU)."""
    return MOE_COUNTERS + (
        ("moe_products_in_vmem", float(_product_rows(choices, d, f) is not None)),)


def _tile_sizes(ends, lo, rows: int):
    """The rows of the tile at ``lo`` by group: each held expert's (the
    sorted rows up to ``ends[e]`` are experts ``0 .. e``'s), then the
    closing group's: every row of a tile belongs to a group."""
    return jnp.diff(jnp.clip(ends - lo, 0, rows), prepend=0, append=rows)


def _tile_experts(xs, e_in, e_down, sizes):
    """A tile's rows through their experts: gate and up side by side as one
    grouped product, then down."""
    dot = lambda a, w: jax.lax.ragged_dot(
        a.astype(_OPERAND), w, sizes, preferred_element_type=jnp.float32)
    gate_up = dot(xs, e_in)
    f = e_down.shape[1]
    return dot(jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:], e_down)


def _sum_of_choices(sorted_rows, place, held, top_k: int, weight=None):
    """``out[t] = sum_j weight[t, j] * sorted_rows[place[t, j]]`` over the
    held choices of token ``t`` (``weight`` one where there is none),
    float32: a gather by ``place`` (choice -> sorted row). A ``where`` and
    not a product by zero: what a row holds that is no held choice's (of
    the closing group, or of a tile that was not reached) never reaches
    the sum, whatever a diverged lane left there."""
    picked = jnp.where(held[:, None], sorted_rows[place].astype(jnp.float32), 0.0)
    picked = picked.reshape(-1, top_k, picked.shape[-1])
    return (picked if weight is None else picked * weight[:, :, None]).sum(1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _routed(x, weight, e_in, e_down, order, place, ends, at, top_k, rows):
    """``y[t] = sum_j weight[t, j] * E(x[t])`` over the held choices of
    token ``t``: the rows move from token order to expert order and back by
    gathers alone, in both passes. ``order`` (sorted row -> choice) and
    ``place`` (choice -> sorted row) are the counting sort's permutation
    and its inverse, so each movement's transpose is a gather by the other
    one, and is written so here: what autodiff makes of a gather is a
    scatter-add, which sorts its indices on the device. ``at`` is None and
    the experts' products the plain form's (a loop over tiles of ``rows``
    sorted rows, ``e_in`` and ``e_down`` closed by a group of zero weights),
    or the grouped kernels' visits of tiles of ``rows`` rows
    (``pallas_grouped.visits``) and the products the kernels'."""
    return _routed_forward(x, weight, e_in, e_down, order, place, ends, at, top_k, rows)[0]


#: a number a row is kept across the chip's 128 lanes where a kernel reads
#: or writes it (a column broadcasts to a tile by reuse of registers)
_WEIGHT_LANES = 128


def _gated(gate_up):
    """Between the experts' two products: ``silu(gate) * up`` of a tile's
    ``[gate | up]``, rounded as the down product reads it."""
    f = gate_up.shape[1] // 2
    return ((jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]).astype(_OPERAND),)


def _gated_back(dh, gate_up, w):
    """:func:`_gated` pulled back, a tile: ``dh`` f32[rows, f] is the rows'
    cotangent through the down product, unweighted (``dy_rows @
    e_down^T``); ``w`` the choices' weights, kept across 128 lanes. ->
    ``(w * h`` (the down weights' gradient is its transpose against
    ``dy_rows``), ``d[gate | up]`` of the weighted rows, ``dweight = (dh *
    h).sum(-1)`` across 128 lanes: ``(dy_rows * ys).sum(-1)`` with the down
    product's sum taken in the other order, so that product is not computed
    again)``."""
    f = dh.shape[1]
    gate, up = gate_up[:, :f], gate_up[:, f:]
    s = jax.nn.sigmoid(gate)
    silu = gate * s
    h = (silu * up).astype(_OPERAND).astype(jnp.float32)
    w = jnp.tile(w, (1, f // w.shape[1]))
    dweight = (dh * h).sum(axis=-1, keepdims=True)
    dh = dh * w
    d_gate_up = jnp.concatenate([dh * up * (s + silu * (1.0 - s)), dh * silu], axis=1)
    return ((h * w).astype(_OPERAND), d_gate_up.astype(_OPERAND),
            jnp.broadcast_to(dweight, (dh.shape[0], _WEIGHT_LANES)))


def _rows_reached(source, index, n_held, rows: int):
    """``source[index]`` for the sorted rows that a held choice reaches, in
    chunks of whole tiles of ``rows`` (no more than ``_TILE_ROWS`` rows a
    chunk); the rows past the last reached chunk are as the device hands
    them over (no fill, and no gather of what no kernel visits: three
    quarters of the choices in the Mellum2 lane)."""
    m = index.shape[0]
    chunk = math.gcd(m, max(_TILE_ROWS // rows, 1) * rows)

    def gather(i, out):
        take = jax.lax.dynamic_slice(index, (i * chunk,), (chunk,))
        return jax.lax.dynamic_update_slice(out, source[take], (i * chunk, 0))

    return jax.lax.fori_loop(
        0, -(-n_held // chunk), gather, jax.lax.empty((m, source.shape[1]), source.dtype))


def _kernels_forward(x, weight, e_in, e_down, order, place, ends, at, top_k, rows):
    """:func:`_routed_forward` with the grouped kernels: the sorted rows
    whole, two products, the SwiGLU between them inside the first."""
    interpret = not pallas_available()
    with jax.named_scope("lane.moe"):
        with jax.named_scope("moe.dispatch"):
            xs = _rows_reached(x.astype(_OPERAND), order // top_k, ends[-1], rows)
        with jax.named_scope("moe.experts"):
            h, = pallas_grouped.rows_by_group(
                xs, e_in, at, rows, [(e_down.shape[1], _OPERAND)], epilogue=_gated,
                interpret=interpret)
            ys, = pallas_grouped.rows_by_group(
                h, e_down, at, rows, [(x.shape[1], jnp.float32)], interpret=interpret)
        # the tiles that no held choice reaches are as the device left them,
        # and the combine reads no row of theirs
        with jax.named_scope("moe.combine"):
            y = _sum_of_choices(ys, place, place < ends[-1], top_k, weight)
    return y, (x, weight, e_in, e_down, order, place, ends, at)


def _kernels_backward(top_k, rows, kept, dy):
    """:func:`_routed_backward` with the grouped kernels, product by
    product on the held rows alone: gate and up again; ``dy_rows @
    e_down^T`` with :func:`_gated_back` inside it; each expert's two
    gradients as the transposed kernel, written once; ``d[gate | up] @
    e_in^T``. Eight products' worth of a forward pass's three."""
    x, weight, e_in, e_down, order, place, ends, at = kept
    interpret = not pallas_available()
    grouped = functools.partial(pallas_grouped.rows_by_group, interpret=interpret)
    transposed = functools.partial(pallas_grouped.groups_by_rows, interpret=interpret)
    with jax.named_scope("lane.moe"):
        f = e_down.shape[1]
        with jax.named_scope("moe.dispatch"):
            token = order // top_k
            xs = _rows_reached(x.astype(_OPERAND), token, ends[-1], rows)
            # the combine's transpose: a gather by ``order``, as the dispatch is
            dy_rows = _rows_reached(dy.astype(_OPERAND), token, ends[-1], rows)
            w = jnp.broadcast_to(
                weight.reshape(-1)[order][:, None], (order.shape[0], _WEIGHT_LANES))
        with jax.named_scope("moe.experts"):
            gate_up, = grouped(xs, e_in, at, rows, [(2 * f, jnp.float32)])
            hw, d_gate_up, dweights = grouped(
                dy_rows, e_down, at, rows,
                [(f, _OPERAND), (2 * f, _OPERAND), (_WEIGHT_LANES, jnp.float32)],
                transpose_rhs=True, epilogue=_gated_back, beside=(gate_up, w))
            g_down = transposed(hw, dy_rows, at, rows, e_down.dtype)
            g_in = transposed(xs, d_gate_up, at, rows, e_in.dtype)
            dxs, = grouped(d_gate_up, e_in, at, rows, [(x.shape[1], _OPERAND)],
                           transpose_rhs=True)
        with jax.named_scope("moe.combine"):
            held = place < ends[-1]
            dx = _sum_of_choices(dxs, place, held, top_k)
            dweight = jnp.where(held, dweights[place, 0], 0.0).reshape(weight.shape)
    return dx, dweight, g_in, g_down, None, None, None, None


def _routed_forward(x, weight, e_in, e_down, order, place, ends, at, top_k, rows):
    """``(y, what the backward rule keeps: the inputs)``. The rules name
    their own scope: the backward one is traced where the layer's caller
    has none."""
    if at is not None:
        return _kernels_forward(x, weight, e_in, e_down, order, place, ends, at, top_k, rows)
    with jax.named_scope("lane.moe"):
        d = x.shape[1]
        with jax.named_scope("moe.dispatch"):
            xb = x.astype(_OPERAND)
        with jax.named_scope("moe.experts"):
            n_held = ends[-1]

        def tile(i, ys):
            # dispatch: a gather by ``order``
            with jax.named_scope("moe.dispatch"):
                take = jax.lax.dynamic_slice(order, (i * rows,), (rows,))
                xs = xb[take // top_k]
            with jax.named_scope("moe.experts"):
                return jax.lax.dynamic_update_slice(ys, _tile_experts(
                    xs, e_in, e_down, _tile_sizes(ends, i * rows, rows)),
                    (i * rows, 0))

        # what the tiles produce is the sorted rows, and only the tiles
        # that a held choice reaches are computed. The rows start as the
        # device finds them (no fill: a pass over 0.6 GB a layer at the
        # Mellum2 lane's size): a reached tile writes all of its rows, and
        # the combine reads no row of another
        # the loop is the experts' (what in its body has no name of its own)
        with jax.named_scope("moe.experts"):
            ys = jax.lax.fori_loop(
                0, -(-n_held // rows), tile, jax.lax.empty((order.shape[0], d), jnp.float32))
        # combine: a gather by ``place``, summed over the top k
        with jax.named_scope("moe.combine"):
            y = _sum_of_choices(ys, place, place < n_held, top_k, weight)
    return y, (x, weight, e_in, e_down, order, place, ends, None)


def _routed_backward(top_k, rows, kept, dy):
    """Each reached tile's rows are computed again from the layer's inputs
    (what ``jax.checkpoint`` around a tile did) and differentiated; the
    combine's transpose is a gather by ``order`` and the dispatch's a
    gather by ``place``."""
    if kept[-1] is not None:
        return _kernels_backward(top_k, rows, kept, dy)
    x, weight, e_in, e_down, order, place, ends, _ = kept
    with jax.named_scope("lane.moe"):
        d = x.shape[1]
        with jax.named_scope("moe.dispatch"):
            xb = x.astype(_OPERAND)
        with jax.named_scope("moe.experts"):
            n_held = ends[-1]
        weight_of = weight.reshape(-1)

        def tile(i, grads):
            g_in, g_down, dxs, dweights = grads
            with jax.named_scope("moe.dispatch"):
                take = jax.lax.dynamic_slice(order, (i * rows,), (rows,))
                token = take // top_k
            with jax.named_scope("moe.experts"):
                sizes = _tile_sizes(ends, i * rows, rows)
            with jax.named_scope("moe.dispatch"):
                xs = xb[token]
            # the rule's own recomputation, named as the trainer's is
            with jax.named_scope("moe.experts"), jax.named_scope("pass.recompute"):
                ys, pull = jax.vjp(
                    lambda xs, e_in, e_down: _tile_experts(xs, e_in, e_down, sizes),
                    xs, e_in, e_down)
            # the combine's transpose: a gather by ``order``, as the dispatch is
            with jax.named_scope("moe.dispatch"):
                dy_rows = dy[token]
                dys = dy_rows * weight_of[take][:, None]
            with jax.named_scope("moe.experts"):
                t_xs, t_in, t_down = pull(dys)
                grads = (g_in + t_in, g_down + t_down,
                         jax.lax.dynamic_update_slice(dxs, t_xs, (i * rows, 0)))
            with jax.named_scope("moe.combine"):
                return grads + (jax.lax.dynamic_update_slice(
                    dweights, (dy_rows * ys).sum(-1), (i * rows,)),)

        with jax.named_scope("moe.experts"):
            g_in, g_down, dxs, dweights = jax.lax.fori_loop(
                0, -(-n_held // rows), tile,
                (jnp.zeros_like(e_in), jnp.zeros_like(e_down),
                 jax.lax.empty((order.shape[0], d), xb.dtype),
                 jax.lax.empty(order.shape, jnp.float32)))
        with jax.named_scope("moe.combine"):
            held = place < n_held
            dx = _sum_of_choices(dxs, place, held, top_k)
            dweight = jnp.where(held, dweights[place], 0.0).reshape(weight.shape)
    return dx, dweight, g_in, g_down, None, None, None, None


_routed.defvjp(_routed_forward, _routed_backward)


def moe_held_experts(x, p, layer: ExpertLayer):
    """This chip's part of the expert layer: ``w_e * E_e(x)`` for each
    chosen expert it holds, and the shared expert once where the layer has
    one. Returns ``(y f32[T, D], counters f32[3])``.

    The router scores all ``outputs`` (``s``: a sigmoid each, or a softmax
    over them all), chooses the top k of ``s`` (``s + b`` with a bias: the
    bias chooses and does not weigh) and weighs them ``s_e / (sum(chosen s)
    + epsilon) * scaling``. Token-choices are sorted
    by held expert (the others last) and the held ones go through their
    experts' products, one group an expert: only the tiles of sorted rows
    that a held choice reaches are computed, so the work follows the load
    and no token is dropped whatever the load. Where :func:`_product_rows`
    says so the products are the grouped kernels' (``ops/pallas_grouped.py``,
    over the whole array of sorted rows in tiles of ``_KERNEL_TILE_ROWS``,
    forward and backward); else the plain form's, ``jax.lax.ragged_dot`` in
    tiles of four times the even load (at most ``_TILE_ROWS`` rows), the
    rows that are not for this chip in a last group of zero weights. Rows
    move to expert order and back by gathers alone (:func:`_routed`). The
    counters: (token-choices on held experts, fullest held expert's load
    over the mean held load, rows of the tiles that were computed)."""
    t = x.shape[0]
    top_k, held = layer.top_k, len(layer.held)
    kernel_rows = _product_rows(t * top_k, x.shape[1], p["e_down"].shape[1])
    with jax.named_scope("moe.router"):
        logits = jnp.matmul(x, p["router"], precision=_FLOAT32)
        s = (jax.nn.sigmoid(logits) if layer.score == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
        _, chosen = jax.lax.top_k(s + p["router_bias"] if "router_bias" in p else s, top_k)
        # the chosen scores by comparison and not by index: the transpose of
        # ``take_along_axis`` is a scatter-add too
        s_chosen = jnp.where(
            chosen[:, :, None] == jnp.arange(layer.outputs), s[:, None, :], 0.0).sum(-1)
        total = s_chosen.sum(-1, keepdims=True)
        weight = s_chosen / (total + layer.epsilon if layer.epsilon else total)
        if layer.scaling != 1.0:
            weight = weight * layer.scaling
    slot_of = np.full((layer.outputs,), held, np.int32)          # held = "not here"
    slot_of[list(layer.held)] = np.arange(held)
    with jax.named_scope("moe.sort"):
        slot = jnp.asarray(slot_of)[chosen].reshape(-1)              # [T * k]
        # a counting sort, stable: a choice's place is its slot's start plus the
        # earlier choices of its slot (the chip's compiler takes ten seconds
        # over an ``argsort`` of this length, and there is one a layer and pass)
        in_slot = (slot[:, None] == jnp.arange(held + 1)[None, :]).astype(jnp.int32)
        all_loads = in_slot.sum(0)
        place = (in_slot * (jnp.cumsum(in_slot, 0) - in_slot
                            + (jnp.cumsum(all_loads) - all_loads)[None, :])).sum(1)
        loads = all_loads[:held]
        rows = kernel_rows or min(
            t * top_k, max(min(4 * t * top_k * held // layer.outputs, _TILE_ROWS), 8))
        n_tiles = -(-t * top_k // rows)
        order = jnp.zeros((n_tiles * rows,), jnp.int32).at[place].set(
            jnp.arange(t * top_k, dtype=jnp.int32), unique_indices=True)

    # every row of a tile belongs to a group: after the held experts comes
    # one whose weights are zero and takes the rows that are not for this
    # chip. On the chip ``ragged_dot`` leaves the rows that no group holds
    # as it finds them, in the backward pass too, where a mask on its
    # output cannot reach. The kernels visit no row past the last held one.
    with_rest = (lambda w: w) if kernel_rows else (
        lambda w: jnp.concatenate([w, jnp.zeros_like(w[:1])]))
    # gate and up side by side: one grouped product for the two
    with jax.named_scope("moe.experts"):
        e_in = with_rest(jnp.concatenate([p["e_gate"], p["e_up"]], -1)).astype(_OPERAND)
        e_down = with_rest(p["e_down"]).astype(_OPERAND)
    with jax.named_scope("moe.sort"):
        ends = jnp.cumsum(loads)
        at = pallas_grouped.visits(ends, t * top_k, rows) if kernel_rows else None
        computed = rows * (-(-ends[-1] // rows) if at is None else at.count)
    y = _routed(x, weight, e_in, e_down, order, place, ends, at, top_k, rows)
    if "shared_gate" in p:
        with jax.named_scope("moe.shared"):
            y = y + _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    with jax.named_scope("moe.sort"):
        load = loads.astype(jnp.float32)
        counters = jnp.stack([load.sum(), load.max() / jnp.maximum(load.mean(), 1e-9),
                              computed.astype(jnp.float32)])
    return y, counters


# ------------------------------------------------------- embedding and head
def _entry(seq, exits):
    """The ids whose embeddings are a pass's first state: a row of ids less
    its last, or what the model's ``exits.entry`` reads off a record."""
    return seq[:-1] if exits.entry is None else exits.entry(seq)


def _embed(params, seq, exits):
    with jax.named_scope("lane.head"):
        return params["embed"][_entry(seq, exits)]


def _head_loss(h, norm_f, head, tokens, eps):
    with jax.named_scope("lane.head"):
        logits = _mm(_rms(h, norm_f, eps), head)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0].mean()


# ------------------------------------------------------- visits and exits
class Exits(NamedTuple):
    """Where a model's passes end, and what is read there. A state is
    ``h`` f32[T, D] after so many visits; ``trained`` and ``reported`` take
    ``(states, leaves, tokens)``: the states of ``after`` in order, the
    parameters of ``leaves`` in order, ``tokens`` the pass's sequence: i32[T
    + 1], or the record that ``entry`` reads too."""

    #: exit ``e`` reads the state after ``after[e]`` visits; the last one
    #: reads the last visit's
    after: Tuple[int, ...]
    #: the top-level leaves the exits read and no visit does: differentiated
    #: once a step through all the exits together, and stepped then; but
    #: ``embed`` among them (a head tied to the embedding: the exits read the
    #: matrix that the lookup reads) is differentiated with them and stepped
    #: once, with the lookup's gradient, by the sum of the two
    leaves: Tuple[str, ...]
    #: ``-> the loss a step descends``
    trained: Any
    #: ``-> (the loss a lane reports, f32[counted] or None)``
    reported: Any
    #: how many numbers ``reported`` counts beside its loss
    counted: int = 0
    #: where a sequence is a record and not a row of ids (a tree of arrays,
    #: one slice of the data's): ``entry(seq) -> i32[T]``, the ids whose
    #: embeddings are a pass's first state (a model trained by diffusion:
    #: the clean copy, then the masked one); None: ``seq[:-1]``
    entry: Any = None


def head_exit(n_visits: int, eps, tied: bool = False) -> Exits:
    """One exit after the last visit: final norm, head, mean next-token
    cross-entropy, trained and reported alike. ``tied``: the head is the
    embedding, transposed (the exit's leaves name ``embed``)."""
    def loss(states, leaves, tokens):
        (h,), (norm_f, head) = states, leaves
        if tied:
            with jax.named_scope("lane.head"):
                head = head.T
        return _head_loss(h, norm_f, head, tokens, eps)

    return Exits(after=(n_visits,), leaves=("norm_f", "embed" if tied else "head"),
                 trained=loss, reported=lambda *args: (loss(*args), None))


class Visit(NamedTuple):
    """One step of a pass: ``params[leaf]`` through ``through(h, p) -> (h,
    what the visit counts, f32[counted], or None)``."""

    leaf: str
    through: Any
    #: more than one: the leaves of ``params[leaf]`` are stacked ``[times,
    #: ...]`` and this is ``times`` visits in a row, slice ``i`` the
    #: ``i``-th's weights, traced and compiled as ONE loop's body (layers of
    #: one shape); what it counts is the sum over the slices
    times: int = 1
    #: how many numbers ``through`` counts beside the state (an expert
    #: layer: the two of :func:`moe_held_experts`); 0: it hands back None
    counted: int = 0
    #: ``slices(params[leaf]) -> take``, ``take(i) -> the i-th visit's
    #: parameters``: how a loop's visits get their weights out of the stacked
    #: leaves where that is more than ``x[i]`` (a looped model casts the
    #: stacked matrices to the products' operand type once and takes every
    #: slice out under the scope of the part that reads it: the chip's
    #: compiler makes such casts of whole stacks out of a loop's casts of
    #: slices whatever the program says, and what it makes itself carries
    #: no scope); called once before the loop, forward and backward
    slices: Any = None


def once_through(layers, counted: int = 0):
    """The visits of a plain stack: layer ``i`` takes ``l<i>``, once."""
    return tuple(Visit(f"l{i}", layer, counted=counted) for i, layer in enumerate(layers))


def _slices(visit: Visit, p):
    if visit.slices is not None:
        return visit.slices(p)
    return lambda i: jax.tree.map(lambda x: x[i], p)


def _visit_forward(visit: Visit, h, p, through=None):
    """``-> (h after the visit, what it counts, what its backward pass
    reads: the input, of every slice where the visit is a loop)``."""
    through = through or visit.through
    if visit.times == 1:
        return through(h, p) + (h,)
    take = _slices(visit, p)

    def one(h, i):
        out, c = through(h, take(i))
        return out, (c, h)

    h, (counters, inputs) = jax.lax.scan(one, h, jnp.arange(visit.times))
    return h, jax.tree.map(lambda c: c.sum(0), counters), inputs


def _visit_backward(visit: Visit, one, dh, kept, p, written, read=None, *, scope):
    """The backward pass of a visit through the leaf's parameters ``p``,
    slice by slice from the last where it is a loop: ``one(pull, dh,
    written_i, read_i) -> (dh, written_i anew)``, ``pull(dh) -> (dh, the
    gradient of the slice's parameters)`` the visit's pull-back with its
    inside computed again from what :func:`_visit_forward` kept. ``written``
    and ``read`` are trees of the leaf's shape (stacked where the visit is a
    loop); a slice is rewritten where it lies, in the loop's carry, so that
    what is alive beside the trees is one slice's gradient; taking a slice
    out and putting it back is charged to ``scope``, the part that ``one``
    rewrites them for. ``-> (dh, written anew)``."""
    def pull(h, q):
        def pulled(dh):
            with jax.named_scope("pass.recompute"):
                _, back = jax.vjp(lambda h, q: visit.through(h, q)[0], h, q)
            with jax.named_scope("pass.backward"):
                return back(dh)

        return pulled

    if visit.times == 1:
        return one(pull(kept, p), dh, written, read)
    # the slices' casts are made again for the inside that is computed again
    with jax.named_scope("pass.recompute"):
        take = _slices(visit, p)

    def from_the_last(k, carry):
        dh, written = carry
        i = visit.times - 1 - k
        at = lambda tree: jax.tree.map(lambda x: x[i], tree)
        with jax.named_scope(scope):
            written_i, read_i = at(written), at(read)
        with jax.named_scope("pass.recompute"):
            kept_i, p_i = kept[i], take(i)
        dh, anew = one(pull(kept_i, p_i), dh, written_i, read_i)
        with jax.named_scope(scope):
            return dh, jax.tree.map(
                lambda x, slice_i: x.at[i].set(slice_i), written, anew)

    return jax.lax.fori_loop(0, visit.times, from_the_last, (dh, written))


class Counted(NamedTuple):
    """What a lane counts on the device beside its loss, over its held-out
    passes: the counters are its model's."""

    names: Tuple[str, ...]
    #: ``(what the visits count, f32[visits that count, counted] in their
    #: order or None where none does, what the exits count, f32[k] or None,
    #: both summed over the held-out passes, n_val) -> one number a name``
    reduce: Any
    #: of the visits that count, those ``reduce`` is given (a flag each);
    #: None: all of them
    visits: Optional[Tuple[bool, ...]] = None


def expert_counters(moe_visits, choices_per_pass: int) -> Counted:
    """:data:`LANE_COUNTERS` over the visits that have experts
    (``moe_visits``: a flag a visit), their counters those of
    :func:`moe_held_experts`; ``choices_per_pass`` the token-choices of one
    expert layer a pass."""
    def reduce(moe, _, n_val):
        return [
            moe[:, 0].sum() / max(moe.shape[0] * n_val * choices_per_pass, 1),
            moe[:, 1].mean() / n_val if moe.shape[0] else jnp.float32(0.0),
            moe[:, 2].sum() / jnp.maximum(moe[:, 0].sum(), 1.0),
        ]

    return Counted(LANE_COUNTERS, reduce, tuple(bool(m) for m in moe_visits))


def _stacked(visits, counters):
    """What the visits of a pass count, those that count anything."""
    if not any(visit.counted for visit in visits):
        return None
    return jnp.stack([c for visit, c in zip(visits, counters) if visit.counted])


def _exit_states(hs, exits: Exits):
    return tuple(hs[n] for n in exits.after)


def _loss(params: dict, tokens, visits, exits: Exits):
    """``tokens`` i32[T + 1] (or a record, :class:`Exits`) -> ``(the trained loss, ((the reported loss,
    the exits' counters), the visits' counters, :func:`_stacked`))``
    through ``visits`` (a :class:`Visit` each); for ``jax.grad``: each
    visit's inside is recomputed in the backward pass, and a leaf that
    several visits take gets the sum of their gradients from the
    differentiation itself."""
    hs, counters = [_embed(params, tokens, exits)], []
    for visit in visits:
        h, c, _ = _visit_forward(
            visit, hs[-1], params[visit.leaf], jax.checkpoint(visit.through))
        hs.append(h)
        counters.append(c)
    at = (_exit_states(hs, exits), tuple(params[n] for n in exits.leaves), tokens)
    return exits.trained(*at), (exits.reported(*at), _stacked(visits, counters))


def _forward(params: dict, tokens, visits, exits: Exits):
    """:func:`_loss` with nothing kept for a gradient but the input of
    every visit: ``(the reported loss, (the visits' counters, the
    exits'), [h_0 .. h_V], what each visit's backward pass reads)``. An
    evaluation takes the gradient from these by the chain rule, a visit at
    a time, each visit's inside computed again (what ``jax.grad`` does with
    ``jax.checkpoint`` around every visit), so that a pass that needs no
    gradient (a held-out sequence) is the same trace as one that does."""
    with jax.named_scope("pass.forward"):
        hs, counters, kept = [_embed(params, tokens, exits)], [], []
        for visit in visits:
            h, c, inputs = _visit_forward(visit, hs[-1], params[visit.leaf])
            hs.append(h)
            counters.append(c)
            kept.append(inputs)
        loss, counted = exits.reported(
            _exit_states(hs, exits), tuple(params[n] for n in exits.leaves), tokens)
        return loss, (_stacked(visits, counters), counted), hs, kept


# ------------------------------------------------------------------- data
def make_token_dataset(key: jax.Array, cfg):
    """``(train i32[n_train, T + 1], val i32[n_val, T + 1])``: ids over
    the vocabulary slice (``cfg.vocab_rows``), Zipf-distributed (``p(rank
    r) ~ 1 / r``, by inverse CDF from uniform draws), the second half of
    each sequence repeating its first, so that a lane predicts it only
    through state and attention."""
    cdf = np.cumsum(1.0 / np.arange(1, cfg.vocab_rows + 1, dtype=np.float64))
    cdf = jnp.asarray((cdf / cdf[-1]).astype(np.float32))
    half = cfg.seq_len // 2 + 1

    def draw(k, n):
        ids = jnp.searchsorted(cdf, jax.random.uniform(k, (n, half)))
        ids = jnp.minimum(ids, cfg.vocab_rows - 1).astype(jnp.int32)
        return jnp.concatenate([ids, ids[:, :cfg.seq_len + 1 - half]], axis=1)

    kt, kv = jax.random.split(key)
    return draw(kt, cfg.n_train), draw(kv, cfg.n_val)


# ------------------------------------------------------------- evaluation
def _pass(p: dict, v: dict, seq, training, visits, exits: Exits, update):
    """One sequence through the lane: ``-> (p, v, the reported loss, the
    counters of :func:`_forward`)``. Every pass runs the forward trace; where
    ``training`` (a traced flag) it runs the backward one and ``update(p_leaf,
    v_leaf, g_leaf) -> (p_leaf, v_leaf)`` besides, else ``p`` and ``v`` come
    back as they are. The backward pass and the update are a ``lax.cond`` a
    visit (the exits, each visit from the last, the embedding): parameters
    and momentum through ONE ``cond`` would be held twice over, and of the
    leaves that one visit takes all the gradients would be alive at once.

    A visit's gradient is taken from the visit alone, its inside computed
    again; a leaf that several visits share (layers run several times with
    one set of weights) is **stepped once, where the backward pass leaves
    its first visit**, by the sum of its visits' gradients: until then
    every earlier visit still differentiates through the weights as they
    were. The exits' leaves are differentiated once, through all the exits
    together, and each exit's cotangent enters the chain where its state
    was read. A head tied to the embedding (``embed`` among the exits'
    leaves) is one leaf read twice a pass: its gradient as the head is kept
    to the end of the backward pass, the lookup's is added into it, and the
    leaf is stepped once, in ``embed_step``, by the sum."""
    n_visits = len(visits)
    first_visit = {}
    for j, visit in enumerate(visits):
        first_visit.setdefault(visit.leaf, j)
    shared = {visit.leaf for j, visit in enumerate(visits) if first_visit[visit.leaf] != j}
    loss, counters, hs, kept = _forward(p, seq, visits, exits)
    p, v = dict(p), dict(v)

    def if_training(step, *state, out=None):
        """``step(*state)`` in a training pass; else ``state`` as it is (of
        it the places ``out``, where ``step`` hands back fewer)."""
        same = (lambda *same: same) if out is None else (
            lambda *same: tuple(same[i] for i in out))
        return jax.lax.cond(training, step, same, *state)

    n_exits = len(exits.after)

    def unwritten(tree):
        return jax.tree.map(lambda x: jax.lax.empty(x.shape, x.dtype), tree)

    # an exits' leaf that is stepped elsewhere (``embed``, the tied head: in
    # ``embed_step``) is differentiated here and its gradient handed on;
    # where a stepped leaf has its momentum it has a place for that gradient
    stepped_here = [leaf != "embed" for leaf in exits.leaves]

    def exits_step(*state):
        pv = state[n_exits:]
        with jax.named_scope("pass.backward"):
            d_states, grads = jax.grad(exits.trained, argnums=(0, 1))(
                _exit_states(hs, exits), pv[::2], seq)
        stepped = [update(*pvg) if here else pvg[2:]
                   for here, pvg in zip(stepped_here, zip(pv[::2], pv[1::2], grads))]
        return tuple(d_states) + tuple(x for pair in stepped for x in pair)

    def exits_same(*state):
        # a held-out pass reads no gradient: the place of one as the device finds it
        kept = [pair if here else (unwritten(pair[0]),) for here, pair in zip(
            stepped_here, zip(state[n_exits::2], state[n_exits + 1::2]))]
        return state[:n_exits] + tuple(x for pair in kept for x in pair)

    stepped = list(jax.lax.cond(
        training, exits_step, exits_same,
        *[jnp.zeros_like(h) for h in _exit_states(hs, exits)],
        *[x for leaf in exits.leaves for x in (p[leaf], v[leaf])]))
    # exit ``e`` read the state after ``exits.after[e]`` visits
    exit_after = {n: e for e, n in enumerate(exits.after)}
    d_exits, stepped = stepped[:n_exits], stepped[n_exits:]
    as_head = ()    # a tied head's gradient, for ``embed_step``
    for here, leaf in zip(stepped_here, exits.leaves):
        if here:
            p[leaf], v[leaf] = stepped.pop(0), stepped.pop(0)
        else:
            as_head = (stepped.pop(0),)
    dh = d_exits[exit_after[n_visits]]
    # the sum of a shared leaf's gradients over its visits so far: the
    # leaf's last visit, the backward pass's first, writes it whole (a fill
    # of zeros to add to is a pass over the leaf that lands in no part)
    sums = {}
    last_visit = {visit.leaf: j for j, visit in enumerate(visits)}

    def add(total, g):
        with jax.named_scope("lane.accumulate"):
            return jax.tree.map(jnp.add, total, g)

    def start_it(pull, dh, total, _):
        """A shared leaf's last visit: its gradient starts the sum."""
        dh, g = pull(dh)
        with jax.named_scope("lane.accumulate"):
            return dh, jax.tree.map(lambda t, gi: gi.astype(t.dtype), total, g)

    def step_it(pull, dh, pv, total):
        """A leaf's first visit: its gradient is whole, step it."""
        dh, g = pull(dh)
        return dh, update(*pv, g if total is None else add(total, g))

    def sum_it(pull, dh, total, _):
        """A later visit of a shared leaf: into the sum."""
        dh, g = pull(dh)
        return dh, add(total, g)

    for j in reversed(range(n_visits)):
        visit, leaf = visits[j], visits[j].leaf
        back = functools.partial(
            _visit_backward, visit, kept=kept[j],
            scope="lane.accumulate" if first_visit[leaf] != j else "lane.update")
        if leaf in shared and last_visit[leaf] == j:
            dh, sums[leaf] = jax.lax.cond(
                training,
                lambda dh, pl, back=back: back(start_it, dh, p=pl, written=unwritten(pl)),
                lambda dh, pl: (dh, unwritten(pl)), dh, p[leaf])
        elif first_visit[leaf] != j:
            dh, sums[leaf] = if_training(
                lambda dh, total, pl, back=back: back(sum_it, dh, p=pl, written=total),
                dh, sums[leaf], p[leaf], out=(0, 1))
        elif leaf in shared:
            def visit_step(dh, total, pl, vl, back=back):
                dh, (pl, vl) = back(step_it, dh, p=pl, written=(pl, vl), read=total)
                return dh, pl, vl

            dh, p[leaf], v[leaf] = if_training(
                visit_step, dh, sums.pop(leaf), p[leaf], v[leaf], out=(0, 2, 3))
        else:
            def visit_step(dh, pl, vl, back=back):
                dh, (pl, vl) = back(step_it, dh, p=pl, written=(pl, vl))
                return dh, pl, vl

            dh, p[leaf], v[leaf] = if_training(visit_step, dh, p[leaf], v[leaf])
        if j in exit_after:
            # an earlier exit read the state this visit took in
            dh = dh + d_exits[exit_after[j]]

    tied = not all(stepped_here)

    def embed_step(pe, ve, *g_head):
        # a tied head's gradient is what the lookup's is added into
        with jax.named_scope("pass.backward"), jax.named_scope("lane.head"):
            (start,) = g_head if tied else (jnp.zeros_like(pe),)
            g = start.at[_entry(seq, exits)].add(dh)
        return update(pe, ve, g)

    p["embed"], v["embed"] = if_training(
        embed_step, p["embed"], v["embed"], *as_head, out=(0, 1) if tied else None)
    return p, v, loss, counters


def make_lane_eval_fn(*, init, visits, exits: Exits, data, lane_bytes: int,
                      counted: Counted, static_counters=(),
                      tokens_per_step: Optional[int] = None):
    """``eval_fn(config_vec, budget) -> held-out loss`` of a lane, handed to
    ``FusedBOHB(eval_fn=...)`` as ``make_transformer_eval_fn``'s is. Budget
    is momentum-SGD steps of one sequence; step ``t`` trains on sequence
    ``t mod n_train``; ``v <- m v + g + wd p; p <- p - lr v``.
    ``eval_fn.lane_facts`` states the lane's footprint, its tokens a step and
    its device counters (``counted.names``, then ``static_counters``), which
    the rung's evaluation (``ops.fused.eval_lanes``) reads;
    ``eval_fn.change(config_vec, budget)`` is what the steps changed, the
    parameters after them less the parameters at initialisation.

    The model is what it hands over:

    * ``init`` (:class:`Init`): ``init(init_scale) -> params``, ``embed`` and
      what the visits and the exits name, from the configuration's key, and
      its two halves, the draw that no hyperparameter changes
      (``lane_facts.shared``) and its scaling;
    * ``visits``: a :class:`Visit` each, a pass in order. A plain stack
      visits every layer once (:func:`once_through`); a leaf may be visited
      several times a pass (layers run several times with one set of
      weights): :func:`_pass` sums its gradient over the visits and steps it
      once;
    * ``exits`` (:class:`Exits`): where a pass's state is read, the loss
      that is trained and the loss that is reported (a plain stack:
      :func:`head_exit`);
    * ``data = (train, val)`` of :func:`make_token_dataset`, or two trees
      of arrays with the sequences along their first axes (a sequence is
      then a record, one slice of every array: ``exits.entry`` and the
      exits' losses read it) and ``tokens_per_step`` the data tokens of one;
      ``lane_bytes`` the device bytes a lane needs while it trains;
    * ``counted`` (:class:`Counted`): what the lane counts on the device
      over its held-out passes (a lane with experts:
      :func:`expert_counters`);
    * ``static_counters``: ``((name, value), ...)`` facts of how the lane
      is computed that ride beside the counted ones."""
    train, val = data
    n_train, n_val = (jax.tree.leaves(tree)[0].shape[0] for tree in (train, val))
    if tokens_per_step is None:
        tokens_per_step = train.shape[1] - 1
    if exits.after[-1] != len(visits) or list(exits.after) != sorted(set(exits.after)):
        raise ValueError("the last exit reads the last visit's state; exits in order")

    counting = [visit.counted for visit in visits if visit.counted]
    if len(set(counting)) > 1:
        raise ValueError("the visits that count anything count as many numbers each")

    def passes(vec: jax.Array, budget, held_out: int, shared=None):
        """``budget`` training passes, then ``held_out`` held-out ones: ``->
        (the parameters at initialisation, after the passes, the held-out
        losses' sum, what the held-out passes counted)``. ``shared``: the
        unit draws (``init.shared()``) made by the caller, once for all its
        evaluations; without them the evaluation draws for itself."""
        lr, momentum, wd, init_scale = decode_lane_hparams(vec)
        params = init(init_scale) if shared is None else init.scale(shared, init_scale)
        steps = jnp.asarray(budget, jnp.float32).round().astype(jnp.int32)

        def update(p, v, g):
            with jax.named_scope("lane.update"):
                v = jax.tree.map(lambda vi, gi, pi: momentum * vi + gi + wd * pi, v, g, p)
                return jax.tree.map(lambda pi, vi: pi - lr * vi, p, v), v

        # ONE loop over the training sequences and then the held-out ones:
        # each pass runs the forward trace, and a training pass the backward
        # one and the update besides (:func:`_pass`), so the program holds
        # the forward pass once for both (a quarter of its compilation)
        def one_pass(t, carry):
            p, v, held_loss, held_counters = carry
            training = t < steps
            seq = jax.tree.map(
                lambda train, val: jnp.where(
                    training, train[t % n_train], val[jnp.clip(t - steps, 0, n_val - 1)]),
                train, val)
            p, v, loss, counters = _pass(p, v, seq, training, visits, exits, update)
            held = jnp.where(training, 0.0, 1.0)
            return p, v, held_loss + held * loss, jax.tree.map(
                lambda total, c: total + held * c, held_counters, counters)

        after, _, loss, counters = jax.lax.fori_loop(
            0, steps + held_out, one_pass, (
                params, jax.tree.map(jnp.zeros_like, params), jnp.float32(0.0),
                (jnp.zeros((len(counting), counting[0]), jnp.float32) if counting else None,
                 jnp.zeros((exits.counted,), jnp.float32) if exits.counted else None)))
        return params, after, loss, counters

    def with_counters(vec: jax.Array, budget, shared=None):
        _, _, loss, (visit_counters, exit_counters) = passes(vec, budget, n_val, shared)
        loss = loss / n_val
        if counted.visits is not None:
            visit_counters = visit_counters[np.asarray(counted.visits, bool)]
        # a lane whose training diverged has no number for a loss: it
        # reports the worst one, infinity; NaN is the sweep's mask for a crash
        return jnp.where(jnp.isnan(loss), jnp.inf, loss), jnp.stack(
            list(counted.reduce(visit_counters, exit_counters, n_val))
            + [jnp.float32(value) for _, value in static_counters])

    def change(vec: jax.Array, budget):
        """What ``budget`` steps change: the parameters after them less the
        parameters at initialisation, leaf by leaf (a check of the trainer
        against a reference reads it: a loss hardly tells a step that went
        wrong from rounding, the step itself does)."""
        params, after, _, _ = passes(vec, budget, 0)
        return jax.tree.map(jnp.subtract, after, params)

    def eval_fn(vec: jax.Array, budget, shared=None) -> jax.Array:
        return with_counters(vec, budget, shared)[0]

    eval_fn.lane_facts = LaneFacts(
        bytes=lane_bytes, tokens_per_step=tokens_per_step,
        counters=tuple(counted.names) + tuple(name for name, _ in static_counters),
        with_counters=with_counters, traced_budget=True, shared=init.shared)
    eval_fn.change = change
    return eval_fn
