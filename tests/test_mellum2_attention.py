"""The Mellum2 lane's attention, on the CPU at a small size
(``mellum2_small.py``): the blocked attention against a full masked softmax,
the fused kernels (Pallas, interpreted here) against the plain form, and the
rotary tables against numbers worked by hand. ``tests/test_mellum2.py``'s
three attention sections, in a file of their own so that under ``--dist
loadfile`` no one worker runs all of the lane's cases (ISSUE 52).

Where a test holds the equations to the reference it sets the lanes'
matrix-product operands to float32 (``lane._OPERAND``): then only the order
of float32 sums differs, and the tolerances say so. Where it runs the lane
as the chip does (bfloat16 operands), the tolerance is bfloat16's.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads import mellum2 as M

from mellum2_small import BENCHMARK, SMALL, load


@pytest.fixture(scope="module")
def reference():
    return load("reference", "mellum2-sgd.py")


@pytest.fixture(scope="module")
def builders():
    # the builders import the harness's ``program`` by that name
    sys.modules.setdefault("program", load("program.py"))
    return {"mellum2": load("configs", "mellum2-sgd.py").lane_config}


@pytest.fixture
def float32_operands(monkeypatch):
    monkeypatch.setattr(lane, "_OPERAND", jnp.float32)


def _cfg(builders, config):
    return builders["mellum2"](config)._replace(attn_query_block=16)


# ------------------------------------------------------------- attention
def _full_masked_softmax(q, k, v, window):
    """The whole ``T x T`` square, key/value heads repeated outright."""
    t, g, r, d = q.shape
    k, v = (jnp.repeat(y, r, axis=1) for y in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q.reshape(t, g * r, d), k) / math.sqrt(d)
    at, key = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = key <= at
    if window is not None:
        seen &= at - key < window
    att = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", att, v).reshape(t, g, r, d)


def _qkv(length, seed=0, g=2, r=4, d=16):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (length, g, r, d)),
            jax.random.normal(keys[1], (length, g, d)),
            jax.random.normal(keys[2], (length, g, d)))


@pytest.mark.parametrize("length", [64, 70, 9])
@pytest.mark.parametrize("window", [None, 8, 1, 64, 1000])
def test_blocked_attention_is_the_full_masked_softmax(float32_operands, window, length):
    """Windows from one position to longer than the sequence, lengths that
    are and are not multiples of the block of 16, the gradient too."""
    q, k, v = _qkv(length, seed=length)
    got = M.banded_attention(q, k, v, window, 16)
    want = _full_masked_softmax(q, k, v, window)
    np.testing.assert_allclose(got, want, atol=2e-6)
    ours = jax.grad(lambda *x: (M.banded_attention(*x, window, 16) ** 2).sum(), (0, 1, 2))
    theirs = jax.grad(lambda *x: (_full_masked_softmax(*x, window) ** 2).sum(), (0, 1, 2))
    for g, w in zip(ours(q, k, v), theirs(q, k, v)):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("window", [8, 1, 17])
def test_a_position_never_sees_past_its_window(float32_operands, window):
    """Perturb the key and value at ``j``: no output at ``i`` with ``i - j
    >= window`` moves, nor any before ``j``; those inside the window do."""
    q, k, v = _qkv(64, seed=3)
    base = M.banded_attention(q, k, v, window, 16)
    j = 20
    moved = M.banded_attention(
        q, k.at[j].add(5.0), v.at[j].add(-3.0), window, 16)
    changed = np.asarray(jnp.abs(moved - base).max(axis=(1, 2, 3)) > 0)
    assert changed[j:j + window].all()
    assert not changed[:j].any() and not changed[j + window:].any()


def test_sharing_a_key_value_head_is_repeating_it(float32_operands):
    q, k, v = _qkv(40, seed=5)          # 2 key/value heads, 4 query heads each
    shared = M.banded_attention(q, k, v, 8, 16)
    each_its_own = M.banded_attention(
        q.reshape(40, 8, 1, 16), jnp.repeat(k, 4, axis=1), jnp.repeat(v, 4, axis=1), 8, 16)
    np.testing.assert_allclose(shared.reshape(40, 8, 16), each_its_own[:, :, 0], atol=1e-6)


@pytest.mark.parametrize("window", [None, 8])
def test_key_value_heads_at_once_do_not_change_the_result(float32_operands, window):
    """One key/value head at a time, two (a remainder of one), all four."""
    q, k, v = _qkv(40, seed=7, g=4, r=2)
    one_at_a_time = M.banded_attention(q, k, v, window, 16)
    a_block = 2 * 16 * (40 if window is None else 24)   # R x queries x widest keys
    for scores_at_once in (2 * a_block, 3 * a_block, 10 ** 9):
        np.testing.assert_allclose(
            M.banded_attention(q, k, v, window, 16, scores_at_once), one_at_a_time, atol=1e-6)


def test_blocks_outside_the_band_are_never_computed():
    """Static facts of the blocking: at the published size three window
    layers compute 15 blocks of keys each (the first block of queries one,
    the others two) and the full layer 36, of 64 a square; the lane's
    facts carry them beside the counted ones."""
    windows = [1024, 1024, 1024, None]
    assert M.attention_key_blocks(8192, windows, 1024) == (3 * 15 + 36, 4 * 64)
    assert M.attention_key_blocks(8192, [1024], 1024) == (15, 64)
    assert M.attention_key_blocks(8192, [None], 1024) == (36, 64)
    # whatever the length, a window layer's products are two blocks wide
    assert max(khi - klo for _, _, ((klo, khi),) in M._attention_spans(32768, 1024, 1024)) == 2048
    assert max(khi - klo for _, _, ((klo, khi),) in M._attention_spans(8192, None, 1024)) == 8192
    cfg = M.Mellum2Config(seq_len=64, n_train=2, n_val=1, vocab_rows=96, hidden_size=32,
                          num_heads=4, num_kv_heads=2, head_dim=8, sliding_window=8,
                          moe_intermediate_size=16, attn_query_block=16)
    facts = M.make_mellum2_eval_fn(cfg).lane_facts
    assert facts.counters == (lane.LANE_COUNTERS + M.ATTENTION_COUNTERS
                              + ("attn_scores_in_vmem", "attn_rotation_in_vmem")
                              + tuple(name for name, _ in lane.MOE_COUNTERS)
                              + ("moe_products_in_vmem",))
    assert facts.traced_budget and facts.tokens_per_step == 64
    # queries in 4 blocks: a window of 8 reaches one block back, 1 + 3 x 2;
    # the full layer 1 + 2 + 3 + 4
    assert M.attention_key_blocks(64, [8, 8, 8, None], 16) == (3 * 7 + 10, 4 * 16)


# ----------------------------------------------- the fused kernels (Pallas)
def _kernel_qkv(length, g, r, d=128):
    return _qkv(length, seed=length + r, g=g, r=r, d=d)


_DIFFUSION = lane.BlockDiffusion(4)


_KERNEL_HEADS = [
    (2, 1, 128), (1, 8, 128),
    # heads of 64, two key/value heads side by side in a tile of lanes: one
    # pair and its eight query heads (a 128-lane slice of the block holds
    # two query heads of ONE key/value head), two pairs of one query head
    # each (a slice holds a query head of each), four pairs
    (2, 4, 64), (4, 1, 64), (8, 1, 64),
]
_CAUSAL_CASES = [
    # float32 operands: the plain form's float32 sums in another order
    (jnp.float32, 2e-5, 128, None, (128, 128)),    # a sequence of one tile
    (jnp.float32, 2e-5, 384, None, (128, 128)),    # of several: 1 + 2 + 3 tiles of keys
    (jnp.float32, 2e-5, 384, 128, (128, 128)),     # a window that is a multiple of the tile
    (jnp.float32, 2e-5, 384, 100, (128, 128)),     # and one that is not
    (jnp.float32, 2e-5, 256, 200, (64, 128)),      # blocks of queries narrower than a tile
    (jnp.float32, 2e-5, 256, 1, (64, 128)),        # a position sees itself alone
    # as the chip runs it: both products' operands rounded to bfloat16 (8
    # bits of mantissa, 2^-9 = 2e-3 an operand; the kernel rounds the
    # softmax's terms before their sum is divided out, the plain form after)
    (jnp.bfloat16, 2e-2, 384, None, (128, 128)),
    (jnp.bfloat16, 2e-2, 384, 100, (128, 128)),
    (jnp.bfloat16, 2e-2, 512, 200, (64, 256)),
]
_DIFFUSION_CASES = [
    # the block-diffusion rule of sight over 2 x 256 rows in diffusion
    # blocks of 4: a block of queries narrower than a tile of keys, as wide,
    # wider; and one of whole tiles of lanes under a wider tile of keys,
    # whose masked blocks walk their own keys as a tile of their own width
    (jnp.float32, 2e-5, 512, _DIFFUSION, (64, 128)),
    (jnp.float32, 2e-5, 512, _DIFFUSION, (128, 128)),
    (jnp.float32, 2e-5, 512, _DIFFUSION, (256, 128)),
    (jnp.float32, 2e-5, 512, _DIFFUSION, (128, 256)),
    (jnp.bfloat16, 2e-2, 512, _DIFFUSION, (64, 128)),
    (jnp.bfloat16, 2e-2, 512, _DIFFUSION, (128, 256)),
]


@pytest.mark.parametrize(
    "operand, limit, length, sight, tiles, g, r, d",
    [case + heads for case in _CAUSAL_CASES for heads in _KERNEL_HEADS]
    # under the rule: heads of 128, one and eight to a key/value head, and a
    # pair of heads of 64 with its eight query heads
    + [case + heads for case in _DIFFUSION_CASES for heads in _KERNEL_HEADS[:3]])
def test_the_fused_kernels_are_the_plain_form(monkeypatch, operand, limit, length,
                                              sight, tiles, g, r, d):
    """``ops.pallas_attention`` in the Pallas interpreter against
    ``banded_attention``'s plain JAX under the same rule of sight (a window
    or ``None``: causal; the block-diffusion rule), heads of 128 and pairs
    of heads of 64: the values and the gradients with respect to ``q``,
    ``k`` and ``v``, each within ``limit`` of the largest entry (of one
    where the plain form gives all zeros: the queries' gradient when a
    position sees itself alone)."""
    from hpbandster_tpu.ops import pallas_attention

    monkeypatch.setattr(lane, "_OPERAND", operand)
    q, k, v = _kernel_qkv(length, g, r, d)
    tiles, rule = pallas_attention.Tiles(*tiles), lane._rule(sight)
    assert pallas_attention.fits(length, d, r, g, tiles) and rule.whole_tiles(length, tiles)
    t = length
    flat = lambda x: x.reshape(t, -1)     # the kernels take the heads side by side
    fused = lambda q, k, v: pallas_attention.fused_banded_attention(
        flat(q), flat(k), flat(v), (g, r, d), rule, tiles, operand, "lane.swa", True
    ).reshape(q.shape)
    plain = lambda q, k, v: lane.banded_attention(q, k, v, sight, 64)
    weigh = jax.random.normal(jax.random.key(1), q.shape)
    got, pull = jax.vjp(fused, q, k, v)
    want, pull_plain = jax.vjp(plain, q, k, v)
    for ours, theirs in zip((got,) + pull(weigh), (want,) + pull_plain(weigh)):
        assert ours.shape == theirs.shape and ours.dtype == theirs.dtype
        np.testing.assert_allclose(
            ours, theirs, atol=limit * max(float(jnp.abs(theirs).max()), 1.0))


@pytest.mark.parametrize("operand, limit", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("window", [None, 100])
def test_a_pair_of_heads_leaks_nothing_between_its_halves(monkeypatch, operand, limit, window):
    """Two key/value heads of 64 in one tile of lanes, the second's keys,
    values and queries a thousand times the first's: every head's output
    and gradients are the plain form's within ``limit`` of THAT HEAD's
    largest entry (a thousandth of the second head in the first's half
    would be as large as the first itself), and what is pulled back through
    the first head's queries alone reaches nothing of the second head, to
    the last bit."""
    from hpbandster_tpu.ops import pallas_attention

    monkeypatch.setattr(lane, "_OPERAND", operand)
    t, g, r, d = 256, 2, 2, 64
    tiles = pallas_attention.Tiles(64, 128)
    loud = jnp.asarray([1.0, 1000.0])
    q, k, v = _kernel_qkv(t, g, r, d)
    # the scores stay the same size (the queries' gain in the keys' place
    # would saturate the softmax): the values and the weights carry it
    v = v * loud[None, :, None]
    flat = lambda x: x.reshape(t, -1)
    fused = lambda q, k, v: pallas_attention.fused_banded_attention(
        flat(q), flat(k), flat(v), (g, r, d), lane.Causal(window), tiles, operand, "lane.gqa",
        True).reshape(q.shape)
    plain = lambda q, k, v: lane.banded_attention(q, k, v, window, 64)
    weigh = jax.random.normal(jax.random.key(2), q.shape) * loud[None, :, None, None]
    got, pull = jax.vjp(fused, q, k, v)
    want, pull_plain = jax.vjp(plain, q, k, v)
    for ours, theirs in zip((got,) + pull(weigh), (want,) + pull_plain(weigh)):
        for head in range(g):
            np.testing.assert_allclose(
                ours[:, head], theirs[:, head],
                atol=limit * max(float(jnp.abs(theirs[:, head]).max()), 1.0))
    first_alone = weigh.at[:, 1].set(0.0)
    dq, dk, dv = pull(first_alone)
    for of_the_second in (dq[:, 1], dk[:, 1], dv[:, 1]):
        np.testing.assert_array_equal(of_the_second, 0.0)
    assert float(jnp.abs(dk[:, 0]).max()) > 0 and float(jnp.abs(dv[:, 0]).max()) > 0


@pytest.mark.parametrize("t, d, r, g, taken", [
    (8192, 128, 8, 4, True), (2048, 128, 1, 16, True), (8192, 128, 8, 3, True),
    # heads of 64 in pairs: the LFM2 lane's (4 pairs of 4 query heads each)
    (8192, 64, 4, 8, True), (8192, 64, 1, 2, True),
    # no pair for the last head; no width between: 32 lanes, 96, 192
    (8192, 64, 4, 7, False), (8192, 64, 4, 1, False),
    (8192, 32, 4, 8, False), (8192, 96, 4, 8, False), (8192, 192, 4, 8, False),
    # a length that is no whole tile, keys that do not fit VMEM
    (8200, 64, 4, 8, False), (2 ** 16, 64, 4, 8, False),
])
def test_the_kernels_take_whole_tiles_of_lanes_or_pairs_of_64(t, d, r, g, taken):
    """``fits``: heads of a multiple of 128 lanes, or of 64 where the
    key/value heads pair up; nothing else."""
    from hpbandster_tpu.ops.pallas_attention import Tiles, fits

    assert fits(t, d, r, g, Tiles(128, 512)) == taken


def test_the_kernels_visit_the_band_and_one_tile(monkeypatch):
    """Static facts of the kernels' own range: a window layer's computed
    band is the window and one block of queries wide, the full layer's the
    triangle and its diagonal's tiles."""
    from hpbandster_tpu.ops.pallas_attention import Tiles, fits, tiles_visited

    # 64 blocks of 128 queries: keys from ``lo - 1,023`` to ``lo + 127``, in
    # tiles of 512 three (the first blocks fewer)
    assert tiles_visited(8192, lane.Causal(1024), Tiles(128, 512)) == 1 + 1 + 1 + 1 + 2 * 4 + 3 * 56
    assert tiles_visited(8192, lane.Causal(), Tiles(128, 512)) == 4 * sum(range(1, 17))
    assert tiles_visited(2048, lane.Causal(), Tiles(512, 512)) == 1 + 2 + 3 + 4
    assert tiles_visited(256, lane.Causal(1), Tiles(64, 128)) == 4
    # the block-diffusion rule over 2 x 4,096 rows: a clean block of queries
    # walks the causal triangle of its half (144 tiles), a masked one the
    # same clean tiles (the last of them masked) and its own 128 keys of the
    # masked copy, a quarter of a tile: 31.25 % of the square's 1,024 tiles
    # where the own keys are walked as a whole tile, 28.9 % as they are
    assert tiles_visited(8192, _DIFFUSION, Tiles(128, 512)) == 144 + 144 + 32 / 4 == 296
    # a block as wide as a tile of keys walks its own keys as that tile
    assert tiles_visited(8192, _DIFFUSION, Tiles(512, 512)) == 36 + 36 + 8
    # whole tiles of whole lanes, and a head's keys and values within VMEM
    assert fits(8192, 128, 8, 4, Tiles(128, 512)) and fits(2048, 128, 1, 16, Tiles(512, 512))
    assert not fits(8192, 64, 8, 3, Tiles(128, 512))
    assert not fits(8200, 128, 8, 4, Tiles(128, 512))
    assert not fits(2 ** 16, 128, 8, 4, Tiles(128, 512))


@pytest.mark.parametrize("sight", [None, 1, 100, 128, 200, 512, 1000,
                                   _DIFFUSION, lane.BlockDiffusion(32), lane.BlockDiffusion(64)])
@pytest.mark.parametrize("block_q, block_k", [(64, 128), (128, 128), (256, 128), (128, 512)])
def test_the_kernels_loops_cover_what_a_block_sees_once(sight, block_q, block_k):
    """The loops of a block of queries (``tile_loops`` of the rule of
    sight: what the kernels walk and ``tiles_visited`` counts), held against
    the pairs themselves (the rule's ``seen``): every key that a query of
    the block sees lies in exactly one walked tile, no walked tile holds
    none, a tile walked without a mask holds no hidden pair, and a masked
    tile's mask is the rule's own."""
    from hpbandster_tpu.ops.pallas_attention import Tiles, tiles_visited

    t, tiles, rule = 1024, Tiles(block_q, block_k), lane._rule(sight)
    assert rule.whole_tiles(t, tiles)
    seen = np.asarray(rule.seen(jnp.arange(t)[:, None], jnp.arange(t)[None, :], t))
    walked_keys = 0
    for lo in range(0, t, block_q):
        block, covered = seen[lo:lo + block_q], np.zeros(t, int)
        for first, end, width, mask in rule.tile_loops(lo, tiles, t):
            for klo in range(first * width, end * width, width):
                covered[klo:klo + width] += 1
                tile = block[:, klo:klo + width]
                assert tile.any()
                if mask is None:
                    assert tile.all()
                else:
                    np.testing.assert_array_equal(mask(klo), tile)
        assert covered.max() == 1 and (covered[block.any(axis=0)] == 1).all()
        walked_keys += covered.sum()
    assert tiles_visited(t, rule, tiles) == walked_keys / block_k


def test_off_the_chip_the_plain_form_runs_and_the_counter_says_so(monkeypatch):
    """The rule (``lane._kernel_tiles``) reads the backend and the shapes,
    nothing else: on the CPU the plain form whatever the shape, and
    ``attn_scores_in_vmem`` is 0; told that Mosaic compiles here, the
    kernels at the Mellum2 lane's published size, and the plain form where
    the keys are few (the Ouro lane's 2,048) or a shape does not fit the
    kernels' tiles."""
    published = [(8192, 128, 8, 4), (2048, 128, 1, 16), (8192, 64, 4, 8)]
    for t, d, r, g in published:
        assert lane._kernel_tiles(t, d, r, g) is None
        assert lane.attention_counters(t, d, r, g) == (
        ("attn_scores_in_vmem", 0.0), ("attn_rotation_in_vmem", 0.0))
    blocks = lane.attention_key_blocks(8192, [1024, 1024, 1024, None], 1024)
    bytes_plain = lane.attention_alive_bytes(8192, 4, 8, 128, [1024, None], 1024)
    assert bytes_plain == 3 * 4 * 8 * 1024 * 8192

    monkeypatch.setattr(lane, "pallas_available", lambda: True)
    # 8 heads x 128 queries against 512 keys; one head's 512 queries, no
    # wider than a tile of keys
    assert lane._kernel_tiles(8192, 128, 8, 4) == (128, 512)
    assert lane._kernel_tiles(4096, 128, 1, 16) == (512, 512)
    assert lane.attention_counters(8192, 128, 8, 4) == (
        ("attn_scores_in_vmem", 1.0), ("attn_rotation_in_vmem", 1.0))
    # a pair of heads of 64 a step: the block of queries is sized from the
    # pair's 2 x 4 query heads, the rows a step really holds
    assert lane._kernel_tiles(8192, 64, 4, 8) == (128, 512)
    assert lane._kernel_tiles(8192, 64, 1, 2) == (512, 512)
    # few keys (a block's scores stay on the plain softmax's fast path), the
    # tests' lanes (heads of 8 and 16), heads of 64 of which one has no pair,
    # a length that is no whole tile, a sequence whose keys do not fit VMEM
    for t, d, r, g in [(2048, 128, 1, 16), (64, 8, 2, 2), (8192, 64, 8, 3), (8200, 128, 8, 4),
                       (2 ** 16, 128, 8, 4)]:
        assert lane._kernel_tiles(t, d, r, g) is None
        assert lane.attention_counters(t, d, r, g) == (
        ("attn_scores_in_vmem", 0.0), ("attn_rotation_in_vmem", 0.0))
    # the footprint and the counted tiles follow the path that runs: the
    # kernels keep an output and a log-sum-exp a row, no block of scores
    assert lane.attention_alive_bytes(8192, 4, 8, 128, [1024, None], 1024) == (
        4 * 8192 * 32 * (128 + 128)) < bytes_plain
    computed, square = lane.attention_key_blocks(
        8192, [1024, 1024, 1024, None], 1024, lane._kernel_tiles(8192, 128, 8, 4))
    assert computed / square < blocks[0] / blocks[1]


# ----------------------------------------------------------------- rotary
def test_yarn_against_numbers_worked_by_hand(reference, builders):
    cfg = M.Mellum2Config()
    # c(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000): c(32) = 18.08, c(1) = 34.98
    assert M.yarn_correction_range(cfg) == (18, 35)
    plain, one = M.rotary_inv_freq(cfg, "sliding")
    yarn, factor = M.rotary_inv_freq(cfg, "full")
    assert one == 1.0 and factor == pytest.approx(0.1 * math.log(16) + 1.0, abs=1e-12)
    theta = 500000.0
    np.testing.assert_allclose(plain[[0, 1, 63]], [1.0, theta ** (-2 / 128), theta ** (-126 / 128)])
    # below the ramp the frequency is kept, above it divided by 16, on it mixed
    np.testing.assert_allclose(yarn[:19], plain[:19])
    np.testing.assert_allclose(yarn[35:], plain[35:] / 16)
    ramp = (26 - 18) / (35 - 18)
    assert yarn[26] == pytest.approx((1 - ramp) * plain[26] + ramp * plain[26] / 16)
    # the configuration's file gives the same tables as the reference builds
    published = json.load(open(os.path.join(BENCHMARK, "configs", "mellum2-sgd.json")))
    built = builders["mellum2"](published)
    assert built == cfg
    for kind in ("sliding", "full"):
        for ours, theirs in zip(M._rotary_tables(built, kind, 40),
                                reference.rotary(published, kind, 40)):
            np.testing.assert_allclose(ours, theirs, atol=1e-6)
    assert reference.yarn_range(published["rope_parameters"]["full_attention"], 128) == (18, 35)
    # the small configuration has a ramp too: c(4) = 1.6, c(1) = 4.03
    assert M.yarn_correction_range(_cfg(builders, SMALL)) == (1, 5)


def test_rotation_keeps_norms_and_depends_on_distance_alone():
    cfg = M.Mellum2Config(head_dim=16)
    cos, sin = M._rotary_tables(cfg, "sliding", 32)
    x = jax.random.normal(jax.random.key(0), (16,))
    rows = M._rotate(jnp.broadcast_to(x, (32, 1, 16)), cos, sin)[:, 0]
    np.testing.assert_allclose(jnp.linalg.norm(rows, axis=-1), jnp.linalg.norm(x), rtol=1e-5)
    # the same vector at positions i and j: the product depends on i - j
    np.testing.assert_allclose(rows[3] @ rows[10], rows[20] @ rows[27], rtol=1e-4)


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_heads_side_by_side_turn_as_heads_apart(kind):
    """``lane._rotate_side_by_side`` on ``[T, heads x d]`` is ``_rotate`` on
    ``[T, heads, d]`` to the last bit, and so is its gradient: the same
    products and sums an entry."""
    cfg = M.Mellum2Config(head_dim=16)
    t, heads = 24, 6
    cos, sin = M._rotary_tables(cfg, kind, t)
    x = jax.random.normal(jax.random.key(2), (t, heads * 16))
    apart = lambda x: M._rotate(x.reshape(t, heads, 16), cos, sin).reshape(t, -1)
    beside = lambda x: lane._rotate_side_by_side(x, cos, sin, scope="lane.swa")
    np.testing.assert_array_equal(beside(x), apart(x))
    cube = lambda turn: jax.grad(lambda x: (turn(x) ** 3).sum())(x)
    np.testing.assert_array_equal(cube(beside), cube(apart))


@pytest.mark.parametrize("window, r, d, normed", [
    (None, 1, 128, False), (100, 4, 128, False),
    # heads of 64 in pairs, each head of the queries and of the keys through
    # its norm first (the LFM2 lane's layer); and heads of 128 under the norm
    (None, 4, 64, True), (100, 1, 64, True), (100, 4, 64, False), (None, 1, 128, True),
    # the block-diffusion rule of sight (the rows a clean and a masked copy,
    # the rotary tables at the rows' repeated positions): the SDAR lane's
    # layer, heads of 128 under the norm; and pairs of heads of 64
    (_DIFFUSION, 4, 128, True), (_DIFFUSION, 1, 128, False), (_DIFFUSION, 4, 64, True),
])
def test_the_mixer_with_the_kernels_is_the_mixer_without(monkeypatch, window, r, d, normed):
    """``attention_mixer`` as the chip runs it (the rule told that Mosaic
    compiles here, the kernels in the Pallas interpreter, heads side by side
    from the projections to ``wo``) against itself in plain JAX: the output
    and the gradients with respect to its input, its four matrices and,
    where the layer has them, the per-head norms' weights, within bfloat16
    operands' 2e-2 of the largest entry."""
    from hpbandster_tpu.ops import pallas_attention, pallas_rotary

    t, g, hidden = 256, 2, 64
    keys = jax.random.split(jax.random.key(4), 7)
    x = jax.random.normal(keys[0], (t, hidden))
    p = {name: jax.random.normal(key, shape) * shape[0] ** -0.5 for key, (name, shape) in zip(
        keys[1:], {"wq": (hidden, g * r * d), "wk": (hidden, g * d), "wv": (hidden, g * d),
                   "wo": (g * r * d, hidden)}.items())}
    if normed:
        p["q_norm"] = 1.0 + 0.3 * jax.random.normal(keys[5], (d,))
        p["k_norm"] = 1.0 + 0.3 * jax.random.normal(keys[6], (d,))
    mixer = lambda x, p: lane.attention_mixer(
        x, p, kv_heads=g, heads_per_kv=r, head_dim=d,
        inv_freq=10000.0 ** (-np.arange(0, d, 2) / d), factor=1.0, sight=window,
        block=64, scope="lane.swa", norm_eps=1e-5)
    weigh = jax.random.normal(jax.random.key(5), (t, hidden))
    want, pull = jax.vjp(mixer, x, p)
    want = (want,) + tuple(jax.tree.leaves(pull(weigh)))

    monkeypatch.setattr(lane, "pallas_available", lambda: True)
    monkeypatch.setattr(lane, "_KERNEL_ROWS", 128)
    monkeypatch.setattr(lane, "_KERNEL_KEYS", 128)
    monkeypatch.setattr(lane, "_PLAIN_KEYS", 0)
    in_interpreter = pallas_attention.fused_banded_attention
    calls = []
    monkeypatch.setattr(
        pallas_attention, "fused_banded_attention",
        lambda *args: calls.append(args[3:6]) or in_interpreter(*args, True))
    # the queries' and the keys' turn is the rotation's kernel on this path
    turn_in_interpreter = pallas_rotary.rotate_side_by_side
    turns = []
    monkeypatch.setattr(
        pallas_rotary, "rotate_side_by_side",
        lambda *args: turns.append(args[3:]) or turn_in_interpreter(*args, True))
    got, pull = jax.vjp(mixer, x, p)
    # a step's rows are 128 whatever the width: a pair's 2 x r heads of 64
    assert calls == [((g, r, d), lane._rule(window), (max(128 // (r * (128 // d)), 16), 128))]
    assert turns == [(d // 2, lane._OPERAND, "lane.swa")] * 2
    for ours, theirs in zip((got,) + tuple(jax.tree.leaves(pull(weigh))), want):
        np.testing.assert_allclose(ours, theirs, atol=2e-2 * float(jnp.abs(theirs).max()))
