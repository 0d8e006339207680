"""Chipless compile checks: the TPU compiler on the programs the chip runs.

Interpret mode (every other Pallas test here) is plain XLA ops: it has no
VMEM limit and partitions over a mesh on its own, so it cannot see the two
ways these kernels failed on hardware — a VMEM footprint that grew with the
observation count, and a Mosaic call inside an SPMD-partitioned program.
The installed libtpu compiles for a ``v5e:2x2`` topology DESCRIPTION with
no chip attached, which can: these tests AOT-compile the kernels at the
capacities ``pow2_capacities`` produces and the 4-chip fused sweep.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from hpbandster_tpu.ops import pallas_kde
from hpbandster_tpu.ops.bracket import hyperband_bracket
from hpbandster_tpu.ops.kde import KDE
from hpbandster_tpu.ops.sweep import build_space_codec, make_fused_sweep_fn
from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to DESCRIBE the topology is the one allowed skip
        pytest.skip(
            "no v5e:2x2 topology description from the installed libtpu "
            f"({type(e).__name__}: {e})"
        )
    assert len(topo.devices) == 4
    # a TPU executable cannot be read back without a TPU client: keep
    # these compiles out of the suite's (CPU) persistent cache. jax
    # decides once per process whether the cache is in use, so the
    # decision is reset on both sides of the switch.
    from jax.experimental.compilation_cache import compilation_cache

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n_obs", [256, 4096, 8192, 16384])
def test_scorer_compiles_for_v5e_at_sweep_capacities(v5e_devices, n_obs):
    one = SingleDeviceSharding(v5e_devices[0])
    d, n_cands = 2, 8192

    def score(cands, gd, gm, gb, bd, bm, bb, vartypes, cards):
        return pallas_kde.pallas_score_candidates(
            cands, KDE(gd, gm, gb), KDE(bd, bm, bb), vartypes, cards
        )

    f32, i32 = jnp.float32, jnp.int32
    kde = (_sds((n_obs, d), f32, one), _sds((n_obs,), f32, one),
           _sds((d,), f32, one))
    compiled = jax.jit(score).lower(
        _sds((n_cands, d), f32, one), *kde, *kde,
        _sds((d,), i32, one), _sds((d,), i32, one),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_four_chip_mesh_sweep_compiles_with_pallas_scorer(v5e_devices):
    """The README's "shard over all chips" program: a Mosaic call inside
    the mesh-sharded sweep must sit under a shard_map or the SPMD
    partitioner refuses the whole program at lowering."""
    mesh = Mesh(np.asarray(v5e_devices), ("config",))
    plans = [hyperband_bracket(i, 1, 9, 3) for i in range(3)]
    fn = make_fused_sweep_fn(
        branin_from_vector, plans, build_space_codec(branin_space(seed=0)),
        mesh=mesh, use_pallas=True, pallas_interpret=False,
    )
    seed = _sds((), jnp.uint32, NamedSharding(mesh, PartitionSpec()))
    text = fn.lower(seed).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text or "all-gather" in text


def _kernel_parts(text):
    """``[(kernel, lane part)]`` of the compiled text's Mosaic kernels, the
    part as the benchmark's reduction reads it (``device_phase_map`` by
    ``LANE_SCOPES``), in the order of their names."""
    import re

    from hpbandster_tpu.obs.profile import device_phase_map
    from hpbandster_tpu.obs.timeline import LANE_SCOPES

    parts = device_phase_map(text, LANE_SCOPES)
    kernels = re.findall(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    return sorted((name.split(".")[0], parts.get(name)) for name in kernels)


def _f32_sizes(text):
    import re

    return [int(np.prod([int(n) for n in dims.split(",")]))
            for dims in re.findall(r"f32\[([\d,]+)\]", text)]


def _kernels_with_the_turns(scope, forward_passes=1):
    """A mixer's Mosaic kernels as :func:`_kernel_parts` lists them where its
    rotation is a kernel too (``ops/pallas_rotary.py``): the queries' and the
    keys' turn a forward pass, and their transposes backward."""
    return ([("banded_attention_backward", scope)]
            + [("banded_attention_forward", scope)] * forward_passes
            + [("rotary_turn", scope)] * 2 * (forward_passes + 1))


@pytest.fixture
def mosaic_compiles_here(monkeypatch):
    """The lanes' rule asks the backend, which is the CPU here: told that
    Mosaic compiles, it hands the described chip what the real one gets."""
    from hpbandster_tpu.workloads import lane

    monkeypatch.setattr(lane, "pallas_available", lambda: True)


@pytest.mark.parametrize("kind, scope", [("sliding", "lane.swa"), ("full", "lane.gqa")])
def test_banded_attention_compiles_for_v5e_at_the_published_size(
        v5e_devices, mosaic_compiles_here, kind, scope):
    """The Mellum2 lane's mixer (``workloads/mellum2.py``) at 8,192 tokens,
    forward and backward pass: the chip's compiler takes it, the scores are
    a Mosaic kernel's (``ops/pallas_attention.py``) and no float32 array of
    a block's scores exists (the largest is the projections' output, 8,192
    x 5,120, for both kinds; the plain form's widest block was 8 x 1,024 x
    8,192), and the forward and the backward kernel both carry the caller's
    part in the compiled text, which is what charges their device time to
    ``lane.swa`` / ``lane.gqa``."""
    from hpbandster_tpu.workloads import mellum2 as M

    one = SingleDeviceSharding(v5e_devices[0])
    cfg = M.Mellum2Config()
    leaves = {name: _sds(shape, jnp.float32, one)
              for name, shape in M._layer_shapes(cfg).items()}
    x = _sds((cfg.seq_len, cfg.hidden_size), jnp.float32, one)

    def both_passes(x, p, dy):
        with jax.named_scope(scope):
            y, pull = jax.vjp(lambda x, p: M._attention(x, p, kind, cfg), x, p)
        return y, pull(dy)     # pulled back where the caller's scope is closed

    text = jax.jit(both_passes).lower(x, leaves, x).compile().as_text()
    assert _kernel_parts(text) == _kernels_with_the_turns(scope)
    assert max(_f32_sizes(text)) == cfg.seq_len * 5120


@pytest.mark.parametrize("kind, scope, heads", [
    ("full", "lane.gqa", 48), ("sliding", "lane.swa", 64)])
def test_a_group_of_six_query_heads_compiles_for_v5e_with_the_kernels(
        v5e_devices, mosaic_compiles_here, kind, scope, heads):
    """The Laguna-XS.2 lane's mixers (``workloads/laguna.py``) at 8,192 keys,
    forward and backward pass: a full layer's 48 query heads, 6 a key/value
    head (768 rows a step: no power of two) over heads of which half is
    turned, and a window layer's 64 under the 512 window, both gated a head.
    Mosaic takes both, the kernels carry the caller's part, and no float32
    array of a block's scores exists (the largest is the projections' output
    with the gate's columns, made a whole tile of lanes). And
    ``lane._kernel_tiles`` returns for every
    shape a cell ran before exactly the tiles it returned then."""
    from hpbandster_tpu.workloads import laguna as L
    from hpbandster_tpu.workloads import lane

    one = SingleDeviceSharding(v5e_devices[0])
    cfg = L.LagunaConfig()
    r, g, d, t = heads // cfg.num_kv_heads, cfg.num_kv_heads, cfg.head_dim, cfg.seq_len
    assert dict(cfg.heads_by_kind)[kind] == heads
    assert lane._kernel_tiles(t, d, r, g, L._sight(cfg, kind)) == (128, 512)
    # the accepted cells' shapes (Mellum2 / SDAR, LFM2's pairs of 64, the
    # SDAR rule, one head a key/value head) keep their tiles; few keys the
    # plain form
    assert [lane._kernel_tiles(*shape) for shape in (
        (8192, 128, 8, 4), (8192, 128, 8, 4, 1024), (8192, 64, 4, 8),
        (8192, 128, 8, 4, lane.BlockDiffusion(4)), (4096, 128, 1, 16),
        (2048, 128, 1, 16), (2048, 128, 1, 30))] == [(128, 512)] * 4 + [(512, 512), None, None]
    shapes = L._layer_shapes(cfg, kind, "sparse")
    leaves = {name: _sds(shapes[name], jnp.float32, one)
              for name in ("wq", "wk", "wv", "w_head_gate", "wo")}
    x = _sds((t, cfg.hidden_size), jnp.float32, one)

    def both_passes(x, p, dy):
        with jax.named_scope(scope):
            y, pull = jax.vjp(lambda x, p: L._attention(x, p, kind, cfg), x, p)
        return y, pull(dy)     # pulled back where the caller's scope is closed

    text = jax.jit(both_passes).lower(x, leaves, x).compile().as_text()
    assert _kernel_parts(text) == _kernels_with_the_turns(scope)
    # (the gate's columns padded to a whole tile of lanes)
    assert max(_f32_sizes(text)) == t * (heads * d + 2 * g * d + 128)


@pytest.mark.parametrize("kind, scope, heads, rotary", [
    ("sliding", "lane.swa", 64, 128), ("full", "lane.gqa", 48, 64)])
def test_the_rotation_of_heads_side_by_side_copies_no_row_on_v5e(
        v5e_devices, mosaic_compiles_here, kind, scope, heads, rotary):
    """The same two mixers, forward and backward, read for what the rotation
    leaves in the compiled text: it is the kernel (``ops/pallas_rotary.py``),
    four calls under the caller's part, the forward ones handing the attention
    kernels bfloat16; no table is tiled across the heads; and the compiler
    copies out no float32 array of 8,192 rows at all: not the turns' slices
    (``[8192, 8128]`` of a window layer's queries, ``[8192, 6112]`` of a full
    layer's, which the plain form's two turns of the whole row were), and not
    the queries themselves (``[8192, 8192]`` / ``[8192, 6144]``: the
    projections' product is whole tiles of lanes wide, so the compiler holds
    it row-major, as the kernel takes it)."""
    import re

    from hpbandster_tpu.workloads import laguna as L
    from hpbandster_tpu.workloads import lane

    one = SingleDeviceSharding(v5e_devices[0])
    cfg = L.LagunaConfig()
    t, d, g = cfg.seq_len, cfg.head_dim, cfg.num_kv_heads
    assert L._rotary(cfg, kind) == rotary
    assert lane._turn_in_vmem(t, heads * d, d, rotary) and lane._turn_in_vmem(t, g * d, d, rotary)
    shapes = L._layer_shapes(cfg, kind, "sparse")
    leaves = {name: _sds(shapes[name], jnp.float32, one)
              for name in ("wq", "wk", "wv", "w_head_gate", "wo")}
    x = _sds((t, cfg.hidden_size), jnp.float32, one)

    def both_passes(x, p, dy):
        with jax.named_scope(scope):
            y, pull = jax.vjp(lambda x, p: L._attention(x, p, kind, cfg), x, p)
        return y, pull(dy)

    text = jax.jit(both_passes).lower(x, leaves, x).compile().as_text()
    turns = re.findall(r"%rotary_turn\S* = (\w+)\[(\d+),(\d+)\]", text)
    assert sorted(turns) == sorted(
        (dtype, str(t), str(width)) for dtype in ("bf16", "f32") for width in (heads * d, g * d))
    assert "/tile\"" not in text
    assert not re.findall(r"= f32\[%d,\d+\]\S* copy\(" % t, text)


def test_a_looped_layer_compiles_for_v5e_at_the_published_size(
        v5e_devices, mosaic_compiles_here):
    """The Ouro lane's layer (``workloads/ouro.py``: the lanes' one attention
    at 16 key/value heads of ONE query head each, then the SwiGLU) at 2,048
    tokens: the chip's compiler takes it; at so few keys the rule keeps the
    plain form on the chip too (``lane._PLAIN_KEYS``: no Mosaic kernel), the
    scores stay two-dimensional a head (rows = queries x 1), and no float32
    array is larger than the SwiGLU's gate and up side by side (2,048 x
    11,264): the widest block's scores of all 16 heads (16 x 512 x 2,048)
    lie under it, and no head's square is ever whole. Then the same layers
    at twice the keys as the trainer runs them, a loop over the stacked
    leaves forward and another backward: there the scores are the kernels',
    and both carry ``lane.gqa`` inside the loops' bodies too."""
    from hpbandster_tpu.workloads import lane
    from hpbandster_tpu.workloads import ouro as O

    one = SingleDeviceSharding(v5e_devices[0])
    cfg = O.OuroConfig()
    shapes = O._layer_shapes(cfg)
    compiled = jax.jit(lambda h, p: O._layer(h, p, cfg)[0]).lower(
        _sds((cfg.seq_len, cfg.hidden_size), jnp.float32, one),
        {name: _sds(shape, jnp.float32, one) for name, shape in shapes.items()}).compile()
    sizes = _f32_sizes(compiled.as_text())
    assert max(sizes) == cfg.seq_len * 2 * cfg.intermediate_size
    assert 16 * cfg.attn_query_block * cfg.seq_len in sizes
    assert _kernel_parts(compiled.as_text()) == []

    longer = cfg._replace(seq_len=2 * cfg.seq_len)
    visit = O._visits(longer)[0]
    assert visit.times == cfg.num_layers

    def a_pass_and_back(h, p, dh):
        out, _, kept = lane._visit_forward(visit, h, p)
        take_it = lambda pull, dh, written, _: pull(dh)
        return out, lane._visit_backward(
            visit, take_it, dh, kept, p, jax.tree.map(jnp.zeros_like, p),
            scope="lane.accumulate")

    h = _sds((longer.seq_len, cfg.hidden_size), jnp.float32, one)
    stacked = {name: _sds((cfg.num_layers,) + shape, jnp.float32, one)
               for name, shape in shapes.items()}
    text = jax.jit(a_pass_and_back).lower(h, stacked, h).compile().as_text()
    # the forward kernel twice: the pass, and the backward pass's own
    # recomputation of a visit's inside
    assert _kernel_parts(text) == _kernels_with_the_turns("lane.gqa", forward_passes=2)


def test_the_convolution_and_narrow_head_layers_compile_for_v5e_at_the_published_size(
        v5e_devices, mosaic_compiles_here):
    """The LFM2 lane's two mixers (``workloads/lfm2.py``) at 8,192 tokens,
    forward and backward pass. The gated short convolution is plain JAX under
    ``lane.conv`` in both passes, and no float32 array is larger than ``W_in``'s
    output (8,192 x 6,144). Attention of 64-wide heads is the Mosaic
    kernels' (``ops/pallas_attention.py``: two key/value heads side by side
    in one tile of lanes): both carry ``lane.gqa`` in the compiled text, no
    float32 array of a block's scores exists (the plain form's was one
    key/value head's four query heads at a time, 4 x 1,024 rows of 8,192
    keys; nothing is as wide as the keys now), and the per-head norm's
    reciprocal root is traced before them."""
    import re

    from hpbandster_tpu.workloads import lane
    from hpbandster_tpu.workloads import lfm2 as L

    one = SingleDeviceSharding(v5e_devices[0])
    cfg = L.Lfm2Config()
    x = _sds((cfg.seq_len, cfg.hidden_size), jnp.float32, one)

    def both_passes(mixer):
        def run(x, p, dy):
            y, pull = jax.vjp(mixer, x, p)
            return y, pull(dy)
        return run

    leaves = lambda kind: {name: _sds(shape, jnp.float32, one)
                           for name, shape in L._layer_shapes(cfg, *kind).items()}
    text = jax.jit(both_passes(
        lambda x, p: lane.short_conv_mixer(x, p, scope="lane.conv"))).lower(
            x, leaves(("conv", "dense")), x).compile().as_text()
    assert max(_f32_sizes(text)) == cfg.seq_len * 3 * cfg.hidden_size
    assert "lane.conv" in text and "transpose(jvp(lane.conv))" in text
    assert _kernel_parts(text) == []

    def attention(x, p):
        with jax.named_scope("lane.gqa"):
            return lane.attention_mixer(
                x, p, kv_heads=cfg.num_kv_heads, heads_per_kv=4, head_dim=cfg.head_dim,
                inv_freq=L.rotary_inv_freq(cfg), factor=1.0, sight=None,
                block=cfg.attn_query_block, scope="lane.gqa", norm_eps=cfg.norm_eps)

    text = jax.jit(both_passes(attention)).lower(
        x, leaves(("attention", "moe")), x).compile().as_text()
    # (the turn of heads of 64 is the rotation's kernel too, two heads a tile of lanes)
    assert _kernel_parts(text) == _kernels_with_the_turns("lane.gqa")
    assert "/tile\"" not in text
    # no array is as wide as the keys; the largest is the log-sum-exp, a
    # number a (query head, query) kept across the 128 lanes
    assert not re.search(r"f32\[[\d,]*\b%d\]" % cfg.seq_len, text)
    assert max(_f32_sizes(text)) == cfg.num_heads * cfg.seq_len * 128
    assert "rsqrt" in text


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_the_expert_layer_compiles_for_v5e_to_gathers_and_unfilled_buffers(
        v5e_devices, score):
    """The lanes' expert layer (``workloads/lane.py`` ``moe_held_experts``),
    forward and backward pass as the chip's compiler leaves them: the rows
    move by gathers (no floating-point scatter, whose indices the chip
    sorts; the one integer scatter writes the sorted order), nothing sorts
    but the router's top k, and the loops over tiles start from buffers the
    device hands over unfilled (``AllocateBuffer``: on the CPU
    ``jax.lax.empty`` is zeros, so only this compile can tell)."""
    import re

    from hpbandster_tpu.workloads import lane
    from kimi_small import lower_forward_and_backward

    one = SingleDeviceSharding(v5e_devices[0])
    t, d, f, outputs, held, k = 512, 256, 128, 16, 4, 2
    facts = lane.ExpertLayer(
        outputs=outputs, top_k=k, held=tuple(range(held)), score=score)
    p = {"router": (d, outputs), "e_gate": (held, d, f), "e_up": (held, d, f),
         "e_down": (held, f, d)}
    p = {name: _sds(shape, jnp.float32, one) for name, shape in p.items()}
    x = _sds((t, d), jnp.float32, one)

    text = lower_forward_and_backward(
        lambda x, p: lane.moe_held_experts(x, p, facts)[0], x, p).compile().as_text()
    moved = re.findall(r"= \(?(\w+)\[([\d,]*)\]\S* (scatter|gather)\(", text)
    assert [m for m in moved if m[2] == "scatter"] == [("s32", str(t * k), "scatter")]
    rows = "%d,%d" % (t * k, d)
    assert {("f32", rows, "gather"), ("bf16", rows, "gather")} <= set(moved)
    assert all("top_k" in line for line in text.splitlines() if " sort(" in line)
    unfilled = re.findall(
        r"= (\w+\[[\d,]*\])\S* custom-call\(\), custom_call_target=\"AllocateBuffer\"", text)
    assert {"f32[%s]" % rows, "bf16[%s]" % rows} <= set(unfilled)


@pytest.mark.parametrize("lane_name, t, f, outputs, held, score", [
    ("mellum2", 8192, 896, 64, 16, "softmax"), ("kimi-linear", 4096, 1024, 256, 8, "sigmoid")])
def test_the_experts_products_compile_for_v5e_as_grouped_kernels(
        v5e_devices, mosaic_compiles_here, lane_name, t, f, outputs, held, score):
    """The expert layer at both lanes' published sizes (hidden 2,304, top 8),
    forward and backward pass, where the rule takes the grouped kernels
    (``ops/pallas_grouped.py``): the chip's compiler takes them (VMEM, the
    grid whose length the device counts); two kernels forward and five in
    the backward rule, every one charged to ``lane.moe`` and to
    ``moe.experts`` by the names in the compiled text; no ``ragged-dot`` is
    left, nothing scatters but the integer write of the sorted order, and
    nothing sorts but the router's top k."""
    import re

    from hpbandster_tpu.obs.profile import device_phase_map
    from hpbandster_tpu.obs.timeline import MOE_SCOPES
    from hpbandster_tpu.workloads import lane
    from kimi_small import lower_forward_and_backward

    one = SingleDeviceSharding(v5e_devices[0])
    d, k = 2304, 8
    assert lane._product_rows(t * k, d, f) == lane._KERNEL_TILE_ROWS
    facts = lane.ExpertLayer(
        outputs=outputs, top_k=k, held=tuple(range(held)), score=score)
    p = {"router": (d, outputs), "e_gate": (held, d, f), "e_up": (held, d, f),
         "e_down": (held, f, d)}
    p = {name: _sds(shape, jnp.float32, one) for name, shape in p.items()}
    text = lower_forward_and_backward(
        lambda x, p: lane.moe_held_experts(x, p, facts)[0],
        _sds((t, d), jnp.float32, one), p).compile().as_text()
    kernels = _kernel_parts(text)
    assert kernels == [("grouped_groups_by_rows", "lane.moe")] * 2 + [
        ("grouped_rows_by_group", "lane.moe")] * 5
    pieces = device_phase_map(text, MOE_SCOPES)
    named = re.findall(r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert len(named) == 7 and {pieces.get(name) for name in named} == {"moe.experts"}
    assert "ragged" not in text
    moved = re.findall(r"= \(?(\w+)\[([\d,]*)\]\S* (scatter|gather)\(", text)
    assert [m for m in moved if m[2] == "scatter"] == [("s32", str(t * k), "scatter")]
    assert all("top_k" in line for line in text.splitlines() if " sort(" in line)


def test_attention_under_the_block_diffusion_rule_compiles_for_v5e_with_the_kernels(
        v5e_devices, mosaic_compiles_here):
    """The SDAR lane's attention layer (``workloads/sdar.py``: 2 x 4,096
    rows, the clean and the masked copy, under ``lane.BlockDiffusion(4)``) at
    the published size, forward and backward pass. Where Mosaic compiles the
    rule of sight takes the fused kernels at this shape as the causal rule
    does (``lane._kernel_tiles`` answers by the backend and the shapes): both
    carry ``lane.bda`` in the compiled text, no float32 array of a block's
    scores exists (the plain form's widest was one key/value head's eight
    query heads of 512 queries against 4,096 + 512 keys; nothing is as wide
    as the rows now): the largest is the projections' output (8,192 x
    5,120), and after it the log-sum-exp, a number a (query head, row) kept
    across the 128 lanes. Then the plain form under the rule
    alone, which the CPU and the reference's tests still run: the chip's
    compiler takes it, with no kernel, and its largest float32 array is that
    masked block's scores."""
    import re

    from hpbandster_tpu.workloads import lane
    from hpbandster_tpu.workloads import sdar as D

    one = SingleDeviceSharding(v5e_devices[0])
    cfg = D.SdarConfig()
    rows, g, r, d = 2 * cfg.seq_len, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim
    sight = lane.BlockDiffusion(cfg.block_length)
    assert lane._kernel_tiles(rows, d, r, g, sight) == lane._kernel_tiles(rows, d, r, g) == (128, 512)

    def attention(x, p):
        with jax.named_scope("lane.bda"):
            return lane.attention_mixer(
                x, p, kv_heads=g, heads_per_kv=r, head_dim=d, inv_freq=D.rotary_inv_freq(cfg),
                factor=1.0, sight=sight, block=cfg.attn_query_block, scope="lane.bda",
                norm_eps=cfg.rms_norm_eps)

    def both_passes(x, p, dy):
        y, pull = jax.vjp(attention, x, p)
        return y, pull(dy)     # pulled back where the caller's scope is closed

    x = _sds((rows, cfg.hidden_size), jnp.float32, one)
    leaves = {name: _sds(shape, jnp.float32, one)
              for name, shape in D._layer_shapes(cfg).items()
              if name in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")}
    text = jax.jit(both_passes).lower(x, leaves, x).compile().as_text()
    assert _kernel_parts(text) == _kernels_with_the_turns("lane.bda")
    # (the one array as long as the rows is their positions, a vector)
    assert not re.search(r"f32\[[\d,]+,%d\]" % rows, text)
    sizes = _f32_sizes(text)
    assert max(sizes) == rows * (g * r * d + 2 * g * d) and cfg.num_heads * rows * 128 in sizes

    def scores(q, k, v):
        with jax.named_scope("lane.bda"):
            return lane.banded_attention(q, k, v, sight, cfg.attn_query_block)

    text = jax.jit(scores).lower(
        _sds((rows, g, r, d), jnp.float32, one), _sds((rows, g, d), jnp.float32, one),
        _sds((rows, g, d), jnp.float32, one)).compile().as_text()
    assert _kernel_parts(text) == [] and "lane.bda" in text
    assert not re.search(r"f32\[[\d,]*\b%d\]" % rows, text)
    widest = r * cfg.attn_query_block * (cfg.seq_len + cfg.attn_query_block)
    assert max(size for size in _f32_sizes(text) if size != rows * g * r * d) == widest


@pytest.mark.parametrize("cell, chunks, heads, width", [
    ("kimi-linear-sgd", 64, 32, 128 + 128), ("olmo-hybrid-sgd", 32, 30, 96 + 192)])
def test_the_delta_rules_systems_compile_for_v5e_as_one_kernel(
        v5e_devices, mosaic_compiles_here, cell, chunks, heads, width):
    """Both cells' chunk systems (64 x 64, one a chunk and head, against
    ``[V, K exp G]``) through ``delta_rule._inverse_and_solved``: Mosaic takes
    the kernel at these shapes (two systems side by side in a tile of lanes,
    a right-hand side of 288 lanes too), the program holds it once under the
    caller's part and no triangular solve, and the kernel asks for no more
    VMEM than its shapes say."""
    import re

    from hpbandster_tpu.ops import pallas_triangular
    from hpbandster_tpu.workloads import delta_rule

    one = SingleDeviceSharding(v5e_devices[0])
    assert pallas_triangular.fits(chunks * heads, 64, width)
    assert pallas_triangular._vmem_bytes(64, width) < 16 * 2 ** 20

    def solve(system, rhs):
        with jax.named_scope("lane.gdn"):
            return delta_rule._inverse_and_solved(system, rhs)

    text = jax.jit(solve).lower(
        _sds((chunks, heads, 64, 64), jnp.float32, one),
        _sds((chunks, heads, 64, width), jnp.float32, one)).compile().as_text()
    assert _kernel_parts(text) == [("delta_inverse_and_solved", "lane.gdn")]
    assert not re.search(r'op_name="[^"]*triangular_solve|custom_call_target="[^"]*(?:trsm|[Tt]riang)', text)


def test_a_gated_deltanet_layer_compiles_for_v5e_at_the_published_size(
        v5e_devices, mosaic_compiles_here):
    """The Olmo-Hybrid lane's linear layer (``workloads/olmo_hybrid.py``: the
    Gated-DeltaNet mixer, 30 heads of ``d_k`` 96 beside ``d_v`` 192, gated
    once a head, and the SwiGLU, each under the norm that follows it) at
    2,048 tokens, forward and backward pass: the chip's compiler takes it;
    the scan's form of a gate a head is plain JAX under ``lane.gdn`` in both
    passes but for the chunks' systems, which one kernel inverts and solves
    in VMEM (``ops/pallas_triangular.py``, booked under ``lane.gdn`` too; no
    triangular solve is left), the backward rule
    (``delta_rule._chunks_backward``) naming the part itself; a chunk's decays are ``[chunks, heads, 64, 64]`` arrays and
    no array carries the per-channel form's blocks (no ``16 x 16 x 96``); no
    float32 array is larger than the gradient of the SwiGLU's gate and up
    side by side (3,840 x 22,016). The
    full layer at as many keys: no positions, so no cosine, and at 2,048
    keys the plain form on the chip too; at 8,192 keys the kernels take 30
    heads of 128."""
    import re
    import time

    from hpbandster_tpu.workloads import lane
    from hpbandster_tpu.workloads import olmo_hybrid as OH

    one = SingleDeviceSharding(v5e_devices[0])
    cfg = OH.OlmoHybridConfig()
    x = _sds((cfg.seq_len, cfg.hidden_size), jnp.float32, one)
    leaves = lambda mixer: {name: _sds(shape, jnp.float32, one)
                            for name, shape in OH._layer_shapes(cfg, mixer).items()}

    def both_passes(mixer):
        def run(x, p, dy):
            y, pull = jax.vjp(lambda x, p: OH._layer(x, p, mixer, cfg)[0], x, p)
            return y, pull(dy)
        return run

    t0 = time.perf_counter()
    text = jax.jit(both_passes("gdn")).lower(x, leaves("gdn"), x).compile().as_text()
    print("a linear layer's forward and backward pass compiled for v5e in %.1f s"
          % (time.perf_counter() - t0))
    assert _kernel_parts(text) == [("delta_inverse_and_solved", "lane.gdn")]
    assert not re.search(r'op_name="[^"]*triangular_solve|custom_call_target="[^"]*(?:trsm|[Tt]riang)', text)
    assert "lane.gdn" in text and "transpose(jvp(lane.gdn))" in text
    # the rule's own scan and solve, named by the rule
    assert re.search(r'op_name="[^"]*jvp\(lane\.gdn\)\)?/lane\.gdn/while', text)
    chunks, heads = cfg.seq_len // cfg.gdn_chunk, cfg.linear_num_heads
    assert re.search(r"f32\[%d,%d,64,64\]" % (chunks, heads), text)
    assert not re.search(r"f32\[[\d,]*16,16,96\]", text)
    assert max(_f32_sizes(text)) == cfg.hidden_size * 2 * cfg.intermediate_size

    text = jax.jit(both_passes("gqa")).lower(x, leaves("gqa"), x).compile().as_text()
    assert _kernel_parts(text) == [] and "cosine" not in text and "lane.gqa" in text
    assert lane._kernel_tiles(8192, cfg.head_dim, 1, cfg.num_kv_heads) is not None


def test_the_tpu_compilers_text_gives_every_instruction_a_kind_and_a_lifted_cast_its_part(
        v5e_devices):
    """The facts ``obs.profile`` keeps of an instruction (ISSUE 52), on the
    TPU compiler's real text: two parts scanned over their stacked leaves,
    under ``jax.grad`` and a momentum step. The compiler lifts the casts of
    the whole stacks out of the loop and leaves them without a name: each is
    adopted by the part whose product reads it inside."""
    import lane_names
    from hpbandster_tpu.obs.profile import (
        adopted_phase_map, device_kind_map, device_phase_map)
    from hpbandster_tpu.obs.timeline import LANE_SCOPES, OP_KINDS

    one = SingleDeviceSharding(v5e_devices[0])
    layers, width, rows = 4, 512, 1024

    def layer(x, w):
        with jax.named_scope("lane.gqa"):
            x = x + jnp.tanh(jnp.dot(x.astype(jnp.bfloat16), w[0].astype(jnp.bfloat16),
                                     preferred_element_type=jnp.float32))
        with jax.named_scope("lane.dense_ffn"):
            x = x + jnp.dot(jax.nn.silu(x).astype(jnp.bfloat16), w[1].astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        return x, None

    def loss(ws, x):
        x, _ = jax.lax.scan(layer, x, ws)
        with jax.named_scope("lane.head"):
            return jnp.mean(x * x)

    def step(ws, m, x):
        g = jax.grad(loss)(ws, x)
        with jax.named_scope("lane.update"):
            m = jax.tree.map(lambda m, g: 0.9 * m + g, m, g)
            return jax.tree.map(lambda w, m: w - 0.1 * m, ws, m), m

    def train(ws, x):
        m = jax.tree.map(jnp.zeros_like, ws)
        return jax.lax.fori_loop(0, 3, lambda i, c: step(*c, x), (ws, m))[0]

    stack = _sds((layers, width, width), jnp.float32, one)
    text = jax.jit(train).lower((stack, stack), _sds((rows, width), jnp.float32, one)
                                ).compile().as_text()
    program = lane_names.check_the_maps_are_what_they_were(text)
    assert len(program.instructions) == sum(map(len, program.computations.values())) > 300
    kinds = device_kind_map(program)
    assert kinds.keys() == program.instructions.keys() and set(kinds.values()) <= set(OP_KINDS)
    assert {"copy", "cast_slice", "fill", "compute"} <= set(kinds.values())
    parts = device_phase_map(program, LANE_SCOPES)
    adopted = adopted_phase_map(program, LANE_SCOPES)
    assert adopted and not set(adopted) & set(parts)
    named = {n for rows_ in program.computations.values() for n, op_name, _ in rows_ if op_name}
    lifted = [n for n, (opcode, _, _, shape) in program.instructions.items()
              if opcode == "convert" and n not in named
              and shape.startswith("bf16[%d,%d,%d]" % (layers, width, width))]
    assert len(lifted) == 2 and not set(lifted) & set(parts)
    assert {adopted.get(n) for n in lifted} == {"lane.gqa", "lane.dense_ffn"}
    # the zeros of the momentum are filled for the update alone, or for a
    # part's gradient sum: never left to no one
    fills = [n for n, kind in kinds.items() if kind == "fill" and n not in parts
             and program.instructions[n][3].startswith("f32[%d," % layers)]
    assert fills and all(n in adopted for n in fills)
