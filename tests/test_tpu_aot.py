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


@pytest.mark.parametrize("rows", [512, 4096, 8192, 16384, 131072])
def test_moments_kernel_compiles_for_v5e(v5e_devices, rows):
    one = SingleDeviceSharding(v5e_devices[0])
    block = _sds((rows, 128), jnp.float32, one)
    compiled = jax.jit(
        lambda data, mask: pallas_kde._masked_moments_padded(
            data, mask, interpret=False
        )
    ).lower(block, block).compile()
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


@pytest.mark.parametrize("kind, largest", [
    # two key/value heads' scores of a block (2 x 8 x 1,024 x 2,048) lie
    # under the projections' output, 8,192 x 5,120
    ("sliding", 8192 * 5120),
    # one key/value head's widest block: 8 x 1,024 x 8,192
    ("full", 2 ** 26)])
def test_banded_attention_compiles_for_v5e_at_the_published_size(
        v5e_devices, kind, largest):
    """The Mellum2 lane's mixer (``workloads/mellum2.py``) at 8,192 tokens,
    forward pass only: the chip's compiler takes it, and its largest float32
    array is a block's scores, not a square's (32 heads x 8,192^2 is 2^31
    elements, one head's 2^26 in a window layer too)."""
    import re

    from hpbandster_tpu.workloads import mellum2 as M

    one = SingleDeviceSharding(v5e_devices[0])
    cfg = M.Mellum2Config()
    leaves = {name: _sds(shape, jnp.float32, one)
              for name, shape in M._layer_shapes(cfg).items()}
    compiled = jax.jit(lambda x, p: M._attention(x, p, kind, cfg)).lower(
        _sds((cfg.seq_len, cfg.hidden_size), jnp.float32, one), leaves).compile()
    sizes = [int(np.prod([int(n) for n in dims.split(",")]))
             for dims in re.findall(r"f32\[([\d,]+)\]", compiled.as_text())]
    assert max(sizes) == largest


def test_a_looped_layer_compiles_for_v5e_at_the_published_size(v5e_devices):
    """The Ouro lane's layer (``workloads/ouro.py``: the lanes' one attention
    at 16 key/value heads of ONE query head each, then the SwiGLU) at 2,048
    tokens, forward pass only: the chip's compiler takes it, the scores stay
    two-dimensional a head (rows = queries x 1), and no float32 array is
    larger than the SwiGLU's gate and up side by side (2,048 x 11,264): the
    widest block's scores of all 16 heads (16 x 512 x 2,048) lie under it,
    and no head's square is ever whole (2,048 x 2,048 times 16 heads)."""
    import re

    from hpbandster_tpu.workloads import ouro as O

    one = SingleDeviceSharding(v5e_devices[0])
    cfg = O.OuroConfig()
    leaves = {name: _sds(shape, jnp.float32, one)
              for name, shape in O._layer_shapes(cfg).items()}
    compiled = jax.jit(lambda h, p: O._layer(h, p, cfg)[0]).lower(
        _sds((cfg.seq_len, cfg.hidden_size), jnp.float32, one), leaves).compile()
    sizes = [int(np.prod([int(n) for n in dims.split(",")]))
             for dims in re.findall(r"f32\[([\d,]+)\]", compiled.as_text())]
    assert max(sizes) == cfg.seq_len * 2 * cfg.intermediate_size
    assert 16 * cfg.attn_query_block * cfg.seq_len in sizes


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
