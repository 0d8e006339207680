"""Ring attention vs dense attention — exact parity on the 8-device mesh.

The sequence axis shards across the virtual 'seq' ring; K/V blocks rotate
via ppermute with online-softmax accumulation. The result must equal
dense full-sequence attention (not approximate it): f32 compute is pinned
tight, the bf16 MXU path within bf16 rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.ops.ring_attention import (
    make_ring_attention,
    ring_attention_block,
    seq_mesh,
)

T, H, DH = 64, 4, 16


def _qkv(key, t=T):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (t, H, DH)
    return (jax.random.normal(kq, shape, jnp.float32),
            jax.random.normal(kk, shape, jnp.float32),
            jax.random.normal(kv, shape, jnp.float32))


def dense_attention(q, k, v, causal):
    s = jnp.einsum("qhd,khd->hqk", q, k) * (DH ** -0.5)
    if causal:
        t = q.shape[0]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->hqd", a, v).transpose(1, 0, 2)


class TestRingAttentionParity:
    @pytest.mark.parametrize("causal", [True, False])
    def test_f32_matches_dense_exactly(self, causal):
        if not causal and jax.default_backend() == "cpu":
            pytest.xfail("XLA CPU SPMD: PartitionId unsupported on the "
                         "non-causal ring path")
        q, k, v = _qkv(jax.random.key(0))
        ring = make_ring_attention(
            seq_mesh(), causal=causal, compute_dtype=jnp.float32
        )
        out = jax.jit(ring)(q, k, v)
        ref = dense_attention(q, k, v, causal)
        assert out.shape == (T, H, DH)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_bf16_mxu_path_within_rounding(self):
        q, k, v = _qkv(jax.random.key(1))
        ring = make_ring_attention(seq_mesh(), compute_dtype=jnp.bfloat16)
        out = jax.jit(ring)(q, k, v)
        ref = dense_attention(q, k, v, True)
        # bf16 has ~8 mantissa bits; attention outputs are O(1)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=5e-2, rtol=5e-2
        )

    def test_single_device_ring_degenerates_to_local(self):
        q, k, v = _qkv(jax.random.key(2), t=16)
        mesh = seq_mesh(jax.devices()[:1])
        ring = make_ring_attention(mesh, compute_dtype=jnp.float32)
        ref = dense_attention(q, k, v, True)
        np.testing.assert_allclose(
            np.asarray(jax.jit(ring)(q, k, v)), np.asarray(ref),
            atol=2e-5, rtol=2e-5,
        )

    def test_differentiable(self):
        # training through ring attention is the point of seq parallelism
        q, k, v = _qkv(jax.random.key(3))
        ring = make_ring_attention(seq_mesh(), compute_dtype=jnp.float32)

        def loss(q):
            return (ring(q, k, v) ** 2).mean()

        g = jax.jit(jax.grad(loss))(q)
        assert g.shape == q.shape
        assert np.isfinite(np.asarray(g)).all()
        # grads must match the dense formulation too
        g_ref = jax.grad(lambda q: (dense_attention(q, k, v, True) ** 2)
                         .mean())(q)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(g_ref), atol=2e-5, rtol=2e-4
        )

    def test_striped_layout_matches_dense(self):
        # the load-balanced causal schedule: positions striped across the
        # ring (device i holds p ≡ i mod P), relayouted in/out by the
        # wrapper — results must still be exactly dense attention. Only
        # causal is meaningful here: make_ring_attention downgrades
        # non-causal striped to the contiguous path (nothing to balance),
        # which test_striped_noncausal_downgrades pins.
        q, k, v = _qkv(jax.random.key(5))
        ring = make_ring_attention(
            seq_mesh(), causal=True, compute_dtype=jnp.float32,
            striped=True,
        )
        out = jax.jit(ring)(q, k, v)
        ref = dense_attention(q, k, v, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_striped_noncausal_downgrades_to_contiguous(self):
        # non-causal attention has no mask imbalance: striped=True must
        # produce bit-identical results to the contiguous path (the
        # wrapper skips the relayout entirely)
        if jax.default_backend() == "cpu":
            pytest.xfail("XLA CPU SPMD: PartitionId unsupported on the "
                         "non-causal ring path")
        q, k, v = _qkv(jax.random.key(7))
        a = jax.jit(make_ring_attention(
            seq_mesh(), causal=False, compute_dtype=jnp.float32,
            striped=True))(q, k, v)
        b = jax.jit(make_ring_attention(
            seq_mesh(), causal=False, compute_dtype=jnp.float32,
            striped=False))(q, k, v)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_striped_grads_match_dense(self):
        q, k, v = _qkv(jax.random.key(6))
        ring = make_ring_attention(
            seq_mesh(), compute_dtype=jnp.float32, striped=True
        )

        def loss(args):
            q, k, v = args
            return (ring(q, k, v) ** 2).mean()

        g = jax.jit(jax.grad(loss))((q, k, v))
        g_ref = jax.grad(
            lambda a: (dense_attention(*a, True) ** 2).mean()
        )((q, k, v))
        for got, ref, name in zip(g, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-4,
                err_msg=f"d{name}",
            )

    def test_stripe_indices_roundtrip(self):
        from hpbandster_tpu.ops.ring_attention import stripe_indices

        to_striped, to_natural = stripe_indices(24, 8)
        x = np.arange(24)
        np.testing.assert_array_equal(x[to_striped][to_natural], x)
        # device i's contiguous shard of the striped order holds exactly
        # the positions congruent to i mod P
        striped = x[to_striped]
        for i in range(8):
            shard = striped[i * 3:(i + 1) * 3]
            assert all(p % 8 == i for p in shard), (i, shard)

    def test_composes_inside_user_shard_map(self):
        # ring_attention_block is usable inside an existing shard_map —
        # the composition seam for mixing seq parallelism with other axes
        from jax.sharding import PartitionSpec

        from jax import shard_map

        q, k, v = _qkv(jax.random.key(4))
        mesh = seq_mesh()
        spec = PartitionSpec("seq", None, None)
        out = jax.jit(shard_map(
            lambda qb, kb, vb: ring_attention_block(
                qb, kb, vb, "seq", compute_dtype=jnp.float32
            ),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        ))(q, k, v)
        ref = dense_attention(q, k, v, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )
