"""Tests for the transformer workload (attention model family).

Tiny shapes: the suite runs on the virtual 8-device CPU mesh, so the point
is the batched-training contract (finite, deterministic, vmappable, traced
budget) plus the COPY task's semantics — the copied half is predictable
only by attending across the separator, which is what makes val accuracy a
real generalization axis (prefix space >> any training set).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.workloads import (
    TransformerConfig,
    make_copy_dataset,
    make_transformer_accuracy_fn,
    make_transformer_error_fn,
    make_transformer_eval_fn,
    transformer_forward,
    transformer_space,
)
from hpbandster_tpu.workloads.transformer import init_transformer_params

#: contract fixture, not a learning benchmark — but it DOES learn: with
#: lr 0.3 / momentum 0.9 the copy circuit reaches ~0.97 val accuracy at
#: budget 120 (measured on the CPU suite backend; see TestLearnsCopy)
TINY = TransformerConfig(
    vocab=16, prefix_len=7, d_model=32, n_heads=2, n_layers=2, d_ff=128,
    n_train=128, n_val=64, batch_size=64,
)

GOOD = {"lr": 0.3, "momentum": 0.9, "weight_decay": 1e-6, "init_scale": 1.0}


def _good_vec():
    return jnp.asarray(
        transformer_space(seed=0).to_vector(GOOD), jnp.float32
    )


class TestCopyDataset:
    def test_structure_and_mask(self):
        (xt, yt), (xv, yv), mask = make_copy_dataset(jax.random.key(0), TINY)
        t = TINY.seq_len - 1
        assert xt.shape == (TINY.n_train, t) and yt.shape == (TINY.n_train, t)
        assert xv.shape == (TINY.n_val, t)
        # teacher forcing: y is x shifted left by one
        np.testing.assert_array_equal(np.asarray(xt[:, 1:]),
                                      np.asarray(yt[:, :-1]))
        # the masked targets are exactly the copied prefix
        P = TINY.prefix_len
        sel = np.asarray(mask, bool)
        np.testing.assert_array_equal(np.asarray(yt)[:, sel],
                                      np.asarray(xt)[:, :P])
        # separator sits where the mask opens
        assert (np.asarray(xt)[:, P] == TINY.vocab).all()
        assert sel.sum() == P

    def test_deterministic_and_split_disjoint(self):
        (xt, _), (xv, _), _ = make_copy_dataset(jax.random.key(0), TINY)
        (xt2, _), _, _ = make_copy_dataset(jax.random.key(0), TINY)
        np.testing.assert_array_equal(np.asarray(xt), np.asarray(xt2))
        # val prefixes are fresh draws: none should repeat a train row
        tr = {tuple(r) for r in np.asarray(xt)[:, :TINY.prefix_len]}
        va = {tuple(r) for r in np.asarray(xv)[:, :TINY.prefix_len]}
        assert not (tr & va)


class TestTransformerWorkload:
    @pytest.fixture(scope="class")
    def eval_fn(self):
        return jax.jit(make_transformer_eval_fn(TINY))

    def test_forward_shapes(self):
        params = init_transformer_params(jax.random.key(0), TINY, 1.0)
        tokens = jnp.zeros((TINY.seq_len - 1,), jnp.int32)
        logits = transformer_forward(params, tokens, TINY)
        assert logits.shape == (TINY.seq_len - 1, TINY.vocab + 1)
        assert np.isfinite(np.asarray(logits)).all()

    def test_training_reduces_loss(self, eval_fn):
        loss_0 = float(eval_fn(_good_vec(), 0.0))
        loss_n = float(eval_fn(_good_vec(), 120.0))
        assert np.isfinite(loss_0) and np.isfinite(loss_n)
        assert loss_n < loss_0, "120 SGD steps did not improve copy loss"

    def test_vmappable_and_jittable(self):
        eval_fn = make_transformer_eval_fn(TINY)
        cs = transformer_space(seed=1)
        X = jnp.asarray(cs.sample_vectors(4), jnp.float32)
        losses = jax.jit(
            lambda xs, b: jax.vmap(lambda v: eval_fn(v, b))(xs)
        )(X, jnp.float32(5.0))
        assert losses.shape == (4,)
        assert np.isfinite(np.asarray(losses)).all()

    def test_deterministic(self, eval_fn):
        vec = jnp.asarray([0.5, 0.5, 0.5, 0.5], jnp.float32)
        assert float(eval_fn(vec, 10.0)) == float(eval_fn(vec, 10.0))

    def test_error_fn_is_accuracy_twin(self):
        err_fn = jax.jit(make_transformer_error_fn(TINY))
        acc_fn = jax.jit(make_transformer_accuracy_fn(TINY))
        _, va = acc_fn(_good_vec(), 30.0)
        err = err_fn(_good_vec(), 30.0)
        np.testing.assert_allclose(float(err), 1.0 - float(va), atol=1e-6)


class TestSeqParallelForward:
    def test_ring_forward_matches_local_forward(self):
        # the long-context path: sequence sharded over the 8-device ring,
        # attention computed via ppermute rotation — logits must match
        # the single-device forward within bf16 matmul rounding
        from jax.sharding import PartitionSpec

        from jax import shard_map

        from hpbandster_tpu.ops.ring_attention import seq_mesh
        from hpbandster_tpu.workloads.transformer import (
            transformer_forward_seq_parallel,
        )

        # tokens length is seq_len - 1 = 2 * prefix_len; prefix 8 gives 16,
        # divisible by the 8-device ring (shard_map's contract)
        cfg = TINY._replace(prefix_len=8)
        params = init_transformer_params(jax.random.key(0), cfg, 1.0)
        (xt, _), _, _ = make_copy_dataset(jax.random.key(1), cfg)
        tokens = xt[0]
        assert tokens.shape[0] % 8 == 0

        mesh = seq_mesh()
        rep = PartitionSpec()
        seq = PartitionSpec("seq")
        ring_logits = jax.jit(shard_map(
            lambda p, t: transformer_forward_seq_parallel(p, t, cfg, "seq"),
            mesh=mesh,
            in_specs=(rep, seq),
            out_specs=seq,
        ))(params, tokens)
        local_logits = transformer_forward(params, tokens, cfg)
        assert ring_logits.shape == local_logits.shape
        np.testing.assert_allclose(
            np.asarray(ring_logits), np.asarray(local_logits),
            atol=5e-2, rtol=5e-2,
        )

    def test_ring_forward_grads_match_local(self):
        # TRAINING through the seq-parallel path: param gradients must
        # match the local forward's — this covers the ring custom_vjp per
        # layer, the global-position gathers, AND the shard_map transpose
        # psum-ing replicated-param cotangents (a dropped psum would train
        # silently wrong while the forward parity test stayed green)
        from jax.sharding import PartitionSpec

        from jax import shard_map

        from hpbandster_tpu.ops.ring_attention import seq_mesh
        from hpbandster_tpu.workloads.transformer import (
            transformer_forward_seq_parallel,
        )

        cfg = TINY._replace(prefix_len=8)
        params = init_transformer_params(jax.random.key(0), cfg, 1.0)
        (xt, _), _, _ = make_copy_dataset(jax.random.key(1), cfg)
        tokens = xt[0]
        mesh = seq_mesh()
        ring_fwd = shard_map(
            lambda p, t: transformer_forward_seq_parallel(p, t, cfg, "seq"),
            mesh=mesh,
            in_specs=(PartitionSpec(), PartitionSpec("seq")),
            out_specs=PartitionSpec("seq"),
        )
        g_ring = jax.jit(jax.grad(lambda p: (ring_fwd(p, tokens) ** 2)
                                  .mean()))(params)
        g_local = jax.grad(
            lambda p: (transformer_forward(p, tokens, cfg) ** 2).mean()
        )(params)
        def assert_close(a, b, name):
            # both paths run bf16 attention GEMMs whose rounding differs
            # (reordered reductions), so a few elements drift at the 1e-1
            # level on near-cancelling sums. A STRUCTURAL error — dropped
            # psum on replicated-param cotangents (grads scaled ~1/P or
            # one shard's worth), wrong positions, a dead layer — moves
            # the whole tensor, so pin the relative norm of the
            # difference instead of elementwise tolerance.
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            rel = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-6)
            # bar calibrated to measured drift: l0.wq sits at 0.061 on
            # CPU bf16 (reordered-reduction rounding, not structural —
            # structural errors move the norm by O(1), not 0.06)
            assert rel < 0.08, f"{name}: relative grad error {rel:.3f}"

        for name in ("tok_emb", "pos_emb", "head", "ln_f"):
            assert_close(g_ring[name], g_local[name], name)
        for key in ("wq", "wk", "wv", "wo", "w1", "w2"):
            assert_close(g_ring["l0"][key], g_local["l0"][key], f"l0.{key}")


class TestLearnsCopy:
    @pytest.mark.slow
    def test_good_config_learns_the_attention_circuit(self):
        # chance on the copied half is 1/16; the copy is only predictable
        # by attending back across the separator, so clearing 0.8 proves
        # the attention path trains end to end (measured: ~0.97)
        acc_fn = jax.jit(make_transformer_accuracy_fn(TINY))
        _, va = acc_fn(_good_vec(), 120.0)
        assert float(va) >= 0.8, float(va)

    @pytest.mark.slow
    def test_fused_sweep_finds_a_learning_config(self):
        # end-to-end: FusedBOHB over the error objective on a small
        # ladder; the incumbent must beat chance decisively
        from hpbandster_tpu.optimizers import FusedBOHB

        cs = transformer_space(seed=2)
        opt = FusedBOHB(
            configspace=cs, eval_fn=make_transformer_error_fn(TINY),
            run_id="tfm", min_budget=9, max_budget=81, eta=3, seed=2,
            min_points_in_model=5,
        )
        res = opt.run(n_iterations=2)
        opt.shutdown()
        traj = res.get_incumbent_trajectory()
        best_acc = 1.0 - traj["losses"][-1]
        assert np.isfinite(best_acc)
        # the learnable-lr band is narrow (the calibration probe shows most
        # draws stall at chance), so a 2-bracket sweep certifies WIRING +
        # beats-chance, not the documented target, which is the full
        # config's (measured here: 0.292 with seed 2)
        assert best_acc > 0.2, (
            f"incumbent copied-half val acc {best_acc:.3f}: the sweep "
            f"failed to climb decisively above chance (~0.0625)"
        )
