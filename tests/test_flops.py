"""Analytic FLOPs models vs XLA's own cost analysis (VERDICT r2 #1).

Each workload's per-step formula is pinned against ``cost_analysis()`` of a
compiled single training step. The analytic model counts matmul/conv FLOPs
only and charges backward = 2x forward per layer; XLA's count adds
elementwise work but *omits* the first layer's input gradient (not needed —
its input is data). At these shapes both effects are small, so the ratio
must sit near 1 — a transposed kernel, a missing conv, or a wrong stride
shifts it far outside the window.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.workloads.flops import (
    cnn_forward_flops,
    cnn_step_flops,
    mlp_step_flops,
    peak_bf16_flops,
    resnet_step_flops,
    sweep_training_flops,
    teacher_epoch_flops,
    transformer_step_flops,
)

RATIO_LO, RATIO_HI = 0.80, 1.45


def _xla_flops(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    return float(cost["flops"])


def _sgd_step(forward, xent):
    def step(params, x, y):
        g = jax.grad(lambda p: xent(forward(p, x), y))(params)
        return jax.tree.map(lambda p, gi: p - 0.1 * gi, params, g)

    return step


class TestStepFlopsVsXLA:
    def test_mlp(self):
        from hpbandster_tpu.workloads.mlp import (
            MLPConfig,
            _xent,
            init_mlp_params,
            mlp_forward,
        )

        cfg = MLPConfig()
        params = init_mlp_params(jax.random.key(0), cfg, 1.0)
        x = jnp.ones((cfg.batch_size, cfg.d_in), jnp.float32)
        y = jnp.zeros((cfg.batch_size,), jnp.int32)
        xla = _xla_flops(_sgd_step(mlp_forward, _xent), params, x, y)
        ratio = xla / mlp_step_flops(cfg)
        assert RATIO_LO < ratio < RATIO_HI, ratio

    def test_cnn(self):
        from hpbandster_tpu.workloads.cnn import (
            CNNConfig,
            _xent,
            cnn_forward,
            init_cnn_params,
        )

        cfg = CNNConfig()
        params = init_cnn_params(jax.random.key(0), cfg, 1.0)
        x = jnp.ones((cfg.batch_size, cfg.image_size, cfg.image_size,
                      cfg.channels), jnp.float32)
        y = jnp.zeros((cfg.batch_size,), jnp.int32)
        xla = _xla_flops(_sgd_step(cnn_forward, _xent), params, x, y)
        ratio = xla / cnn_step_flops(cfg)
        assert RATIO_LO < ratio < RATIO_HI, ratio

    @pytest.mark.slow
    def test_resnet(self):
        from hpbandster_tpu.workloads.cnn import _xent
        from hpbandster_tpu.workloads.resnet import (
            ResNetConfig,
            init_resnet_params,
            resnet_forward,
        )

        cfg = ResNetConfig(batch_size=32)  # keep the CPU compile tractable
        params = init_resnet_params(jax.random.key(0), cfg)
        x = jnp.ones((32, cfg.image_size, cfg.image_size, cfg.channels),
                     jnp.float32)
        y = jnp.zeros((32,), jnp.int32)
        fwd = lambda p, xb: resnet_forward(p, xb, cfg.groups)  # noqa: E731
        xla = _xla_flops(_sgd_step(fwd, _xent), params, x, y)
        ratio = xla / resnet_step_flops(cfg._replace(batch_size=32))
        assert RATIO_LO < ratio < RATIO_HI, ratio

    def test_transformer(self):
        from hpbandster_tpu.workloads.transformer import (
            TransformerConfig,
            _masked_xent,
            init_transformer_params,
        )

        cfg = TransformerConfig(batch_size=32, n_train=32)
        params = init_transformer_params(jax.random.key(0), cfg, 1.0)
        t = cfg.seq_len - 1
        x = jnp.zeros((32, t), jnp.int32)
        y = jnp.zeros((32, t), jnp.int32)
        mask = jnp.ones((t,), jnp.float32)

        def step(params, x, y):
            g = jax.grad(lambda p: _masked_xent(p, x, y, cfg, mask))(params)
            return jax.tree.map(lambda p, gi: p - 0.1 * gi, params, g)

        xla = _xla_flops(step, params, x, y)
        ratio = xla / transformer_step_flops(cfg)
        assert RATIO_LO < ratio < RATIO_HI, ratio

    def test_forward_only_is_one_third(self):
        from hpbandster_tpu.workloads.cnn import CNNConfig

        cfg = CNNConfig()
        assert cnn_step_flops(cfg) == pytest.approx(
            3.0 * cnn_forward_flops(cfg, cfg.batch_size)
        )


class TestAggregation:
    def test_teacher_epoch_counts_steps_per_epoch(self):
        from hpbandster_tpu.workloads.teacher import TeacherConfig

        cfg = TeacherConfig()
        spe = cfg.n_train // cfg.batch_size
        assert teacher_epoch_flops(cfg) == pytest.approx(
            spe * 3.0 * 2.0 * cfg.batch_size * (
                cfg.d_in * cfg.student_width
                + cfg.student_width * cfg.student_width
                + cfg.student_width * cfg.n_classes
            )
        )

    def test_sweep_training_flops_sums_budgets(self):
        class Run:
            def __init__(self, budget, loss):
                self.budget, self.loss = budget, loss

        class FakeResult:
            def get_all_runs(self):
                return [Run(3.0, 0.5), Run(9.0, 0.1), Run(27.0, None)]

        # crashed (None-loss) runs are excluded from the training total
        assert sweep_training_flops(FakeResult(), step_flops=10.0) == 120.0
        assert sweep_training_flops(
            FakeResult(), step_flops=10.0, steps_per_budget_unit=4.0
        ) == 480.0

    def test_peak_lookup(self):
        class Dev:
            def __init__(self, kind):
                self.device_kind = kind

        assert peak_bf16_flops(Dev("TPU v5 lite")) == 197e12
        assert peak_bf16_flops(Dev("TPU v5p chip")) == 459e12
        assert peak_bf16_flops(Dev("TPU v4")) == 275e12
        assert peak_bf16_flops(Dev("cpu")) is None
