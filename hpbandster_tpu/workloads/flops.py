"""Analytic FLOPs models for the built-in training workloads (VERDICT r2 #1).

Grounds the perf story in hardware terms: these per-step costs times the
number of SGD steps a sweep executed, over its wall-clock, are achieved
FLOP/s and **MFU** (fraction of the chip's peak bf16 throughput), instead
of only workload-specific configs/s.

Accounting convention (the standard MFU bookkeeping used for large-model
utilization reports): count matmul/convolution FLOPs only (2 FLOPs per
multiply-accumulate), and charge a training step 3x the forward cost — one
forward pass plus a backward pass that computes both the input gradient and
the weight gradient, each a GEMM of the forward's size. Elementwise ops,
normalizations, pooling, and the optimizer update are excluded (they are
HBM-bound, not MXU work, and amount to a few percent at these shapes).
``tests/test_flops.py`` pins each model against XLA's own
``cost_analysis()`` flop count so the analytic formulas cannot drift from
the compiled computation.
"""

from __future__ import annotations

from typing import Optional

from hpbandster_tpu.workloads.cnn import CNNConfig
from hpbandster_tpu.workloads.mlp import MLPConfig
from hpbandster_tpu.workloads.resnet import ResNetConfig
from hpbandster_tpu.workloads.teacher import TeacherConfig, _student_cfg
from hpbandster_tpu.workloads.transformer import TransformerConfig

__all__ = [
    "mlp_forward_flops",
    "mlp_step_flops",
    "teacher_step_flops",
    "teacher_epoch_flops",
    "cnn_forward_flops",
    "cnn_step_flops",
    "resnet_forward_flops",
    "resnet_step_flops",
    "transformer_forward_flops",
    "transformer_step_flops",
    "peak_bf16_flops",
    "sweep_training_flops",
]

#: per-chip peak dense bf16 FLOP/s by ``device.device_kind`` prefix.
#: v5e ("TPU v5 lite"): 394 TOPS int8 / 197 TFLOP/s bf16; v4: 275; v5p: 459;
#: v6e ("TPU v6 lite", Trillium): 918. Unknown kinds return None — a
#: caller can then report achieved FLOP/s without an MFU percentage, and
#: ``chip_smoke.py`` fails on a chip that has no row here.
_PEAK_BF16 = {
    "TPU v6 lite": 918e12,
    "TPU v5 lite": 197e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v3": 123e12,
}


def peak_bf16_flops(device) -> Optional[float]:
    """Peak dense bf16 FLOP/s for one chip, or None if unknown."""
    kind = str(getattr(device, "device_kind", ""))
    for prefix, peak in _PEAK_BF16.items():
        if kind.startswith(prefix):
            return peak
    return None


def _dense(batch: int, d_in: int, d_out: int) -> float:
    return 2.0 * batch * d_in * d_out


def _conv(batch: int, h_out: int, w_out: int, kh: int, kw: int,
          c_in: int, c_out: int) -> float:
    return 2.0 * batch * h_out * w_out * kh * kw * c_in * c_out


# ------------------------------------------------------------------- MLP
def mlp_forward_flops(cfg: MLPConfig, batch: int) -> float:
    """One forward pass of ``mlp_forward`` (3 dense layers)."""
    return (
        _dense(batch, cfg.d_in, cfg.width)
        + _dense(batch, cfg.width, cfg.width)
        + _dense(batch, cfg.width, cfg.n_classes)
    )


def mlp_step_flops(cfg: MLPConfig) -> float:
    """One momentum-SGD minibatch step for ONE config (3x forward)."""
    batch = min(cfg.batch_size, cfg.n_train)
    return 3.0 * mlp_forward_flops(cfg, batch)


# --------------------------------------------------------------- teacher
def teacher_step_flops(cfg: TeacherConfig = TeacherConfig()) -> float:
    """One student SGD step (the teacher labelling is a one-time dataset
    cost, not part of the sweep's training work)."""
    return mlp_step_flops(_student_cfg(cfg))


def teacher_epoch_flops(cfg: TeacherConfig = TeacherConfig()) -> float:
    """Budget unit for the teacher workload is EPOCHS."""
    steps_per_epoch = max(cfg.n_train // cfg.batch_size, 1)
    return steps_per_epoch * teacher_step_flops(cfg)


# ------------------------------------------------------------------- CNN
def cnn_forward_flops(cfg: CNNConfig, batch: int) -> float:
    """One forward pass of ``cnn_forward``: 3 convs (stride 1, 2, 2,
    SAME padding) + the classifier head."""
    s = cfg.image_size
    w = cfg.width
    s2 = (s + 1) // 2
    s4 = (s2 + 1) // 2
    return (
        _conv(batch, s, s, 3, 3, cfg.channels, w)
        + _conv(batch, s2, s2, 3, 3, w, 2 * w)
        + _conv(batch, s4, s4, 3, 3, 2 * w, 2 * w)
        + _dense(batch, 2 * w, cfg.n_classes)
    )


def cnn_step_flops(cfg: CNNConfig = CNNConfig()) -> float:
    batch = min(cfg.batch_size, cfg.n_train)
    return 3.0 * cnn_forward_flops(cfg, batch)


# ---------------------------------------------------------------- ResNet
def resnet_forward_flops(cfg: ResNetConfig, batch: int) -> float:
    """One forward pass of ``resnet_forward``: stem + 4 stages x 2 basic
    blocks (3x3 + 3x3, 1x1 projection on the widening block) + head."""
    s = cfg.image_size
    w = cfg.width
    total = _conv(batch, s, s, 3, 3, cfg.channels, w)
    c_in, h = w, s
    for si, c_out in enumerate([w, 2 * w, 4 * w, 8 * w]):
        for bi in range(2):
            stride = 2 if (si > 0 and bi == 0) else 1
            h_out = (h + stride - 1) // stride
            total += _conv(batch, h_out, h_out, 3, 3, c_in, c_out)
            total += _conv(batch, h_out, h_out, 3, 3, c_out, c_out)
            if c_in != c_out:
                total += _conv(batch, h_out, h_out, 1, 1, c_in, c_out)
            c_in, h = c_out, h_out
    return total + _dense(batch, 8 * w, cfg.n_classes)


def resnet_step_flops(cfg: ResNetConfig = ResNetConfig()) -> float:
    batch = min(cfg.batch_size, cfg.n_train)
    return 3.0 * resnet_forward_flops(cfg, batch)


# ----------------------------------------------------------- transformer
def transformer_forward_flops(cfg: TransformerConfig, batch: int) -> float:
    """One forward pass of ``transformer_forward`` over a batch: per layer
    QKV/out projections (4 GEMMs), attention scores + mixing (2 T x T
    GEMMs across heads), the 2-GEMM MLP; plus the vocabulary head.
    Embedding/positional lookups are gathers, not MXU work (excluded by
    the module convention)."""
    t = cfg.seq_len - 1
    d = cfg.d_model
    per_layer = (
        4 * _dense(t, d, d)          # wq, wk, wv, wo
        + 2 * 2.0 * t * t * d        # scores q@k^T + mixing att@v
        + _dense(t, d, cfg.d_ff)     # mlp up
        + _dense(t, cfg.d_ff, d)     # mlp down
    )
    head = _dense(t, d, cfg.vocab + 1)
    return batch * (cfg.n_layers * per_layer + head)


def transformer_step_flops(
        cfg: TransformerConfig = TransformerConfig()) -> float:
    batch = min(cfg.batch_size, cfg.n_train)
    return 3.0 * transformer_forward_flops(cfg, batch)


# ------------------------------------------------------------- aggregation
def sweep_training_flops(result, step_flops: float,
                         steps_per_budget_unit: float = 1.0,
                         include_failed: bool = False) -> float:
    """Total model FLOPs a sweep's TRAINING work executed.

    Every run at budget ``b`` trains from scratch for
    ``b * steps_per_budget_unit`` SGD steps (the workloads' contract:
    ``eval_fn`` re-trains per evaluation; promotions do not resume), so the
    sweep total is ``step_flops * sum(budgets) * steps_per_budget_unit``
    over all finished runs. The per-run evaluation forward (one pass over
    the validation split) is excluded — it is <1% of a budget>=3 run.

    ``include_failed``: on the FUSED tier a crashed (NaN-loss) config's
    training steps DID execute on device before being masked, so callers
    measuring device throughput there must pass True or achieved FLOP/s
    and MFU are understated. The host tiers' crashed runs may have aborted
    mid-budget, so the default stays conservative (exclude).
    """
    total_units = sum(
        r.budget for r in result.get_all_runs()
        if include_failed or r.loss is not None
    )
    return step_flops * steps_per_budget_unit * float(total_units)
