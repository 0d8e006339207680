"""ResNet-18 sweep workload — BASELINE.json config 5 (ResNet-18, eta=4 sweep).

A ResNet-18-shaped network (stem + 4 stages x 2 basic blocks + GAP head)
whose training run is fully jittable and vmappable over a config batch, so a
whole hyperparameter sweep trains as one batched dispatch per SH stage.

TPU-first choices:

* **GroupNorm instead of BatchNorm** — per-sample statistics, so the network
  is semantically identical under ``vmap`` over configs and under 'config'-
  axis sharding (BatchNorm's cross-batch running stats break both); this is
  the idiomatic JAX substitution, not a fidelity loss.
* convolutions in bfloat16 with float32 accumulation (MXU regime).
* residual adds and norms stay float32 for stability.
* budget = SGD steps via ``lax.while_loop`` with a traced bound: one
  compilation covers the whole eta=4 budget ladder.

Reference analog: the reference's example workers (hpbandster/examples
example_5, PyTorch MNIST net with budget = epochs) — here scaled to the
BASELINE.json rung-5 target architecture.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from hpbandster_tpu.space import ConfigurationSpace, UniformFloatHyperparameter
from hpbandster_tpu.workloads.cnn import (
    CNNConfig,
    make_image_dataset,
    momentum_sgd_train,
    _conv,
    _xent,
)

__all__ = [
    "ResNetConfig",
    "resnet_space",
    "decode_resnet_hparams",
    "init_resnet_params",
    "resnet_forward",
    "make_resnet_eval_fn",
]


class ResNetConfig(NamedTuple):
    image_size: int = 32
    channels: int = 3
    width: int = 64          # stem width; stages are (w, 2w, 4w, 8w)
    n_classes: int = 10
    n_train: int = 512
    n_val: int = 256
    batch_size: int = 128
    groups: int = 8          # GroupNorm groups (must divide every stage width)
    #: generalization-axis knobs, shared with the CNN rung's dataset
    #: (train-only label noise + image-noise ceiling; VERDICT r2 #9)
    label_noise: float = 0.05
    image_noise: float = 2.0


def resnet_space(seed=None) -> ConfigurationSpace:
    """lr (log), momentum, weight decay (log), label smoothing."""
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameter(UniformFloatHyperparameter("lr", 1e-4, 1.0, log=True))
    cs.add_hyperparameter(UniformFloatHyperparameter("momentum", 0.0, 0.99))
    cs.add_hyperparameter(
        UniformFloatHyperparameter("weight_decay", 1e-7, 1e-2, log=True)
    )
    cs.add_hyperparameter(
        UniformFloatHyperparameter("label_smoothing", 0.0, 0.2)
    )
    return cs


def decode_resnet_hparams(vec: jax.Array):
    """Unit-cube vector -> (lr, momentum, weight_decay, label_smoothing)."""
    lr = 10.0 ** (-4.0 + 4.0 * vec[0])
    momentum = 0.99 * vec[1]
    wd = 10.0 ** (-7.0 + 5.0 * vec[2])
    ls = 0.2 * vec[3]
    return lr, momentum, wd, ls


def _conv_init(key, kh, kw, c_in, c_out):
    fan_in = kh * kw * c_in
    w = (2.0 / fan_in) ** 0.5 * jax.random.normal(key, (kh, kw, c_in, c_out))
    return w.astype(jnp.float32)


def _group_norm(x, gamma, beta, groups):
    n, h, w, c = x.shape
    xg = x.reshape(n, h, w, groups, c // groups)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + 1e-5)
    return xg.reshape(n, h, w, c) * gamma + beta


def _block_params(key, c_in, c_out):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "conv1": _conv_init(k1, 3, 3, c_in, c_out),
        "g1": jnp.ones((c_out,), jnp.float32),
        "be1": jnp.zeros((c_out,), jnp.float32),
        "conv2": _conv_init(k2, 3, 3, c_out, c_out),
        # zero-init the last norm's scale: blocks start as identity, the
        # standard residual-learning trick that replaces careful warmup
        "g2": jnp.zeros((c_out,), jnp.float32),
        "be2": jnp.zeros((c_out,), jnp.float32),
    }
    if c_in != c_out:
        p["proj"] = _conv_init(k3, 1, 1, c_in, c_out)
    return p


def init_resnet_params(key: jax.Array, cfg: ResNetConfig) -> dict:
    w = cfg.width
    stage_widths = [w, 2 * w, 4 * w, 8 * w]
    keys = jax.random.split(key, 2 + 8)
    params = {
        "stem": _conv_init(keys[0], 3, 3, cfg.channels, w),
        "g0": jnp.ones((w,), jnp.float32),
        "be0": jnp.zeros((w,), jnp.float32),
        "wh": (2.0 / (8 * w)) ** 0.5
        * jax.random.normal(keys[1], (8 * w, cfg.n_classes)).astype(jnp.float32),
        "bh": jnp.zeros((cfg.n_classes,), jnp.float32),
    }
    c_in = w
    ki = 2
    for si, c_out in enumerate(stage_widths):
        for bi in range(2):
            params[f"s{si}b{bi}"] = _block_params(keys[ki], c_in, c_out)
            c_in = c_out
            ki += 1
    return params


def _basic_block(x, p, groups, stride):
    h = _conv(x, p["conv1"], stride=stride)
    h = jax.nn.relu(_group_norm(h, p["g1"], p["be1"], groups))
    h = _conv(h, p["conv2"])
    h = _group_norm(h, p["g2"], p["be2"], groups)
    if "proj" in p:
        x = _conv(x, p["proj"], stride=stride)
    elif stride != 1:
        x = x[:, ::stride, ::stride, :]
    return jax.nn.relu(h + x)


def resnet_forward(params: dict, x: jax.Array, groups: int = 8) -> jax.Array:
    """x: [N, H, W, C] float32 -> logits [N, n_classes]."""
    h = _conv(x, params["stem"])
    h = jax.nn.relu(_group_norm(h, params["g0"], params["be0"], groups))
    for si in range(4):
        for bi in range(2):
            stride = 2 if (si > 0 and bi == 0) else 1
            h = _basic_block(h, params[f"s{si}b{bi}"], groups, stride)
    h = h.mean(axis=(1, 2))
    head = h.astype(jnp.bfloat16) @ params["wh"].astype(jnp.bfloat16)
    return head.astype(jnp.float32) + params["bh"]


def _smoothed_xent(logits, labels, smoothing):
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
    uniform = -logp.mean()
    return (1.0 - smoothing) * nll + smoothing * uniform


def make_resnet_eval_fn(cfg: ResNetConfig = ResNetConfig(), data_seed: int = 0):
    """Build ``eval_fn(config_vec, budget) -> val_loss`` for VmapBackend."""
    data_cfg = CNNConfig(
        image_size=cfg.image_size,
        channels=cfg.channels,
        n_classes=cfg.n_classes,
        n_train=cfg.n_train,
        n_val=cfg.n_val,
        batch_size=cfg.batch_size,
        label_noise=cfg.label_noise,
        image_noise=cfg.image_noise,
    )
    train, (x_v, y_v) = make_image_dataset(jax.random.key(data_seed), data_cfg)
    init_key = jax.random.key(data_seed + 1)

    def eval_fn(vec: jax.Array, budget) -> jax.Array:
        lr, momentum, wd, ls = decode_resnet_hparams(vec)
        params = init_resnet_params(init_key, cfg)

        def loss_fn(p, xb, yb):
            return _smoothed_xent(resnet_forward(p, xb, cfg.groups), yb, ls)

        params = momentum_sgd_train(
            params, lr, momentum, wd, train,
            jnp.asarray(budget, jnp.float32), loss_fn,
            cfg.batch_size, cfg.n_train,
        )
        return _xent(resnet_forward(params, x_v, cfg.groups), y_v)

    return eval_fn
