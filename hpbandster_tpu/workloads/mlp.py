"""MLP hyperparameter-search workload — the flagship batched-training path.

BASELINE.json config 3 ("MLP with JAX-trainable worker"): every config is a full
MLP training run (SGD with momentum + weight decay on a classification set),
and the *whole config batch trains simultaneously* — parameters for all
configs are stacked on a leading config axis and the training loop is one
``vmap``-ed, jitted computation. On a mesh, the config axis shards across
devices ('config') and the hidden dimension can shard across 'model',
turning the per-config matmuls into MXU-friendly batched GEMMs.

Budget = number of SGD steps, consumed by a ``lax.while_loop`` with a traced
bound so every rung of the ladder shares one compilation.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from hpbandster_tpu.space import ConfigurationSpace, UniformFloatHyperparameter
from hpbandster_tpu.workloads.train import momentum_sgd_train

__all__ = [
    "mlp_space",
    "decode_mlp_hparams",
    "init_mlp_params",
    "mlp_forward",
    "make_synthetic_dataset",
    "make_mlp_eval_fn",
    "batched_sgd_train_step",
    "MLPConfig",
]


class MLPConfig(NamedTuple):
    d_in: int = 16
    width: int = 64
    n_classes: int = 8
    n_train: int = 512
    n_val: int = 256
    batch_size: int = 128


def mlp_space(seed=None) -> ConfigurationSpace:
    """lr (log), momentum, weight decay (log), init scale (log)."""
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


def decode_mlp_hparams(vec: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Unit-cube vector -> (lr, momentum, weight_decay, init_scale).

    Must mirror mlp_space()'s codec (log ranges) so host dicts and device
    vectors decode identically.
    """
    lr = 10.0 ** (-4.0 + 4.0 * vec[0])
    momentum = 0.99 * vec[1]
    wd = 10.0 ** (-7.0 + 5.0 * vec[2])
    init_scale = 10.0 ** (-1.0 + 2.0 * vec[3])
    return lr, momentum, wd, init_scale


def init_mlp_params(key: jax.Array, cfg: MLPConfig, init_scale) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = init_scale * (2.0 / cfg.d_in) ** 0.5
    s2 = init_scale * (2.0 / cfg.width) ** 0.5
    return {
        "w1": (s1 * jax.random.normal(k1, (cfg.d_in, cfg.width))).astype(jnp.float32),
        "b1": jnp.zeros((cfg.width,), jnp.float32),
        "w2": (s2 * jax.random.normal(k2, (cfg.width, cfg.width))).astype(jnp.float32),
        "b2": jnp.zeros((cfg.width,), jnp.float32),
        "w3": (s2 * jax.random.normal(k3, (cfg.width, cfg.n_classes))).astype(
            jnp.float32
        ),
        "b3": jnp.zeros((cfg.n_classes,), jnp.float32),
    }


def mlp_forward(params: dict, x: jax.Array) -> jax.Array:
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    h = jnp.tanh(h @ params["w2"] + params["b2"])
    return h @ params["w3"] + params["b3"]


def _xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()


def make_synthetic_dataset(key: jax.Array, cfg: MLPConfig):
    """Gaussian class blobs: learnable but not trivial (overlapping)."""
    kc, kx, kv = jax.random.split(key, 3)
    centers = 2.0 * jax.random.normal(kc, (cfg.n_classes, cfg.d_in))

    def draw(k, n):
        k1, k2 = jax.random.split(k)
        labels = jax.random.randint(k1, (n,), 0, cfg.n_classes)
        x = centers[labels] + 1.5 * jax.random.normal(k2, (n, cfg.d_in))
        return x.astype(jnp.float32), labels

    train = draw(kx, cfg.n_train)
    val = draw(kv, cfg.n_val)
    return train, val


def _train_loop(params, hp, train, val, budget, cfg: MLPConfig):
    lr, momentum, wd, _ = hp

    def loss_fn(p, xb, yb):
        return _xent(mlp_forward(p, xb), yb)

    params = momentum_sgd_train(
        params, lr, momentum, wd, train, budget, loss_fn,
        cfg.batch_size, cfg.n_train,
    )
    x_v, y_v = val
    return _xent(mlp_forward(params, x_v), y_v)


def make_mlp_eval_fn(cfg: MLPConfig = MLPConfig(), data_seed: int = 0):
    """Build ``eval_fn(config_vec, budget) -> val_loss`` for VmapBackend.

    The dataset and the init key are fixed (closed over) so the objective is
    deterministic per config — the property SURVEY.md §4 calls out for
    testable HPO workloads.
    """
    train, val = make_synthetic_dataset(jax.random.key(data_seed), cfg)
    init_key = jax.random.key(data_seed + 1)

    def eval_fn(vec: jax.Array, budget) -> jax.Array:
        hp = decode_mlp_hparams(vec)
        params = init_mlp_params(init_key, cfg, hp[3])
        budget_arr = jnp.asarray(budget, jnp.float32)
        return _train_loop(params, hp, train, val, budget_arr, cfg)

    return eval_fn


def sgd_train_step_batch(params_batch, velocity_batch, x, y, lrs, momenta, wds):
    """One SGD-with-momentum step for a whole *batch of models* at once.

    ``params_batch`` leaves carry a leading config axis; ``x``/``y`` are
    shared. This is the full training step the multi-chip dry-run shards:
    config axis over 'config', hidden dims over 'model'. Unjitted so callers
    can wrap it with their own shardings.
    """

    def one(p, v, lr, mom, wd):
        g = jax.grad(lambda q: _xent(mlp_forward(q, x), y))(p)
        v = jax.tree.map(lambda vi, gi, pi: mom * vi + gi + wd * pi, v, g, p)
        p = jax.tree.map(lambda pi, vi: pi - lr * vi, p, v)
        loss = _xent(mlp_forward(p, x), y)
        return p, v, loss

    return jax.vmap(one)(params_batch, velocity_batch, lrs, momenta, wds)


batched_sgd_train_step = partial(jax.jit, donate_argnums=(0, 1))(
    sgd_train_step_batch
)
