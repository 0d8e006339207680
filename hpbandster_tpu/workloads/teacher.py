"""Teacher-student classification workload — the deterministic "real-ish" rung.

BASELINE.json's ladder calls for dataset workloads (MLP/MNIST, CNN/CIFAR-10),
but this sandbox is offline (SURVEY.md provenance block), so real downloads
are out. This module provides the next-best thing (VERDICT r1 #8): a FIXED
procedurally generated classification problem whose labels come from a
hidden "teacher" MLP, with an i.i.d. train/validation split. Unlike blob or
template toys, generalization is *meaningful* here — the student only
reaches high validation accuracy by actually recovering the teacher's
decision surface, and overfitting the (label-noised) training set hurts
validation — so "budget = epochs" sweeps optimize a real target, and tests
can assert accuracy, not just finite losses.

Determinism: dataset, teacher weights, label noise, and the student init
are all pure functions of ``data_seed`` via ``jax.random`` — identical on
every machine/backend, like the reference's known-optimum toy workers
(SURVEY.md §4 "determinism handling").

Measured calibration (seed 0, default config, budget 27 epochs): random
guessing scores 1/4 = 0.25; the best of 12 random hyperparameter draws
reaches ≈ 0.92 validation accuracy while bad draws stall below 0.4, and
the train/val gap is real (an over-fit student hits ≥ 0.99 train with
≈ 0.85 val) — wide dynamic range for the optimizer to climb and a true
generalization axis. ``TARGET_VAL_ACCURACY = 0.90`` encodes the documented
target that convergence tests report against.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from hpbandster_tpu.space import ConfigurationSpace, UniformFloatHyperparameter
from hpbandster_tpu.workloads.mlp import (
    _xent,
    decode_mlp_hparams,
    init_mlp_params,
    mlp_forward,
    MLPConfig,
)
from hpbandster_tpu.workloads.train import momentum_sgd_train

__all__ = [
    "TeacherConfig",
    "TARGET_VAL_ACCURACY",
    "teacher_space",
    "make_teacher_dataset",
    "make_teacher_eval_fn",
    "make_teacher_accuracy_fn",
]

#: documented, empirically calibrated target (see module docstring) — a
#: small BOHB sweep's incumbent must exceed this on the validation split
TARGET_VAL_ACCURACY = 0.90


class TeacherConfig(NamedTuple):
    d_in: int = 12
    n_classes: int = 4
    teacher_width: int = 8
    #: fraction of training labels flipped to a random class — the trap
    #: that makes train/val generalization a real distinction
    label_noise: float = 0.05
    n_train: int = 4096
    n_val: int = 1024
    student_width: int = 64
    batch_size: int = 128


def teacher_space(seed=None) -> ConfigurationSpace:
    """Same four knobs as ``mlp_space`` (lr, momentum, wd, init_scale) —
    the decode twin is :func:`decode_mlp_hparams`."""
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


def make_teacher_dataset(data_seed: int, cfg: TeacherConfig = TeacherConfig()):
    """Inputs ~ N(0, I); labels = argmax of a fixed random teacher MLP,
    with ``label_noise`` of the TRAIN labels (only) flipped uniformly.

    Returns ``((x_train, y_train), (x_val, y_val))`` — i.i.d. splits of the
    same generative process, so validation measures true generalization.
    """
    k_teacher, k_tr, k_va, k_noise, k_flip = jax.random.split(
        jax.random.key(data_seed), 5
    )
    k_t1, k_t2 = jax.random.split(k_teacher)
    # teacher: one hidden layer, weights fixed by the seed. The 1.8 gain
    # keeps class margins crisp enough that the Bayes error ~ label_noise.
    w1 = 1.8 * jax.random.normal(k_t1, (cfg.d_in, cfg.teacher_width)) / cfg.d_in**0.5
    w2 = 1.8 * jax.random.normal(k_t2, (cfg.teacher_width, cfg.n_classes)) / cfg.teacher_width**0.5

    def label(x):
        return jnp.argmax(jnp.tanh(x @ w1) @ w2, axis=-1)

    x_tr = jax.random.normal(k_tr, (cfg.n_train, cfg.d_in), jnp.float32)
    x_va = jax.random.normal(k_va, (cfg.n_val, cfg.d_in), jnp.float32)
    y_tr, y_va = label(x_tr), label(x_va)

    flip = jax.random.uniform(k_noise, (cfg.n_train,)) < cfg.label_noise
    y_rand = jax.random.randint(k_flip, (cfg.n_train,), 0, cfg.n_classes)
    y_tr = jnp.where(flip, y_rand, y_tr)
    return (x_tr, y_tr), (x_va, y_va)


def _student_cfg(cfg: TeacherConfig) -> MLPConfig:
    return MLPConfig(
        d_in=cfg.d_in,
        width=cfg.student_width,
        n_classes=cfg.n_classes,
        n_train=cfg.n_train,
        n_val=cfg.n_val,
        batch_size=cfg.batch_size,
    )


def _train_student(vec, budget_epochs, train, cfg: TeacherConfig, init_key):
    hp = decode_mlp_hparams(vec)
    scfg = _student_cfg(cfg)
    params = init_mlp_params(init_key, scfg, hp[3])
    steps_per_epoch = max(cfg.n_train // cfg.batch_size, 1)
    steps = jnp.asarray(budget_epochs, jnp.float32) * steps_per_epoch

    def loss_fn(p, xb, yb):
        return _xent(mlp_forward(p, xb), yb)

    return momentum_sgd_train(
        params, hp[0], hp[1], hp[2], train, steps, loss_fn,
        cfg.batch_size, cfg.n_train,
    )


def make_teacher_eval_fn(cfg: TeacherConfig = TeacherConfig(), data_seed: int = 0):
    """``eval_fn(config_vec, budget_epochs) -> validation ERROR RATE``.

    The HPO loss is ``1 - val_accuracy`` (the BOHB paper's convention for
    classification benchmarks), so incumbent trajectories read directly as
    accuracy progress and the documented ``TARGET_VAL_ACCURACY`` maps to
    ``loss < 1 - target``.
    """
    train, val = make_teacher_dataset(data_seed, cfg)
    init_key = jax.random.key(data_seed + 1)

    def eval_fn(vec: jax.Array, budget) -> jax.Array:
        params = _train_student(vec, budget, train, cfg, init_key)
        x_v, y_v = val
        pred = jnp.argmax(mlp_forward(params, x_v), axis=-1)
        return 1.0 - jnp.mean((pred == y_v).astype(jnp.float32))

    return eval_fn


def make_teacher_accuracy_fn(cfg: TeacherConfig = TeacherConfig(), data_seed: int = 0):
    """``acc_fn(config_vec, budget_epochs) -> (train_acc, val_acc)`` — the
    analysis twin of :func:`make_teacher_eval_fn` for tests/notebooks."""
    train, val = make_teacher_dataset(data_seed, cfg)
    init_key = jax.random.key(data_seed + 1)

    def acc_fn(vec: jax.Array, budget) -> Tuple[jax.Array, jax.Array]:
        params = _train_student(vec, budget, train, cfg, init_key)
        accs = []
        for x, y in (train, val):
            pred = jnp.argmax(mlp_forward(params, x), axis=-1)
            accs.append(jnp.mean((pred == y).astype(jnp.float32)))
        return tuple(accs)

    return acc_fn
