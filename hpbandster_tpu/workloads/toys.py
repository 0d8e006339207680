"""Synthetic HPO objectives (BASELINE.json configs 1-2).

Jittable unit-hypercube objectives with known optima: Branin (2-D) and
Hartmann-6 (6-D) — the BOHB paper's toy benchmarks. Budget enters as a
decaying deterministic noise term so lower fidelities are genuinely noisier,
mimicking a real budget ladder.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from hpbandster_tpu.space import ConfigurationSpace, UniformFloatHyperparameter

__all__ = [
    "branin_space",
    "branin_from_vector",
    "branin_dict",
    "BRANIN_OPT",
    "hartmann6_space",
    "hartmann6_from_vector",
    "HARTMANN6_OPT",
]

BRANIN_OPT = 0.397887
HARTMANN6_OPT = -3.32237


def branin_space(seed=None) -> ConfigurationSpace:
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameter(UniformFloatHyperparameter("x", -5.0, 10.0))
    cs.add_hyperparameter(UniformFloatHyperparameter("y", 0.0, 15.0))
    return cs


def branin_from_vector(vec, budget):
    """Branin on the unit-square codec; global minimum ~0.3979."""
    x = vec[0] * 15.0 - 5.0
    y = vec[1] * 15.0
    b, c = 5.1 / (4 * jnp.pi**2), 5.0 / jnp.pi
    t = 1.0 / (8 * jnp.pi)
    val = (y - b * x**2 + c * x - 6.0) ** 2 + 10.0 * (1 - t) * jnp.cos(x) + 10.0
    noise = 5.0 * jnp.sin(13.7 * x + 7.3 * y) / jnp.sqrt(budget + 1e-9)
    return val + noise


def branin_dict(config, budget):
    """Host-side Branin for Worker.compute-style evaluation."""
    x, y = config["x"], config["y"]
    val = (
        (y - 5.1 / (4 * np.pi**2) * x**2 + 5.0 / np.pi * x - 6.0) ** 2
        + 10 * (1 - 1 / (8 * np.pi)) * np.cos(x)
        + 10
    )
    noise = 5.0 * np.sin(13.7 * x + 7.3 * y) / np.sqrt(budget + 1e-9)
    return float(val + noise)


def hartmann6_space(seed=None) -> ConfigurationSpace:
    cs = ConfigurationSpace(seed=seed)
    for i in range(6):
        cs.add_hyperparameter(UniformFloatHyperparameter(f"x{i}", 0.0, 1.0))
    return cs


# numpy, NOT jnp: module-level device-array creation would initialize the
# jax backend at IMPORT time (slow, and it takes the chip, which belongs
# to one process at a time); numpy constants lift into traces identically
_H6_ALPHA = np.array([1.0, 1.2, 3.0, 3.2], np.float32)
_H6_A = np.array(
    [
        [10, 3, 17, 3.5, 1.7, 8],
        [0.05, 10, 17, 0.1, 8, 14],
        [3, 3.5, 1.7, 10, 17, 8],
        [17, 8, 0.05, 10, 0.1, 14],
    ],
    np.float32,
)
_H6_P = 1e-4 * np.array(
    [
        [1312, 1696, 5569, 124, 8283, 5886],
        [2329, 4135, 8307, 3736, 1004, 9991],
        [2348, 1451, 3522, 2883, 3047, 6650],
        [4047, 8828, 8732, 5743, 1091, 381],
    ],
    np.float32,
)


def hartmann6_from_vector(vec, budget):
    """Hartmann-6 on [0,1]^6; global minimum ~-3.3224."""
    inner = (_H6_A * jnp.square(vec[None, :] - _H6_P)).sum(-1)
    val = -(_H6_ALPHA * jnp.exp(-inner)).sum()
    noise = 0.5 * jnp.sin(31.0 * vec.sum()) / jnp.sqrt(budget + 1e-9)
    return val + noise
