"""Vmapped SGD ensembles: real-model training under the fused sweep.

The :class:`~hpbandster_tpu.ops.fused.StatefulEval` reference
implementation (docs/workloads.md): one device program trains a whole
rung of MLPs at once — parameters and momentum buffers for every config
stack on a leading config axis, the SGD step is ``vmap``-ed over that
axis, and budget = CUMULATIVE SGD step count consumed incrementally by a
``lax.scan`` with a static trip count per rung. Promotion gathers the
surviving lanes' live ``(params, velocity)`` pytrees by the rung's top-k
indices, so a promoted config CONTINUES training from its own weights
(warm continuation, bit-identical to an uninterrupted run of the same
cumulative step count — pinned in ``tests/test_ensemble.py``), while an
evicted lane simply drops out of the gather and is re-created in-trace
by the next bracket's ``init_fn``.

Crash containment is by construction: every per-lane quantity (grads,
velocity, loss) is computed inside the per-lane ``vmap`` body with no
cross-lane reduction anywhere, so a diverged (NaN) model can never
pollute a surviving lane's state — its NaN loss ranks behind every real
loss in the bracket via the shared crash key, exactly like the surrogate
path.

Sharding follows the SNIPPETS ``shard_params`` naive path: every state
leaf's leading config axis shards over the mesh's 'config' axis when
divisible, else stays replicated/XLA-chosen. ``match_partition_rules``
regex trees (per-leaf 2-D model x config specs) are reserved for a
future model-parallel mesh — at MLP sizes the config axis is the only
one worth cutting.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from hpbandster_tpu.obs.runtime import tracked_jit
from hpbandster_tpu.ops.fused import StatefulEval, shard_rows
from hpbandster_tpu.workloads.mlp import (
    MLPConfig,
    _xent,
    decode_mlp_hparams,
    init_mlp_params,
    make_synthetic_dataset,
    mlp_forward,
)

__all__ = [
    "EnsembleState",
    "ensemble_lane_bytes",
    "make_mlp_ensemble",
    "make_uninterrupted_train_fn",
    "shard_ensemble_state",
]


class EnsembleState(NamedTuple):
    """Live training state for a whole rung: every leaf carries a leading
    config axis (lane ``i`` belongs to config row ``i``). A NamedTuple is
    a registered pytree, so the bracket's survivor gather is one
    ``jax.tree.map`` and the leaves ride sharding constraints,
    donation and ``lax.scan`` carries without any custom flattening."""

    params: dict
    velocity: dict


def _steps(budget) -> int:
    """Budget -> cumulative SGD step count. Budgets arrive as the plan's
    concrete floats; the ladder semantics need exact integers (a rung
    trains ``steps(b_s) - steps(b_{s-1})`` fresh steps), so round rather
    than truncate — 26.999999 means 27."""
    return int(round(float(budget)))


def ensemble_lane_bytes(cfg: MLPConfig = MLPConfig()) -> int:
    """Device bytes ONE lane of ensemble state occupies (f32 params +
    same-shape momentum buffer). The per-rung memory formula
    (docs/workloads.md) is ``n_configs * ensemble_lane_bytes(cfg)`` plus
    the shared dataset — the number to check against per-device HBM
    before scaling a rung up."""
    n_params = (
        cfg.d_in * cfg.width + cfg.width          # w1, b1
        + cfg.width * cfg.width + cfg.width       # w2, b2
        + cfg.width * cfg.n_classes + cfg.n_classes  # w3, b3
    )
    return 2 * 4 * n_params  # params + velocity, 4 bytes each


def shard_ensemble_state(state, mesh, axis: str = "config"):
    """Naive-path sharding for an ensemble state (SNIPPETS
    ``shard_params``): constrain every leaf's leading config axis over
    ``axis`` when the lane count divides the mesh, else leave the leaf
    to XLA. Identity on values — a constraint never changes bits, the
    same contract :func:`~hpbandster_tpu.ops.fused.shard_rows` pins for
    loss batches. The fused bracket applies this automatically between
    rungs; call it directly only when driving ``step_fn`` by hand on a
    mesh."""
    return jax.tree.map(lambda leaf: shard_rows(leaf, mesh, axis), state)


def make_mlp_ensemble(
    cfg: MLPConfig = MLPConfig(), data_seed: int = 0
) -> StatefulEval:
    """Build the vmapped-SGD MLP ensemble as a :class:`StatefulEval`.

    Dataset and init key are fixed (closed over), so lane ``i``'s
    trajectory is a pure function of its config vector and cumulative
    step count — the determinism the warm-continuation bit-parity test
    relies on. ``init_fn`` maps config vectors to fresh
    ``(params, velocity)`` lanes (per-config ``init_scale``, shared init
    key — configs differ by hyperparameters, not draws, mirroring
    ``make_mlp_eval_fn``); ``step_fn`` advances each lane from
    ``prev_budget`` to ``budget`` cumulative steps, cycling minibatches
    from offset ``steps(prev_budget)`` so the resumed schedule is
    bitwise the uninterrupted one, and returns validation losses.
    """
    train, val = make_synthetic_dataset(jax.random.key(data_seed), cfg)
    init_key = jax.random.key(data_seed + 1)
    x_tr, y_tr = train
    x_val, y_val = val
    batch_size = min(int(cfg.batch_size), int(cfg.n_train))
    n_batches = max(int(cfg.n_train) // batch_size, 1)
    grad_fn = jax.grad(lambda p, xb, yb: _xent(mlp_forward(p, xb), yb))

    def init_one(vec: jax.Array) -> EnsembleState:
        hp = decode_mlp_hparams(vec)
        params = init_mlp_params(init_key, cfg, hp[3])
        return EnsembleState(params, jax.tree.map(jnp.zeros_like, params))

    # device phase names (obs.timeline.DEVICE_SCOPES): lane set-up and the
    # SGD steps are "hpb.train", the loss pass that ends a rung
    # "hpb.validate", so a trace tells the trainer's seconds apart
    def init_fn(vectors: jax.Array) -> EnsembleState:
        with jax.named_scope("hpb.train"):
            return jax.vmap(init_one)(vectors)

    def train_one(state: EnsembleState, vec: jax.Array, n_steps: int,
                  step0: int):
        with jax.named_scope("hpb.train"):
            lr, momentum, wd, _ = decode_mlp_hparams(vec)

        def body(carry, t):
            p, v = carry
            start = ((t + step0) % n_batches) * batch_size
            xb = jax.lax.dynamic_slice_in_dim(x_tr, start, batch_size)
            yb = jax.lax.dynamic_slice_in_dim(y_tr, start, batch_size)
            g = grad_fn(p, xb, yb)
            v = jax.tree.map(
                lambda vi, gi, pi: momentum * vi + gi + wd * pi, v, g, p
            )
            p = jax.tree.map(lambda pi, vi: pi - lr * vi, p, v)
            return (p, v), None

        # scan, not while_loop: the trip count is static (concrete rung
        # budgets), which XLA unrolls/pipelines better and keeps the
        # minibatch offset arithmetic pure index math
        with jax.named_scope("hpb.train"):
            (p, v), _ = jax.lax.scan(
                body, (state.params, state.velocity),
                jnp.arange(n_steps, dtype=jnp.int32),
            )
        with jax.named_scope("hpb.validate"):
            loss = _xent(mlp_forward(p, x_val), y_val)
        return EnsembleState(p, v), loss

    def step_fn(state: EnsembleState, vectors: jax.Array, budget,
                prev_budget):
        n_new = _steps(budget) - _steps(prev_budget)
        if n_new < 0:
            raise ValueError(
                f"budget ladder must be non-decreasing: {prev_budget} -> "
                f"{budget}"
            )
        step0 = _steps(prev_budget)
        return jax.vmap(
            lambda s, v: train_one(s, v, n_new, step0)
        )(state, vectors)

    return StatefulEval(init_fn=init_fn, step_fn=step_fn)


def make_uninterrupted_train_fn(
    cfg: MLPConfig = MLPConfig(), data_seed: int = 0
):
    """Reference trainer for the warm-continuation parity bar: train a
    fresh ensemble straight to ``n_steps`` cumulative steps in one
    segment. ``fn(vectors f32[n, d], n_steps) -> (EnsembleState,
    losses f32[n])``; the carried state a promoted lane exits the rung
    ladder with must be BITWISE this function's output at the same
    cumulative step count (tests/test_ensemble.py)."""
    se = make_mlp_ensemble(cfg, data_seed)

    def uninterrupted_train(vectors: jax.Array, n_steps: int):
        return se.step_fn(se.init_fn(vectors), vectors, float(n_steps), 0.0)

    # donation contract (docs/perf_notes.md): the only input is the tiny
    # [n, d] config batch, which no output aliases (the returned state
    # leaves are model-shaped) — donating would be a warning-only no-op,
    # declined explicitly.
    return tracked_jit(
        uninterrupted_train, name="ensemble_train", static_argnums=(1,),
        donate_argnums=(),
    )
