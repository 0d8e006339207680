"""A block of SDAR-30B-A3B-Chat as a rung's lane: a model trained by masked
diffusion over blocks.

The published model (``model_type`` ``sdar_moe``; JetLM's block-diffusion
chat model; widths from its ``config.json``): 48 pre-norm residual layers
``h += Attention(RMSNorm(h)); h += Experts(RMSNorm(h))``, all alike.
Attention is grouped-query (32 query heads on 4 key/value heads of 128),
each head of the queries and of the keys through an RMSNorm over its 128
channels before plain RoPE (theta 1,000,000); every layer has 128
softmax-routed experts of 768, 8 a token, renormalised, no shared one. A
final RMSNorm and an untied head close it.

**What is new is the training step**, not a width. A sequence ``x`` of ``S``
tokens in blocks of ``L`` goes through the layers **twice over in one pass,
as 2 S rows**: the clean copy ``E[x_i]`` and a masked copy ``E[x~_i]`` (each
position of block ``b`` replaced by the ``MASK`` id with that block's
probability ``t_b``), row ``i`` and row ``S + i`` both at position ``i``.
What a row sees is ``lane.BlockDiffusion``: a clean row its own block (both
ways) and every earlier one; a masked row the *clean* earlier blocks and
the *masked* copy of its own. Norms, projections, router and experts are row
by row over all ``2 S`` rows with one set of weights. The loss reads the
masked rows alone, at their own tokens (no shift), weighted by the
masked-diffusion bound: ``(1 / S) sum_i (m_i / t_B(i)) * -log softmax(z_i)[x_i]``.
**The noise is data**: ``t`` and ``m`` are drawn once a sequence from the
configuration's data seed beside the tokens (:func:`make_diffusion_dataset`),
so a sequence is a record (tokens, mask, weight), a lane's loss at a rung is
the loss of one trajectory and the trainer needs no key a step.

What trains here is **one chip's share** (:class:`SdarConfig`'s cut):
``num_layers`` (published layers 0-3 of 48: the period is one layer),
``experts_held`` (16 of the 128: the router keeps its 128 outputs and its 8
a token, this chip adds ``w_e * E_e(x)`` only for chosen experts it holds)
and ``vocab_rows`` (an eighth of the vocabulary; ``MASK`` is its last id,
which the data never draws). The search space, the rule for a product's
operands, attention (``lane.attention_mixer``, told the rule of sight and
the per-head norm), the expert layer (``lane.moe_held_experts``) and the
trainer (``lane.make_lane_eval_fn``, told the record by its exits' ``entry``)
are every lane's (``workloads/lane.py``); this file has the layer, the
exit, the data's draw, the configuration and the footprint.

Precision as the other lanes state it: float32 parameters, momentum and
gradients; matrix-product operands bfloat16 with float32 accumulation; the
router's product with float32 operands; the per-head norms, softmax, rotary
tables, norms, the weights ``m / t`` and the loss float32. What
``config.json`` does not settle is ``assumed`` in
``benchmark/configs/sdar-sgd.json``: the block length, the noise schedule
and its floor, the ``MASK`` id, no shift, the per-head norms, no auxiliary
loss.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads.lane import (  # noqa: F401 - the lane's public names
    LANE_COUNTERS,
    BlockDiffusion,
    _mm,
    _rms,
    make_token_dataset,
)

__all__ = [
    "ATTENTION_COUNTERS",
    "DIFFUSION_COUNTERS",
    "SdarConfig",
    "init_sdar_params",
    "make_diffusion_dataset",
    "make_sdar_eval_fn",
    "sdar_forward",
    "sdar_lane_bytes",
    "sdar_loss",
    "sdar_space",
]

#: static facts of the blocking that ride beside :data:`LANE_COUNTERS`, per
#: training pass: the key blocks of scores the lane computes, and those of
#: the full ``2 S x 2 S`` squares of its layers
ATTENTION_COUNTERS = ("attn_key_blocks_computed", "attn_key_blocks_square")

#: what the training by diffusion counts: on the device over the held-out
#: passes, the masked positions over ``S``; static, the rows a data token
#: goes through the layers as (the clean copy and the masked one: 2)
DIFFUSION_COUNTERS = ("diffusion_masked_share", "diffusion_rows_per_token")

#: lr (log), momentum, weight decay (log), init scale (log): every lane's
sdar_space = lane.lane_space


class SdarConfig(NamedTuple):
    """Published widths as defaults, then the cut, then the data."""

    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128            # the router's outputs
    num_experts_per_token: int = 8
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    #: assumed (the catalog gives none): the released chat model's
    block_length: int = 4
    #: assumed: ``t`` uniform on ``[noise_floor, 1]`` (the linear schedule
    #: of masked block diffusion; the floor keeps ``1 / t`` finite)
    noise_floor: float = 1e-3
    #: the cut: layers 0-3 of 48 (every layer is of one kind)
    num_layers: int = 4
    #: which of the routed experts this chip holds
    experts_held: Tuple[int, ...] = tuple(range(16))
    vocab_rows: int = 18992
    #: data: tokens a step (twice as many rows), sequences to cycle through
    #: and held out
    seq_len: int = 4096
    n_train: int = 32
    n_val: int = 1
    #: how the program computes it, not what: the block of queries, whole
    #: diffusion blocks (the tests' lanes of 32 tokens take 16)
    attn_query_block: int = 512


def _experts(cfg: SdarConfig) -> lane.ExpertLayer:
    return lane.ExpertLayer(
        outputs=cfg.num_experts, top_k=cfg.num_experts_per_token,
        held=cfg.experts_held, score="softmax")


def _sight(cfg: SdarConfig) -> BlockDiffusion:
    return BlockDiffusion(cfg.block_length)


def _query_block(cfg: SdarConfig) -> int:
    """The block of queries: a copy's rows are whole blocks of it."""
    return min(cfg.attn_query_block, cfg.seq_len)


def mask_id(cfg: SdarConfig) -> int:
    """``MASK``: the last id of the vocabulary's slice."""
    return cfg.vocab_rows - 1


# ------------------------------------------------------------- parameters
def _layer_shapes(cfg: SdarConfig) -> dict:
    d, dh = cfg.hidden_size, cfg.head_dim
    f, e = cfg.moe_intermediate_size, len(cfg.experts_held)
    return dict(
        norm1=(d,), norm2=(d,),
        wq=(d, cfg.num_heads * dh), wk=(d, cfg.num_kv_heads * dh),
        wv=(d, cfg.num_kv_heads * dh), wo=(cfg.num_heads * dh, d),
        q_norm=(dh,), k_norm=(dh,),
        router=(d, cfg.num_experts),
        e_gate=(e, d, f), e_up=(e, d, f), e_down=(e, f, d),
    )


def init_sdar_params(key: jax.Array, cfg: SdarConfig, init_scale) -> dict:
    """``embed``, ``norm_f``, ``head`` and ``layers``: every layer's leaves
    stacked ``[L, ...]``, slice ``i`` drawn as the leaf ``l<i>/<name>``."""
    params = lane._init_params(
        key, cfg, [_layer_shapes(cfg)] * cfg.num_layers, init_scale)
    layers = [params.pop(f"l{i}") for i in range(cfg.num_layers)]
    params["layers"] = jax.tree.map(lambda *slices: jnp.stack(slices), *layers)
    return params


# ------------------------------------------------------------------- data
def make_diffusion_dataset(key: jax.Array, cfg: SdarConfig):
    """``(train, val)``, each a record of arrays ``[n, S]``: ``tokens``
    i32 (the lanes' Zipf draw with its repeat, :func:`make_token_dataset`,
    over the slice less ``MASK``), ``mask`` bool (``m_i``: position ``i`` of
    block ``b`` is masked with probability ``t_b``, ``t_b`` uniform on
    ``[noise_floor, 1]`` a block) and ``weight`` f32 (``m_i / t_B(i)``)."""
    s, length = cfg.seq_len, cfg.block_length
    tokens = make_token_dataset(key, cfg._replace(vocab_rows=cfg.vocab_rows - 1))

    def record(ids, k):
        k_level, k_mask = jax.random.split(k)
        level = jax.random.uniform(
            k_level, (ids.shape[0], s // length), minval=cfg.noise_floor, maxval=1.0)
        level = jnp.repeat(level, length, axis=1)
        mask = jax.random.uniform(k_mask, (ids.shape[0], s)) < level
        return {"tokens": ids[:, :s], "mask": mask,
                "weight": jnp.where(mask, 1.0 / level, 0.0)}

    k_train, k_val = jax.random.split(jax.random.fold_in(key, 1))
    return record(tokens[0], k_train), record(tokens[1], k_val)


# ------------------------------------------------------------------ layers
def rotary_inv_freq(cfg: SdarConfig):
    """Plain RoPE over the whole head: ``theta^(-2i / d)``."""
    d = cfg.head_dim
    return cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)


def _layer(h, p, cfg: SdarConfig):
    x = _rms(h, p["norm1"], cfg.rms_norm_eps)
    with jax.named_scope("lane.bda"):
        h = h + lane.attention_mixer(
            x, p, kv_heads=cfg.num_kv_heads,
            heads_per_kv=cfg.num_heads // cfg.num_kv_heads, head_dim=cfg.head_dim,
            inv_freq=rotary_inv_freq(cfg), factor=1.0, sight=_sight(cfg),
            block=_query_block(cfg), scope="lane.bda", norm_eps=cfg.rms_norm_eps)
    x = _rms(h, p["norm2"], cfg.rms_norm_eps)
    with jax.named_scope("lane.moe"):
        y, counters = lane.moe_held_experts(x, p, _experts(cfg))
    return h + y, counters


def _visits(cfg: SdarConfig):
    """The layers are alike, so a pass's visits of them are one loop over
    their stacked leaves, traced and compiled once (``lane.Visit.times``).
    Compiled for a described v5e at the published size (PR 42) against a
    trace a layer (``l<i>``, ``lane.once_through``): the bracket's program
    112.0 s and 377 MB of code a layer at a time, 50.2 s and 108 MB as one
    loop: the cell's cold traced run has the driver's 360 s, a third of
    which the unrolled program's compilation alone would take."""
    return (lane.Visit("layers", lambda h, p: _layer(h, p, cfg), times=cfg.num_layers,
                       counted=len(LANE_COUNTERS)),)


def _exits(cfg: SdarConfig) -> lane.Exits:
    """One exit after the last layer, over the masked rows alone: final
    norm, head, the cross-entropy of each masked row at its own token
    weighted ``m / t``, over ``S``; it counts the masked positions. A pass
    starts from the clean copy's embeddings, then the masked copy's."""
    s = cfg.seq_len

    def entry(seq):
        return jnp.concatenate(
            [seq["tokens"], jnp.where(seq["mask"], mask_id(cfg), seq["tokens"])])

    def loss(states, leaves, seq):
        (h,), (norm_f, head) = states, leaves
        with jax.named_scope("lane.head"):
            logp = jax.nn.log_softmax(_mm(_rms(h[s:], norm_f, cfg.rms_norm_eps), head))
            nll = -jnp.take_along_axis(logp, seq["tokens"][:, None], axis=-1)[:, 0]
            return (seq["weight"] * nll).mean()

    def reported(states, leaves, seq):
        with jax.named_scope("lane.head"):
            masked = seq["mask"].sum().astype(jnp.float32)[None]
        return loss(states, leaves, seq), masked

    return lane.Exits(after=(1,), leaves=("norm_f", "head"),
                      trained=loss, reported=reported, counted=1, entry=entry)


def sdar_loss(params: dict, seq: dict, cfg: SdarConfig):
    """``seq`` a record of ``tokens`` i32[S], ``mask`` bool[S], ``weight``
    f32[S] -> ``(the weighted cross-entropy of the masked rows over the
    vocabulary slice, counters f32[1, 3]: the layers' summed)``; for
    ``jax.grad``."""
    loss, (_, counters) = lane._loss(params, seq, _visits(cfg), _exits(cfg))
    return loss, counters


def sdar_forward(params: dict, seq: dict, cfg: SdarConfig):
    """:func:`sdar_loss` with nothing kept for a gradient but the input of
    every layer: ``(loss, counters, [h_0, h_L])``, each state f32[2 S, D]
    (clean rows, then masked), what the lanes' trainer takes the gradient
    from (``lane._forward``, which keeps the layers' inputs stacked)."""
    loss, (counters, _), hs, _ = lane._forward(params, seq, _visits(cfg), _exits(cfg))
    return loss, counters, hs


# ------------------------------------------------------------- evaluation
def sdar_lane_bytes(cfg: SdarConfig) -> int:
    """Device bytes one lane needs while it trains: float32 parameters,
    momentum and gradients (12 bytes a parameter) and the peak of its
    activations: the masked rows' logits, their softmax and their gradient,
    a layer's input per layer and one layer's recomputed activations over
    the ``2 S`` rows (about 24 hidden-sized rows a row, as the Mellum2
    lane's) and what attention keeps alive of its scores
    (``lane.attention_alive_bytes``: three copies of the widest block, a
    masked block's, in plain JAX). At the published widths it gives 8.5 GB:
    one lane fits a 16.9 GB chip, two do not."""
    n_params = lane._count_params(
        lambda: init_sdar_params(jax.random.key(0), cfg, 1.0))
    s = cfg.seq_len
    activations = (
        4 * s * 3 * cfg.vocab_rows
        + 4 * 2 * s * (24 + cfg.num_layers) * cfg.hidden_size
        + lane.attention_alive_bytes(
            2 * s, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim,
            [_sight(cfg)], _query_block(cfg)))
    return 12 * n_params + activations


def make_sdar_eval_fn(cfg: SdarConfig = SdarConfig(), data_seed: int = 0):
    """``eval_fn(config_vec, budget) -> held-out loss`` of the lane, by the
    lanes' one trainer (``lane.make_lane_eval_fn``: budget is momentum-SGD
    steps of one ``seq_len``-token sequence, ``2 seq_len`` rows);
    ``eval_fn.lane_facts`` states its footprint, its data tokens a step and
    its counters: :data:`LANE_COUNTERS` and ``diffusion_masked_share`` from
    the device, then the static ones: :data:`ATTENTION_COUNTERS` (of the
    ``2 S x 2 S`` square), ``lane.attention_counters``,
    ``lane.expert_layer_counters`` and ``diffusion_rows_per_token``."""
    init_key = jax.random.key(data_seed + 1)
    heads_per_kv = cfg.num_heads // cfg.num_kv_heads
    rows, sight = 2 * cfg.seq_len, _sight(cfg)
    choices = rows * cfg.num_experts_per_token
    blocks = lane.attention_key_blocks(
        rows, [sight] * cfg.num_layers, _query_block(cfg),
        lane._kernel_tiles(rows, cfg.head_dim, heads_per_kv, cfg.num_kv_heads, sight))
    # the one visit counts the layers' sum
    experts = lane.expert_counters([True], choices * cfg.num_layers)

    def reduce(moe, exits, n_val):
        held, load, computed = experts.reduce(moe, exits, n_val)
        return [held, load / cfg.num_layers, computed, exits[0] / (n_val * cfg.seq_len)]

    return lane.make_lane_eval_fn(
        init=lane.Init(init_sdar_params, init_key, cfg),
        visits=_visits(cfg), exits=_exits(cfg),
        data=make_diffusion_dataset(jax.random.key(data_seed), cfg),
        lane_bytes=sdar_lane_bytes(cfg), tokens_per_step=cfg.seq_len,
        counted=lane.Counted(
            LANE_COUNTERS + DIFFUSION_COUNTERS[:1], reduce, experts.visits),
        static_counters=tuple(zip(ATTENTION_COUNTERS, blocks))
        + lane.attention_counters(rows, cfg.head_dim, heads_per_kv, cfg.num_kv_heads, sight)
        + lane.expert_layer_counters(choices, cfg.hidden_size, cfg.moe_intermediate_size)
        + ((DIFFUSION_COUNTERS[1], 2),))
