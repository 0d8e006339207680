"""A block of Mellum2-12B-A2.5B-Instruct as a rung's lane.

The published model (``model_type`` ``mellum``; JetBrains' code model;
widths from its ``config.json``): pre-norm residual layers ``h +=
Attention(RMSNorm(h)); h += Experts(RMSNorm(h))`` with grouped-query
attention (32 query heads on 4 key/value heads of 128) under rotary
positions, three layers in four seeing a sliding window of 1,024 positions
(plain RoPE, theta 500,000) and the fourth the whole causal past
(YaRN-interpolated frequencies, factor 16 over 8,192, and an attention
factor on cos and sin), and in every layer 64 softmax-routed experts of
width 896, 8 a token, renormalised, no shared one. A final RMSNorm and an
untied head close it.

What trains here is **one chip's share** (:class:`Mellum2Config`'s cut):
``layer_kinds`` (one period: sliding, sliding, sliding, full),
``experts_held`` (16 of the 64: the router keeps its 64 outputs and its 8
a token, this chip adds ``w_e * E_e(x)`` only for chosen experts it holds)
and ``vocab_rows`` (a quarter of the vocabulary). The search space, the
rule for a product's operands, the expert layer, embedding and head, the
tokens and the trainer are every lane's (``workloads/lane.py``); this file
has the attention, the configuration and the footprint.

**One attention function for both kinds of layer**
(:func:`banded_attention`, ``window`` a number or ``None``): queries in
blocks, each block against the keys from the block that holds the first
position it may see to its own end and no others, the 8 query heads of a
key/value head as rows of one product (no head is repeated), as many
key/value heads at a time as keep the scores alive under
``_SCORES_AT_ONCE``, each block's scores recomputed in the backward pass
(``jax.checkpoint``). A block of scores wholly outside the band or above the
diagonal is never computed (:func:`attention_key_blocks` counts them), no
``S x S`` array exists in either kind, and a window layer's products are two
blocks of keys wide whatever the length. Plain JAX, differentiated by JAX:
the form off the chip. On a TPU ``lane.attention_mixer`` takes the fused
kernels of ``ops/pallas_attention.py`` for both kinds (a tile's scores never
leave VMEM, the band is the window and one block of queries wide, the
backward pass is the kernels' own).

Precision as the Kimi-Linear lane states it: float32 parameters, momentum
and gradients; matrix-product operands bfloat16 with float32 accumulation;
the router's product, softmax, rotary tables, norms and the loss float32.
What ``config.json`` does not settle is ``assumed`` in
``benchmark/configs/mellum2-sgd.json``: no per-head norm on queries and
keys, rotate-half pairing over the whole head, softmax before the top 8 and
renormalisation after, a position sees itself and the ``window - 1`` before
it, no auxiliary loss, no multi-token-prediction head.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads.lane import (  # noqa: F401 - the lane's public names
    LANE_COUNTERS,
    _SCORES_AT_ONCE,
    _attention_spans,
    _rms,
    _rotate,
    attention_key_blocks,
    banded_attention,
    make_token_dataset,
    moe_held_experts,
)

__all__ = [
    "ATTENTION_COUNTERS",
    "Mellum2Config",
    "attention_key_blocks",
    "banded_attention",
    "init_mellum2_params",
    "make_mellum2_eval_fn",
    "mellum2_forward",
    "mellum2_lane_bytes",
    "mellum2_loss",
    "mellum2_space",
    "rotary_inv_freq",
]

#: static facts of the blocking that ride beside :data:`LANE_COUNTERS`, per
#: training pass: the key blocks of scores the lane computes, and those of
#: the full ``S x S`` squares of its layers
ATTENTION_COUNTERS = ("attn_key_blocks_computed", "attn_key_blocks_square")

#: lr (log), momentum, weight decay (log), init scale (log): every lane's
mellum2_space = lane.lane_space


class Mellum2Config(NamedTuple):
    """Published widths as defaults, then the cut, then the data."""

    hidden_size: int = 2304
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 896
    num_experts_per_token: int = 8
    sliding_window: int = 1024
    rope_theta: float = 500000.0
    #: rope_parameters.full_attention (``rope_type`` ``yarn``)
    yarn_factor: float = 16.0
    yarn_original_max_position: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    rms_norm_eps: float = 1e-6
    #: the cut: layers 0-3 of 28, one whole period
    layer_kinds: Tuple[str, ...] = ("sliding", "sliding", "sliding", "full")
    #: which of the routed experts this chip holds, of ``router_outputs``
    experts_held: Tuple[int, ...] = tuple(range(16))
    router_outputs: int = 64
    vocab_rows: int = 24576
    #: data: tokens a step, sequences to cycle through and held out
    seq_len: int = 8192
    n_train: int = 32
    n_val: int = 1
    #: how the program computes it, not what: the block of queries (the
    #: tests' lanes of 64 tokens take 16)
    attn_query_block: int = 1024


def _experts(cfg: Mellum2Config) -> lane.ExpertLayer:
    return lane.ExpertLayer(
        outputs=cfg.router_outputs, top_k=cfg.num_experts_per_token,
        held=cfg.experts_held, score="softmax")


# ------------------------------------------------------------- parameters
def _layer_shapes(cfg: Mellum2Config) -> dict:
    d, dh = cfg.hidden_size, cfg.head_dim
    f, e = cfg.moe_intermediate_size, len(cfg.experts_held)
    return dict(
        norm1=(d,), norm2=(d,),
        wq=(d, cfg.num_heads * dh), wk=(d, cfg.num_kv_heads * dh),
        wv=(d, cfg.num_kv_heads * dh), wo=(cfg.num_heads * dh, d),
        router=(d, cfg.router_outputs),
        e_gate=(e, d, f), e_up=(e, d, f), e_down=(e, f, d),
    )


def init_mellum2_params(key: jax.Array, cfg: Mellum2Config, init_scale) -> dict:
    return lane._init_params(
        key, cfg, [_layer_shapes(cfg)] * len(cfg.layer_kinds), init_scale)


# -------------------------------------------------------------- positions
def _yarn(cfg: Mellum2Config) -> lane.Yarn:
    return lane.Yarn(cfg.yarn_factor, cfg.yarn_original_max_position, cfg.yarn_beta_fast,
                     cfg.yarn_beta_slow, cfg.yarn_attention_factor)


def rotary_inv_freq(cfg: Mellum2Config, kind: str):
    """``(inv_freq f64[head_dim / 2], factor)`` of a layer of ``kind``
    (``lane.rotary_inv_freq`` over the whole head): a window layer plain
    RoPE, ``theta^(-2i / d)`` and 1; a full layer YaRN's ramp, cos and sin
    both carrying the attention factor."""
    return lane.rotary_inv_freq(
        cfg.head_dim, cfg.rope_theta, None if kind == "sliding" else _yarn(cfg))


def yarn_correction_range(cfg: Mellum2Config):
    """``(low, high)`` of the full layers' ramp (``lane.yarn_correction_range``)."""
    return lane.yarn_correction_range(cfg.head_dim, cfg.rope_theta, _yarn(cfg))


def _rotary_tables(cfg: Mellum2Config, kind: str, t: int):
    """``(cos, sin)`` f32[T, head_dim] of a layer of ``kind``."""
    return lane._rotary_tables(*rotary_inv_freq(cfg, kind), t)


# --------------------------------------------------------------- attention
def _attention(x, p, kind: str, cfg: Mellum2Config):
    """A layer's mixer, from the norm's output to ``W_o``
    (``lane.attention_mixer``: every lane's causal softmax attention)."""
    inv_freq, factor = rotary_inv_freq(cfg, kind)
    return lane.attention_mixer(
        x, p, kv_heads=cfg.num_kv_heads,
        heads_per_kv=cfg.num_heads // cfg.num_kv_heads, head_dim=cfg.head_dim,
        inv_freq=inv_freq, factor=factor,
        sight=cfg.sliding_window if kind == "sliding" else None,
        block=cfg.attn_query_block, scope=_MIXER_SCOPE[kind])


#: the scope of a layer's mixer by its kind (``obs.timeline.LANE_SCOPES``)
_MIXER_SCOPE = {"sliding": "lane.swa", "full": "lane.gqa"}


def _layer(h, p, kind: str, cfg: Mellum2Config):
    x = _rms(h, p["norm1"], cfg.rms_norm_eps)
    with jax.named_scope(_MIXER_SCOPE[kind]):
        h = h + _attention(x, p, kind, cfg)
    x = _rms(h, p["norm2"], cfg.rms_norm_eps)
    with jax.named_scope("lane.moe"):
        y, counters = moe_held_experts(x, p, _experts(cfg))
    return h + y, counters


def _layers(cfg: Mellum2Config):
    return [lambda h, p, kind=kind: _layer(h, p, kind, cfg) for kind in cfg.layer_kinds]


def mellum2_loss(params: dict, tokens: jax.Array, cfg: Mellum2Config):
    """``tokens`` i32[T + 1] -> ``(mean next-token cross-entropy over the
    vocabulary slice, counters f32[n_layers, 2])``."""
    layers = _layers(cfg)
    loss, (_, counters) = lane._loss(
        params, tokens, lane.once_through(layers, counted=len(LANE_COUNTERS)),
        lane.head_exit(len(layers), cfg.rms_norm_eps))
    return loss, counters


def mellum2_forward(params: dict, tokens: jax.Array, cfg: Mellum2Config):
    """:func:`mellum2_loss` with nothing kept for a gradient but the input
    of every layer: ``(loss, counters, [h_0 .. h_L])``, what the lanes'
    trainer takes the gradient from (``lane._forward``)."""
    layers = _layers(cfg)
    loss, (counters, _), hs, _ = lane._forward(
        params, tokens, lane.once_through(layers, counted=len(LANE_COUNTERS)),
        lane.head_exit(len(layers), cfg.rms_norm_eps))
    return loss, counters, hs


# ------------------------------------------------------------- evaluation
def _windows(cfg: Mellum2Config):
    """Each layer's window, None a full-attention layer's."""
    return [cfg.sliding_window if kind == "sliding" else None
            for kind in cfg.layer_kinds]


def mellum2_lane_bytes(cfg: Mellum2Config) -> int:
    """Device bytes one lane needs while it trains: float32 parameters,
    momentum and gradients (12 bytes a parameter) and the peak of its
    activations: the logits, their softmax and their gradient, a layer's
    input per layer, one layer's recomputed activations (about 24
    hidden-sized rows a token: projections, rotated heads, their
    gradients) and what attention keeps alive of its scores
    (``lane.attention_alive_bytes``: three copies of the widest block in
    plain JAX, the fused kernels' output and log-sum-exp on the chip). At
    the published widths it gives 12.5 GB in plain JAX (11.9 GB with the
    kernels) where the chip's compiler counts 12.0 GB for the bracket and
    its allocator peaks at 8.5 GB: one lane fits a 16.9 GB chip, two do
    not."""
    n_params = lane._count_params(
        lambda: init_mellum2_params(jax.random.key(0), cfg, 1.0))
    t = cfg.seq_len
    activations = (
        4 * t * (3 * cfg.vocab_rows + (24 + len(cfg.layer_kinds)) * cfg.hidden_size)
        + lane.attention_alive_bytes(
            t, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim,
            _windows(cfg), cfg.attn_query_block))
    return 12 * n_params + activations


def make_mellum2_eval_fn(cfg: Mellum2Config = Mellum2Config(), data_seed: int = 0):
    """``eval_fn(config_vec, budget) -> held-out cross-entropy`` of the
    lane, by the lanes' one trainer (``lane.make_lane_eval_fn``: budget is
    momentum-SGD steps of one ``seq_len``-token sequence);
    ``eval_fn.lane_facts`` states its footprint, its tokens a step and its
    counters: :data:`LANE_COUNTERS` from the device, then
    :data:`ATTENTION_COUNTERS`, facts of the blocking (the fused kernels'
    tiles where they run), ``lane.attention_counters``, whether they do, and
    ``lane.expert_layer_counters``, how the expert layer moves its rows and
    whether its products are the grouped kernels'."""
    init_key = jax.random.key(data_seed + 1)
    heads_per_kv = cfg.num_heads // cfg.num_kv_heads
    blocks = attention_key_blocks(
        cfg.seq_len, _windows(cfg), cfg.attn_query_block,
        lane._kernel_tiles(cfg.seq_len, cfg.head_dim, heads_per_kv, cfg.num_kv_heads))
    layers = _layers(cfg)
    return lane.make_lane_eval_fn(
        init=lane.Init(init_mellum2_params, init_key, cfg),
        visits=lane.once_through(layers, counted=len(LANE_COUNTERS)),
        exits=lane.head_exit(len(layers), cfg.rms_norm_eps),
        data=make_token_dataset(jax.random.key(data_seed), cfg),
        lane_bytes=mellum2_lane_bytes(cfg),
        counted=lane.expert_counters(
            [True] * len(layers), cfg.seq_len * cfg.num_experts_per_token),
        static_counters=tuple(zip(ATTENTION_COUNTERS, blocks)) + lane.attention_counters(
            cfg.seq_len, cfg.head_dim, heads_per_kv, cfg.num_kv_heads
        ) + lane.expert_layer_counters(
                cfg.seq_len * cfg.num_experts_per_token, cfg.hidden_size,
                cfg.moe_intermediate_size))
