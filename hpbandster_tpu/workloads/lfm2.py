"""A block of LFM2-8B-A1B as a rung's lane: mixers that are mostly gated
short convolutions.

The published model (``model_type`` ``lfm2_moe``; LiquidAI's on-device
hybrid; widths from its ``config.json``): 24 pre-norm residual layers ``h +=
Mixer(RMSNorm(h)); h += FFN(RMSNorm(h))``. Eighteen mixers are **gated short
convolutions**: ``B, C, x`` the three thirds of ``a W_in``, a depthwise
causal convolution of 3 taps over ``B * x``, gated again by ``C``, then
``W_out``: no attention, no recurrence. Six (layers 2, 6, 10, 14, 18, 21)
are grouped-query attention, 32 query heads on 8 key/value heads of 64, each
head of the queries and of the keys through an RMSNorm over its 64 channels
before plain RoPE (theta 1,000,000). The first two layers feed forward
through a dense SwiGLU of 7,168, the other 22 through 32 sigmoid-routed
experts of 1,792, 4 a token, chosen by ``s + bias`` and weighed ``s_e /
(sum of the chosen s + 1e-6)``, no shared one. A final RMSNorm closes it and
**the head is the embedding**, transposed.

What trains here is **one chip's share** (:class:`Lfm2Config`'s cut):
``layer_kinds`` (published layers 0, 2, 3, 4, 5: the leading dense layer
once, then one whole period), ``experts_held`` (8 of the 32: the router
keeps its 32 outputs and its 4 a token, this chip adds ``w_e * E_e(x)`` only
for chosen experts it holds) and ``vocab_rows`` (a quarter of the
vocabulary: tied, so the slice is the embedding's and the head's at once).
The search space, the rule for a product's operands, the convolution mixer
(``lane.short_conv_mixer``), attention (``lane.attention_mixer``, told the
per-head norm by the layer's leaves), the expert layer, the SwiGLU, the
tokens and the trainer (told the tie by its exits' leaves) are every lane's
(``workloads/lane.py``); this file has the layers, the configuration and the
footprint.

Precision as the other lanes state it: float32 parameters, momentum and
gradients; matrix-product operands bfloat16 with float32 accumulation; the
router's product with float32 operands; the gates, the convolution, the
per-head norms, softmax, sigmoid, rotary tables, norms and the loss float32.
What ``config.json`` does not settle is ``assumed`` in
``benchmark/configs/lfm2-sgd.json``: ``head_dim``, the order of the chunks
and a convolution without activation, the per-head norms, the tie, a bias
that starts at zero and that no step moves, no auxiliary loss.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import numpy as np

from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads.lane import (  # noqa: F401 - the lane's public names
    LANE_COUNTERS,
    _rms,
    _swiglu,
    make_token_dataset,
)

__all__ = [
    "ATTENTION_COUNTERS",
    "LAYOUT_COUNTERS",
    "Lfm2Config",
    "init_lfm2_params",
    "lfm2_forward",
    "lfm2_lane_bytes",
    "lfm2_loss",
    "lfm2_space",
    "make_lfm2_eval_fn",
]

#: static facts of the blocking that ride beside :data:`LANE_COUNTERS`, per
#: training pass: the key blocks of scores the lane computes, and those of
#: the full ``S x S`` squares of its attention layers
ATTENTION_COUNTERS = ("attn_key_blocks_computed", "attn_key_blocks_square")

#: static facts of the lane's make: its convolution layers, and 1 where the
#: head is the embedding
LAYOUT_COUNTERS = ("conv_layers", "head_tied")

#: lr (log), momentum, weight decay (log), init scale (log): every lane's
lfm2_space = lane.lane_space


class Lfm2Config(NamedTuple):
    """Published widths as defaults, then the cut, then the data."""

    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64                # assumed: hidden_size / num_heads
    conv_kernel: int = 3              # conv_L_cache
    intermediate_size: int = 7168     # a leading dense layer's SwiGLU
    moe_intermediate_size: int = 1792
    num_experts: int = 32             # the router's outputs
    num_experts_per_token: int = 4
    routed_scaling_factor: float = 1.0
    #: added to the sum of the chosen scores (the model code's)
    router_epsilon: float = 1e-6
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    #: the cut: (mixer, ffn) of each layer held, layers 0, 2, 3, 4, 5 of 24
    layer_kinds: Tuple[Tuple[str, str], ...] = (
        ("conv", "dense"), ("attention", "moe"), ("conv", "moe"), ("conv", "moe"),
        ("conv", "moe"),
    )
    #: which of the routed experts this chip holds
    experts_held: Tuple[int, ...] = tuple(range(8))
    vocab_rows: int = 16384
    #: data: tokens a step, sequences to cycle through and held out
    seq_len: int = 8192
    n_train: int = 32
    n_val: int = 1
    #: how the program computes it, not what: the block of queries (the
    #: tests' lanes of 32 tokens take 16)
    attn_query_block: int = 1024


def _experts(cfg: Lfm2Config) -> lane.ExpertLayer:
    return lane.ExpertLayer(
        outputs=cfg.num_experts, top_k=cfg.num_experts_per_token,
        held=cfg.experts_held, score="sigmoid", scaling=cfg.routed_scaling_factor,
        epsilon=cfg.router_epsilon)


# ------------------------------------------------------------- parameters
def _layer_shapes(cfg: Lfm2Config, mixer: str, ffn: str) -> dict:
    d, dh = cfg.hidden_size, cfg.head_dim
    shapes = {"norm1": (d,), "norm2": (d,)}
    if mixer == "conv":
        shapes.update(w_in=(d, 3 * d), conv=(cfg.conv_kernel, d), w_out=(d, d))
    else:
        shapes.update(
            wq=(d, cfg.num_heads * dh), wk=(d, cfg.num_kv_heads * dh),
            wv=(d, cfg.num_kv_heads * dh), wo=(cfg.num_heads * dh, d),
            q_norm=(dh,), k_norm=(dh,))
    if ffn == "dense":
        f = cfg.intermediate_size
        shapes.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        f, e = cfg.moe_intermediate_size, len(cfg.experts_held)
        shapes.update(
            router=(d, cfg.num_experts), router_bias=(cfg.num_experts,),
            e_gate=(e, d, f), e_up=(e, d, f), e_down=(e, f, d))
    return shapes


def init_lfm2_params(key: jax.Array, cfg: Lfm2Config, init_scale) -> dict:
    """``embed`` (which is the head too), ``norm_f`` and ``l<i>``."""
    return lane._init_params(
        key, cfg, [_layer_shapes(cfg, *kind) for kind in cfg.layer_kinds],
        init_scale, tied=True)


# ------------------------------------------------------------------ layers
def rotary_inv_freq(cfg: Lfm2Config):
    """Plain RoPE over the whole head: ``theta^(-2i / d)``."""
    d = cfg.head_dim
    return cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)


def _layer(h, p, kind, cfg: Lfm2Config):
    mixer, ffn = kind
    x = _rms(h, p["norm1"], cfg.norm_eps)
    if mixer == "conv":
        h = h + lane.short_conv_mixer(x, p, scope="lane.conv")
    else:
        with jax.named_scope("lane.gqa"):
            h = h + lane.attention_mixer(
                x, p, kv_heads=cfg.num_kv_heads,
                heads_per_kv=cfg.num_heads // cfg.num_kv_heads, head_dim=cfg.head_dim,
                inv_freq=rotary_inv_freq(cfg), factor=1.0, sight=None,
                block=cfg.attn_query_block, scope="lane.gqa", norm_eps=cfg.norm_eps)
    x = _rms(h, p["norm2"], cfg.norm_eps)
    if ffn == "dense":
        with jax.named_scope("lane.dense_ffn"):
            return h + _swiglu(x, p["w_gate"], p["w_up"], p["w_down"]), None
    with jax.named_scope("lane.moe"):
        y, counters = lane.moe_held_experts(x, p, _experts(cfg))
    return h + y, counters


def _visits(cfg: Lfm2Config):
    """A plain stack: layer ``i`` takes ``l<i>``, once; a layer with experts
    counts what the expert layer counts."""
    return tuple(
        lane.Visit(f"l{i}", lambda h, p, kind=kind: _layer(h, p, kind, cfg),
                   counted=len(LANE_COUNTERS) if kind[1] == "moe" else 0)
        for i, kind in enumerate(cfg.layer_kinds))


def _exits(cfg: Lfm2Config) -> lane.Exits:
    return lane.head_exit(len(cfg.layer_kinds), cfg.norm_eps, tied=True)


def lfm2_loss(params: dict, tokens: jax.Array, cfg: Lfm2Config):
    """``tokens`` i32[T + 1] -> ``(mean next-token cross-entropy over the
    vocabulary slice, counters f32[expert layers, 3])``; for ``jax.grad``."""
    loss, (_, counters) = lane._loss(params, tokens, _visits(cfg), _exits(cfg))
    return loss, counters


def lfm2_forward(params: dict, tokens: jax.Array, cfg: Lfm2Config):
    """:func:`lfm2_loss` with nothing kept for a gradient but the input of
    every layer: ``(loss, counters, [h_0 .. h_L])``, what the lanes' trainer
    takes the gradient from (``lane._forward``)."""
    loss, (counters, _), hs, _ = lane._forward(params, tokens, _visits(cfg), _exits(cfg))
    return loss, counters, hs


# ------------------------------------------------------------- evaluation
def _attention_layers(cfg: Lfm2Config) -> int:
    return sum(mixer == "attention" for mixer, _ in cfg.layer_kinds)


def lfm2_lane_bytes(cfg: Lfm2Config) -> int:
    """Device bytes one lane needs while it trains: float32 parameters,
    momentum and gradients (12 bytes a parameter; the tied matrix once) and
    the peak of its activations: the logits, their softmax and their
    gradient, the head's gradient kept to the end of the backward pass, a
    layer's input per layer, one layer's recomputed activations (about 24
    hidden-sized rows a token: the three thirds of ``W_in``'s output, the
    gates, their gradients) and what attention keeps alive of its scores
    (``lane.attention_alive_bytes``). At the published widths it gives 10.2
    GB in plain JAX (10.0 GB with the kernels) where the chip's allocator
    peaks at 6.5 GB (PR 41; 6.4 GB on the plain form, PR 40): one lane fits
    a 16.9 GB chip, two do not."""
    n_params = lane._count_params(
        lambda: init_lfm2_params(jax.random.key(0), cfg, 1.0))
    t = cfg.seq_len
    activations = (
        4 * t * (3 * cfg.vocab_rows + (24 + len(cfg.layer_kinds)) * cfg.hidden_size)
        + 4 * cfg.vocab_rows * cfg.hidden_size
        + lane.attention_alive_bytes(
            t, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim,
            [None], cfg.attn_query_block))
    return 12 * n_params + activations


def make_lfm2_eval_fn(cfg: Lfm2Config = Lfm2Config(), data_seed: int = 0):
    """``eval_fn(config_vec, budget) -> held-out cross-entropy`` of the
    lane, by the lanes' one trainer (``lane.make_lane_eval_fn``: budget is
    momentum-SGD steps of one ``seq_len``-token sequence);
    ``eval_fn.lane_facts`` states its footprint, its tokens a step and its
    counters: :data:`LANE_COUNTERS` from the device over the expert layers,
    then the static ones: :data:`ATTENTION_COUNTERS`,
    ``lane.attention_counters``, ``lane.expert_layer_counters`` and
    :data:`LAYOUT_COUNTERS`."""
    init_key = jax.random.key(data_seed + 1)
    heads_per_kv = cfg.num_heads // cfg.num_kv_heads
    choices = cfg.seq_len * cfg.num_experts_per_token
    blocks = lane.attention_key_blocks(
        cfg.seq_len, [None] * _attention_layers(cfg), cfg.attn_query_block,
        lane._kernel_tiles(cfg.seq_len, cfg.head_dim, heads_per_kv, cfg.num_kv_heads))
    layout = (sum(mixer == "conv" for mixer, _ in cfg.layer_kinds), 1)
    return lane.make_lane_eval_fn(
        init=lane.Init(init_lfm2_params, init_key, cfg),
        visits=_visits(cfg), exits=_exits(cfg),
        data=make_token_dataset(jax.random.key(data_seed), cfg),
        lane_bytes=lfm2_lane_bytes(cfg),
        counted=lane.expert_counters(
            [True] * sum(ffn == "moe" for _, ffn in cfg.layer_kinds), choices),
        static_counters=tuple(zip(ATTENTION_COUNTERS, blocks))
        + lane.attention_counters(
            cfg.seq_len, cfg.head_dim, heads_per_kv, cfg.num_kv_heads)
        + lane.expert_layer_counters(choices, cfg.hidden_size, cfg.moe_intermediate_size)
        + tuple(zip(LAYOUT_COUNTERS, layout)))
