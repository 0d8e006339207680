"""A block of Laguna-XS.2 as a rung's lane.

The published model (``model_type`` ``laguna``; poolside's 33 B mixture of
experts, about 3 B active; widths from its ``config.json``): pre-norm
residual layers ``h += Attention(RMSNorm(h)); h += F(RMSNorm(h))`` of hidden
size 2,048 with grouped-query attention on 8 key/value heads of 128 whose
**query heads differ by the kind of layer** (``num_attention_heads_per_layer``):
a ``full_attention`` layer (every fourth, layer 0 first) has 48, 6 a key/value
head, sees the whole causal past and turns **half of each head**
(``partial_rotary_factor`` 0.5: the first 64 channels, by YaRN-interpolated
frequencies over those 64, factor 64 over 4,096, theta 500,000, an attention
factor on cos and sin; the other 64 pass as they are); a ``sliding_attention``
layer has 64, 8 a key/value head, sees a window of 512 positions and turns
the whole head by plain RoPE, theta 10,000. **Every head is gated**
(``gating``): ``sigmoid(x W_g)``, one number a head and position from the
mixer's own input, multiplies the head's attention output before ``W_o``.
Layer 0's feed-forward is a dense SwiGLU of width 8,192; the other 39 have
256 sigmoid-routed experts of width 512, 8 a token, renormalised and scaled
by 2.5, beside one shared expert of width 512. A final RMSNorm and an untied
head close it.

What trains here is **one chip's share** (:class:`LagunaConfig`'s cut):
``layer_kinds`` and ``mlp_kinds`` (layers 0-4 of 40: the leading dense layer
once and one whole period of the four that follow it, three window layers to
one full one), ``experts_held`` (32 of the 256: the router keeps its 256
outputs and its 8 a token, this chip adds ``w_e * E_e(x)`` only for chosen
experts it holds, and the shared expert once) and ``vocab_rows`` (an eighth
of the vocabulary). The search space, the rule for a product's operands, the
attention (``lane.attention_mixer``: the gate by its leaf ``w_head_gate``, the
partial rotation by the length of ``inv_freq``, the fused kernels for a group
of 6 as for one of 8), the expert layer, the dense SwiGLU, embedding and head,
the tokens and the trainer are every lane's (``workloads/lane.py``); this file
has the configuration, each layer's shapes, the frequencies by kind and the
footprint.

Precision as the other lanes state it: float32 parameters, momentum and
gradients; matrix-product operands bfloat16 with float32 accumulation (the
gate's columns ride the projections' product, and its sigmoid's value is
rounded as an operand before it multiplies a head); the router's product, the
softmax, the gate's sigmoid, rotary tables, norms and the loss float32. What
``config.json`` does not settle is ``assumed`` in
``benchmark/configs/laguna-xs2-sgd.json``, the gate first: ``gating: true``
names no kind, and the family's later ``Laguna-S-2.1`` writes ``"per-head"``.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads.lane import (  # noqa: F401 - the lane's public names
    LANE_COUNTERS,
    _rms,
    _swiglu,
    make_token_dataset,
    moe_held_experts,
)

__all__ = [
    "ATTENTION_COUNTERS",
    "LagunaConfig",
    "init_laguna_params",
    "laguna_forward",
    "laguna_lane_bytes",
    "laguna_loss",
    "laguna_space",
    "make_laguna_eval_fn",
    "rotary_inv_freq",
]

#: the blocking's facts, from the schedule (no device counter): the key
#: blocks or kernel tiles the lane computes against those of the square
ATTENTION_COUNTERS = ("attn_key_blocks_computed", "attn_key_blocks_square")

#: lr (log), momentum, weight decay (log), init scale (log): every lane's
laguna_space = lane.lane_space


class LagunaConfig(NamedTuple):
    """Published widths as defaults, then the cut, then the data. What
    differs by the kind of layer is a pair a kind (a tuple and no
    dictionary: a configuration is hashable)."""

    hidden_size: int = 2048
    num_kv_heads: int = 8
    head_dim: int = 128
    #: num_attention_heads_per_layer, by the layer's kind
    heads_by_kind: Tuple[Tuple[str, int], ...] = (("full", 48), ("sliding", 64))
    #: rope_parameters.<kind>.partial_rotary_factor: the share of a head's
    #: channels, from the first, that are turned
    rotary_by_kind: Tuple[Tuple[str, float], ...] = (("full", 0.5), ("sliding", 1.0))
    #: rope_parameters.<kind>.rope_theta
    theta_by_kind: Tuple[Tuple[str, float], ...] = (("full", 500000.0), ("sliding", 10000.0))
    sliding_window: int = 512
    #: rope_parameters.full_attention (``rope_type`` ``yarn``)
    yarn_factor: float = 64.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672
    intermediate_size: int = 8192     # the leading dense layer's FFN
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts_per_token: int = 8
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    #: the cut: layers 0-4 of 40, the dense layer and one whole period
    layer_kinds: Tuple[str, ...] = ("full", "sliding", "sliding", "sliding", "full")
    mlp_kinds: Tuple[str, ...] = ("dense", "sparse", "sparse", "sparse", "sparse")
    #: which of the routed experts this chip holds, of ``router_outputs``
    experts_held: Tuple[int, ...] = tuple(range(32))
    router_outputs: int = 256
    vocab_rows: int = 12544
    #: data: tokens a step, sequences to cycle through and held out
    seq_len: int = 8192
    n_train: int = 32
    n_val: int = 1
    #: how the program computes it, not what: the plain form's block of
    #: queries (the tests' lanes of 64 tokens take 16)
    attn_query_block: int = 1024


def _heads_per_kv(cfg: LagunaConfig, kind: str) -> int:
    return dict(cfg.heads_by_kind)[kind] // cfg.num_kv_heads


def _experts(cfg: LagunaConfig) -> lane.ExpertLayer:
    return lane.ExpertLayer(
        outputs=cfg.router_outputs, top_k=cfg.num_experts_per_token,
        held=cfg.experts_held, score="sigmoid", scaling=cfg.routed_scaling_factor)


# ------------------------------------------------------------- parameters
def _layer_shapes(cfg: LagunaConfig, kind: str, mlp: str) -> dict:
    """A layer's leaves: its own count of query heads in ``wq``, ``wo`` and
    the gate ``w_head_gate`` (a column a head); the dense SwiGLU's leaves as
    ``ffn_*`` (``w_gate`` is the other lanes' name for a dense layer's)."""
    d, dh = cfg.hidden_size, cfg.head_dim
    heads = dict(cfg.heads_by_kind)[kind]
    shapes = dict(
        norm1=(d,), norm2=(d,),
        wq=(d, heads * dh), wk=(d, cfg.num_kv_heads * dh),
        wv=(d, cfg.num_kv_heads * dh), w_head_gate=(d, heads), wo=(heads * dh, d))
    if mlp == "dense":
        f = cfg.intermediate_size
        shapes.update(ffn_gate=(d, f), ffn_up=(d, f), ffn_down=(f, d))
    else:
        f, e = cfg.moe_intermediate_size, len(cfg.experts_held)
        fs = cfg.shared_expert_intermediate_size
        shapes.update(
            router=(d, cfg.router_outputs),
            shared_gate=(d, fs), shared_up=(d, fs), shared_down=(fs, d),
            e_gate=(e, d, f), e_up=(e, d, f), e_down=(e, f, d))
    return shapes


def _kinds(cfg: LagunaConfig):
    if len(cfg.layer_kinds) != len(cfg.mlp_kinds):
        raise ValueError("one mixer kind and one feed-forward kind a layer")
    return list(zip(cfg.layer_kinds, cfg.mlp_kinds))


def init_laguna_params(key: jax.Array, cfg: LagunaConfig, init_scale) -> dict:
    return lane._init_params(
        key, cfg, [_layer_shapes(cfg, *kinds) for kinds in _kinds(cfg)], init_scale)


# -------------------------------------------------------------- positions
def rotary_inv_freq(cfg: LagunaConfig, kind: str):
    """``(inv_freq f64[rotary / 2], factor)`` of a layer of ``kind``, over
    the ``rotary = partial_rotary_factor x head_dim`` channels it turns
    (``lane.rotary_inv_freq`` at that width and the kind's theta): a window
    layer plain RoPE over the whole head; a full layer YaRN's ramp over its
    64 channels, cos and sin carrying the attention factor."""
    yarn = None if kind == "sliding" else lane.Yarn(
        cfg.yarn_factor, cfg.yarn_original_max_position, cfg.yarn_beta_fast,
        cfg.yarn_beta_slow, cfg.yarn_attention_factor)
    return lane.rotary_inv_freq(_rotary(cfg, kind), dict(cfg.theta_by_kind)[kind], yarn)


def _rotary(cfg: LagunaConfig, kind: str) -> int:
    """The channels of a head that a layer of ``kind`` turns."""
    return int(cfg.head_dim * dict(cfg.rotary_by_kind)[kind])


# ------------------------------------------------------------------ layers
#: the scope of a layer's mixer by its kind (``obs.timeline.LANE_SCOPES``)
_MIXER_SCOPE = {"sliding": "lane.swa", "full": "lane.gqa"}


def _sight(cfg: LagunaConfig, kind: str):
    return cfg.sliding_window if kind == "sliding" else None


def _attention(x, p, kind: str, cfg: LagunaConfig):
    """A layer's mixer, from the norm's output to ``W_o``
    (``lane.attention_mixer``): this kind's heads, frequencies and sight."""
    inv_freq, factor = rotary_inv_freq(cfg, kind)
    return lane.attention_mixer(
        x, p, kv_heads=cfg.num_kv_heads, heads_per_kv=_heads_per_kv(cfg, kind),
        head_dim=cfg.head_dim, inv_freq=inv_freq, factor=factor,
        sight=_sight(cfg, kind), block=cfg.attn_query_block, scope=_MIXER_SCOPE[kind])


def _layer(h, p, kind: str, mlp: str, cfg: LagunaConfig):
    x = _rms(h, p["norm1"], cfg.rms_norm_eps)
    with jax.named_scope(_MIXER_SCOPE[kind]):
        h = h + _attention(x, p, kind, cfg)
    x = _rms(h, p["norm2"], cfg.rms_norm_eps)
    if mlp == "dense":
        with jax.named_scope("lane.dense_ffn"):
            return (h + _swiglu(x, p["ffn_gate"], p["ffn_up"], p["ffn_down"]),
                    jnp.zeros((len(LANE_COUNTERS),)))
    with jax.named_scope("lane.moe"):
        y, counters = moe_held_experts(x, p, _experts(cfg))
    return h + y, counters


def _layers(cfg: LagunaConfig):
    return [lambda h, p, kind=kind, mlp=mlp: _layer(h, p, kind, mlp, cfg)
            for kind, mlp in _kinds(cfg)]


def _visits_and_exits(cfg: LagunaConfig):
    layers = _layers(cfg)
    return (lane.once_through(layers, counted=len(LANE_COUNTERS)),
            lane.head_exit(len(layers), cfg.rms_norm_eps))


def laguna_loss(params: dict, tokens: jax.Array, cfg: LagunaConfig):
    """``tokens`` i32[T + 1] -> ``(mean next-token cross-entropy over the
    vocabulary slice, counters f32[n_layers, 3])``."""
    loss, (_, counters) = lane._loss(params, tokens, *_visits_and_exits(cfg))
    return loss, counters


def laguna_forward(params: dict, tokens: jax.Array, cfg: LagunaConfig):
    """:func:`laguna_loss` with nothing kept for a gradient but the input
    of every layer: ``(loss, counters, [h_0 .. h_L])``, what the lanes'
    trainer takes the gradient from (``lane._forward``)."""
    loss, (counters, _), hs, _ = lane._forward(params, tokens, *_visits_and_exits(cfg))
    return loss, counters, hs


# ------------------------------------------------------------- evaluation
def _attention_shapes(cfg: LagunaConfig):
    """``(heads a key/value head, rule of sight)`` a layer, each a list."""
    return ([_heads_per_kv(cfg, kind) for kind in cfg.layer_kinds],
            [_sight(cfg, kind) for kind in cfg.layer_kinds])


def laguna_lane_bytes(cfg: LagunaConfig) -> int:
    """Device bytes one lane needs while it trains, by ``mellum2_lane_bytes``'
    rule: float32 parameters, momentum and gradients (12 bytes a parameter)
    and the peak of its activations: the logits, their softmax and their
    gradient, a layer's input per layer, one layer's recomputed activations
    (32 hidden-sized rows a token here: a window layer's queries, their
    rotation, the attention's output and its gated copy are four hidden
    sizes each) and what attention keeps alive of its scores
    (``lane.attention_alive_bytes``, each layer at its own head count). At
    the published widths it gives 12.6 GB with the kernels, where the chip's
    allocator peaks at 12.1 GB beside the draw (8.30 GB of it the
    training state of 691.6 M parameters): one lane fits a 16.9 GB chip
    beside the bracket's 2.77 GB draw, two do not."""
    n_params = lane._count_params(
        lambda: init_laguna_params(jax.random.key(0), cfg, 1.0))
    t = cfg.seq_len
    heads, sights = _attention_shapes(cfg)
    activations = (
        4 * t * (3 * cfg.vocab_rows + (32 + len(cfg.layer_kinds)) * cfg.hidden_size)
        + lane.attention_alive_bytes(
            t, cfg.num_kv_heads, heads, cfg.head_dim, sights, cfg.attn_query_block))
    return 12 * n_params + activations


def make_laguna_eval_fn(cfg: LagunaConfig = LagunaConfig(), data_seed: int = 0):
    """``eval_fn(config_vec, budget) -> held-out cross-entropy`` of the
    lane, by the lanes' one trainer (``lane.make_lane_eval_fn``: budget is
    momentum-SGD steps of one ``seq_len``-token sequence);
    ``eval_fn.lane_facts`` states its footprint, its tokens a step and its
    counters: :data:`LANE_COUNTERS` from the device (over the four expert
    layers), then :data:`ATTENTION_COUNTERS`, the blocking summed over the
    layers, each counted once a query head of its own (48 or 64: the fused
    kernels' tiles where they run), ``lane.attention_counters``, the shares
    of the five layers whose scores stay in VMEM and whose turn is a kernel, and
    ``lane.expert_layer_counters``."""
    init_key = jax.random.key(data_seed + 1)
    heads, sights = _attention_shapes(cfg)
    tiles = [lane._kernel_tiles(cfg.seq_len, cfg.head_dim, r, cfg.num_kv_heads, sight)
             for r, sight in zip(heads, sights)]
    blocks = lane.attention_key_blocks(
        cfg.seq_len, sights, cfg.attn_query_block, tiles,
        heads=[r * cfg.num_kv_heads for r in heads])
    visits, exits = _visits_and_exits(cfg)
    choices = cfg.seq_len * cfg.num_experts_per_token
    return lane.make_lane_eval_fn(
        init=lane.Init(init_laguna_params, init_key, cfg),
        visits=visits, exits=exits,
        data=make_token_dataset(jax.random.key(data_seed), cfg),
        lane_bytes=laguna_lane_bytes(cfg),
        counted=lane.expert_counters([mlp == "sparse" for mlp in cfg.mlp_kinds], choices),
        static_counters=tuple(zip(ATTENTION_COUNTERS, blocks)) + lane.attention_counters(
            cfg.seq_len, cfg.head_dim, heads, cfg.num_kv_heads, sights,
            [_rotary(cfg, kind) for kind in cfg.layer_kinds]
        ) + lane.expert_layer_counters(
            choices, cfg.hidden_size, cfg.moe_intermediate_size))
