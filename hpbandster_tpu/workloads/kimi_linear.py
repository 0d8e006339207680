"""A block of Kimi-Linear-48B-A3B-Instruct as a rung's lane.

The published hybrid (``model_type`` ``kimi_linear``; Kimi Linear,
arXiv:2510.26692; widths from the model's ``config.json``): pre-norm
residual layers ``h += Mixer(RMSNorm(h)); h += FFN(RMSNorm(h))`` whose
mixers are Kimi Delta Attention (KDA, a gated delta-rule linear attention
with per-channel decay) three times out of four and latent attention
without positions (NoPE MLA) the fourth, and whose feed-forward is one
dense SwiGLU layer first and then 256 sigmoid-routed experts, 8 a token,
beside one shared expert. A final RMSNorm and an untied head close it.

What trains here is **one chip's share** of that model
(:class:`KimiLinearConfig`'s last fields): ``layer_kinds`` (the layers
held), ``experts_held`` (which of the 256 routed experts live here: the
router keeps its 256 outputs and its 8 a token, this chip adds
``w_e * E_e(x)`` only for chosen experts it holds, and the shared expert
once) and ``vocab_rows`` (a slice of the vocabulary: ids, logits and loss
are over the slice). What the absent experts would add is left out and
nothing stands in for them or for their exchange.

An evaluation (:func:`make_kimi_linear_eval_fn`) is the stateless seam's
``eval_fn(vec, budget)``: initialise the lane from the configuration's
key, train ``budget`` momentum-SGD steps of one sequence each, return the
next-token cross-entropy of the held-out sequences (infinity where the
training diverged and the loss is no number). A promoted lane
restarts from the key at the next budget. Parameters, momentum and
gradients are float32; matmul operands bfloat16 with float32 accumulation
(``workloads/transformer.py``'s rule); the KDA state and gates, softmax,
router scores, norms and the loss float32. Each layer's activations are
recomputed in the backward pass (``jax.checkpoint``). The search space, the
rule for a product's operands, the expert layer, embedding and head, the
tokens and the trainer are every lane's (``workloads/lane.py``); the mixers,
the configuration and the footprint are this file's.

Departures from the published description, all ``assumed`` in
``benchmark/configs/kimi-linear-sgd.json`` too:

* the low-rank width of KDA's decay and output-gate projections (the
  head dim, 128) and the initial ``A_log`` (``log`` of 1..16 over the
  heads) and ``dt_bias`` (``softplus^-1`` of 0.001..0.1 over the
  channels) are not in ``config.json``;
* the router's balancing bias is a buffer held at zero: its update rate
  is not in ``config.json``, and SGD leaves it at zero because top-k
  passes it no gradient;
* KDA is computed chunkwise (chunks of 64, the WY form) with every decay
  applied as ``exp`` of a difference that is never positive
  (``workloads/delta_rule.py``, the linear-attention lanes' one scan, in
  its form of a gate a channel); its gradient is a backward rule of its own
  (``delta_rule._chunks_backward``, a ``jax.custom_vjp``: the scan from the
  last chunk to the first written out, JAX's own pull-back only for the
  part that needs no state); a chunk's triangular system is inverted by
  products, exactly, in one kernel that holds a tile of systems in VMEM
  (``ops/pallas_triangular.py``; plain products off the chip), and the
  solve's transpose is a product with the kept inverse; the rest of the scan
  is plain JAX, no fused kernel;
* the router's 2304 x 256 product keeps float32 operands (three
  bfloat16 passes): its top-8 is a discrete choice that bfloat16 operands
  would flip;
* tokens are synthetic: Zipf-distributed ids over the slice, the second
  half of a sequence repeating its first.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads.delta_rule import (
    _init_leaf, _l2norm, delta_rule_chunked, solve_counters)
from hpbandster_tpu.workloads.lane import (  # noqa: F401 - the lane's public names
    LANE_COUNTERS,
    _FLOAT32,
    _causal_conv,
    _einsum,
    _mm,
    _mm_beside,
    _rms,
    _swiglu,
    make_token_dataset,
)

__all__ = [
    "KimiLinearConfig",
    "LANE_COUNTERS",
    "kimi_linear_space",
    "decode_kimi_linear_hparams",
    "init_kimi_linear_params",
    "kimi_linear_loss",
    "kimi_linear_forward",
    "kda_chunked",
    "moe_held_experts",
    "make_token_dataset",
    "kimi_linear_lane_bytes",
    "make_kimi_linear_eval_fn",
]


class KimiLinearConfig(NamedTuple):
    """Published widths as defaults, then the cut, then the data."""

    hidden_size: int = 2304
    num_heads: int = 32               # of KDA and of MLA alike
    kda_head_dim: int = 128           # linear_attn_config.head_dim: d_k = d_v
    short_conv_kernel_size: int = 4
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64        # kept, not rotated (mla_use_nope)
    v_head_dim: int = 128
    intermediate_size: int = 9216     # the leading dense layer's FFN
    moe_intermediate_size: int = 1024
    num_experts: int = 256            # the router's outputs
    num_experts_per_token: int = 8
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    #: the cut: (mixer, ffn) of each layer held, layers 1-5 of 27
    layer_kinds: Tuple[Tuple[str, str], ...] = (
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe"),
    )
    #: which of the routed experts this chip holds
    experts_held: Tuple[int, ...] = (0, 1, 2, 3, 4, 5, 6, 7)
    vocab_rows: int = 20480
    #: data: tokens a step, sequences to cycle through and held out
    seq_len: int = 4096
    n_train: int = 64
    n_val: int = 2
    #: how the program computes it, not what: KDA's chunk and the blocks of
    #: positions inside it, the heads
    #: whose attention scores are alive at once, the blocks of queries
    kda_chunk: int = 64
    kda_block: int = 16
    mla_heads_at_once: int = 8
    mla_query_blocks: int = 4


#: lr (log), momentum, weight decay (log), init scale (log): every lane's
kimi_linear_space = lane.lane_space
decode_kimi_linear_hparams = lane.decode_lane_hparams


# ------------------------------------------------------------- parameters
def _layer_shapes(cfg: KimiLinearConfig, mixer: str, ffn: str) -> dict:
    d, h = cfg.hidden_size, cfg.num_heads
    dk = cfg.kda_head_dim
    shapes = {"norm1": (d,), "norm2": (d,)}
    if mixer == "kda":
        shapes.update(
            wq=(d, h * dk), wk=(d, h * dk), wv=(d, h * dk),
            conv_q=(cfg.short_conv_kernel_size, h * dk),
            conv_k=(cfg.short_conv_kernel_size, h * dk),
            conv_v=(cfg.short_conv_kernel_size, h * dk),
            wa1=(d, dk), wa2=(dk, h * dk), A_log=(h,), dt_bias=(h * dk,),
            wb=(d, h), wg1=(d, dk), wg2=(dk, h * dk), o_norm=(dk,),
            wo=(h * dk, d),
        )
    else:
        dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        shapes.update(
            wq=(d, h * dq),
            wkva=(d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            kv_norm=(cfg.kv_lora_rank,),
            wkvb=(cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            wo=(h * cfg.v_head_dim, d),
        )
    if ffn == "dense":
        f = cfg.intermediate_size
        shapes.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        f, e = cfg.moe_intermediate_size, len(cfg.experts_held)
        shapes.update(
            router=(d, cfg.num_experts), router_bias=(cfg.num_experts,),
            shared_gate=(d, f), shared_up=(d, f), shared_down=(f, d),
            e_gate=(e, d, f), e_up=(e, d, f), e_down=(e, f, d),
        )
    return shapes


def init_kimi_linear_params(key: jax.Array, cfg: KimiLinearConfig,
                            init_scale) -> dict:
    return lane._init_params(
        key, cfg, [_layer_shapes(cfg, *kind) for kind in cfg.layer_kinds],
        init_scale, _init_leaf)


# ----------------------------------------------------------------- layers
def kda_chunked(q, k, v, log_a, beta, chunk: int, sub: int = None):
    """KDA's scan: the gated delta rule with a gate a channel (``log_a``
    f32[T, H, d_k]), chunk by chunk under its own backward rule
    (``delta_rule.delta_rule_chunked``, which says how)."""
    return delta_rule_chunked(q, k, v, log_a, beta, chunk, sub, scope="lane.kda")


#: how the lane differentiates KDA, beside its counted facts
#: (``make_lane_eval_fn(static_counters=...)``): 1 where the gradient is the
#: rule of ``delta_rule._chunks_backward``
KDA_COUNTERS = (("kda_backward_by_rule", 1),)


def _kda(x, p, cfg: KimiLinearConfig):
    t = x.shape[0]
    h, dk = cfg.num_heads, cfg.kda_head_dim
    heads = lambda y: y.reshape(t, h, dk)
    q, k, v, a1, g1, b = _mm_beside(
        x, p["wq"], p["wk"], p["wv"], p["wa1"], p["wg1"], p["wb"])
    q, k, v = (heads(jax.nn.silu(_causal_conv(y, p[c])))
               for y, c in ((q, "conv_q"), (k, "conv_k"), (v, "conv_v")))
    q, k = _l2norm(q), _l2norm(k)
    # log a_t = -exp(A_log) * softplus(W_a2 W_a1 x_t + dt_bias), per channel
    log_a = -jnp.exp(p["A_log"])[None, :, None] * heads(
        jax.nn.softplus(_mm(a1, p["wa2"]) + p["dt_bias"]))
    beta = jax.nn.sigmoid(b)
    o = kda_chunked(q, k, v, log_a, beta, cfg.kda_chunk,
                    min(cfg.kda_block, cfg.kda_chunk)) * dk ** -0.5
    gate = jax.nn.sigmoid(heads(_mm(g1, p["wg2"])))
    o = _rms(o, p["o_norm"], cfg.rms_norm_eps) * gate
    return _mm(o.reshape(t, h * dk), p["wo"])


def _mla(x, p, cfg: KimiLinearConfig):
    t, h = x.shape[0], cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = _mm(x, p["wq"]).reshape(t, h, dn + dr)
    kva = _mm(x, p["wkva"])
    c = _rms(kva[:, :cfg.kv_lora_rank], p["kv_norm"], cfg.rms_norm_eps)
    k_pe = kva[:, cfg.kv_lora_rank:]            # shared by the heads, not rotated
    kvb = _mm(c, p["wkvb"]).reshape(t, h, dn + dv)
    scale = (dn + dr) ** -0.5
    # queries in blocks, each against the keys up to its own end: what lies
    # wholly above the diagonal is never computed
    block = -(-t // cfg.mla_query_blocks)
    spans = [(lo, min(lo + block, t)) for lo in range(0, t, block)]

    @jax.checkpoint
    def some_heads(qg, kvg):
        kg = jnp.concatenate([
            kvg[..., :dn],
            jnp.broadcast_to(k_pe[:, None, :], kvg.shape[:2] + (dr,))], -1)
        out = []
        for lo, hi in spans:
            s = _einsum("qhd,khd->hqk", qg[lo:hi], kg[:hi]) * scale
            causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
            att = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
            out.append(_einsum("hqk,khd->qhd", att, kvg[:hi, :, dn:]))
        return jnp.concatenate(out, 0)

    g = min(cfg.mla_heads_at_once, h)
    groups = lambda y: y.reshape(t, h // g, g, y.shape[-1]).swapaxes(0, 1)
    out = jax.lax.map(lambda qk: some_heads(*qk), (groups(q), groups(kvb)))
    return _mm(out.swapaxes(0, 1).reshape(t, h * dv), p["wo"])


def _experts(cfg: KimiLinearConfig) -> lane.ExpertLayer:
    return lane.ExpertLayer(
        outputs=cfg.num_experts, top_k=cfg.num_experts_per_token,
        held=cfg.experts_held, score="sigmoid",
        scaling=cfg.routed_scaling_factor)


def moe_held_experts(x, p, cfg: KimiLinearConfig):
    """This chip's part of the expert layer (``lane.moe_held_experts``) as
    this model's router has it: sigmoid scores, the top 8 of ``s + b``,
    ``routed_scaling_factor``, and the shared expert once."""
    return lane.moe_held_experts(x, p, _experts(cfg))


def _layer(h, p, kind, cfg: KimiLinearConfig):
    mixer, ffn = kind
    x = _rms(h, p["norm1"], cfg.rms_norm_eps)
    with jax.named_scope("lane." + mixer):
        h = h + (_kda if mixer == "kda" else _mla)(x, p, cfg)
    x = _rms(h, p["norm2"], cfg.rms_norm_eps)
    if ffn == "dense":
        with jax.named_scope("lane.dense_ffn"):
            return (h + _swiglu(x, p["w_gate"], p["w_up"], p["w_down"]),
                    jnp.zeros((len(LANE_COUNTERS),)))
    with jax.named_scope("lane.moe"):
        y, counters = moe_held_experts(x, p, cfg)
    return h + y, counters


def _layers(cfg: KimiLinearConfig):
    return [lambda h, p, kind=kind: _layer(h, p, kind, cfg) for kind in cfg.layer_kinds]


def kimi_linear_loss(params: dict, tokens: jax.Array, cfg: KimiLinearConfig):
    """``tokens`` i32[T + 1] -> ``(mean next-token cross-entropy over the
    vocabulary slice, counters f32[n_layers, 2])``."""
    layers = _layers(cfg)
    loss, (_, counters) = lane._loss(
        params, tokens, lane.once_through(layers, counted=len(LANE_COUNTERS)),
        lane.head_exit(len(layers), cfg.rms_norm_eps))
    return loss, counters


def kimi_linear_forward(params: dict, tokens: jax.Array, cfg: KimiLinearConfig):
    """:func:`kimi_linear_loss` with nothing kept for a gradient but the
    input of every layer: ``(loss, counters, [h_0 .. h_L])``, what the
    lanes' trainer takes the gradient from (``lane._forward``)."""
    layers = _layers(cfg)
    loss, (counters, _), hs, _ = lane._forward(
        params, tokens, lane.once_through(layers, counted=len(LANE_COUNTERS)),
        lane.head_exit(len(layers), cfg.rms_norm_eps))
    return loss, counters, hs


# ------------------------------------------------------------- evaluation
def kimi_linear_lane_bytes(cfg: KimiLinearConfig) -> int:
    """Device bytes one lane needs while it trains: float32 parameters,
    momentum and gradients (12 bytes a parameter) and the peak of its
    activations: the logits and their gradient, one layer's recomputed
    activations (about 40 hidden-sized rows a token, the attention scores
    of ``mla_heads_at_once`` heads) and a layer's input per layer. At the
    published widths it gives 11.5 GB where the chip's allocator peaks at
    8.88 GB (``device.peak_hbm_bytes`` 8,883,300,000 since PR 44: the
    trainer steps a layer's leaves where the backward pass leaves them, so
    one layer's gradient is alive at a time): one lane fits a 16.9 GB chip,
    two do not."""
    n_params = lane._count_params(
        lambda: init_kimi_linear_params(jax.random.key(0), cfg, 1.0))
    t = cfg.seq_len
    activations = 4 * t * (
        3 * cfg.vocab_rows
        + (40 + len(cfg.layer_kinds)) * cfg.hidden_size
        + 3 * min(cfg.mla_heads_at_once, cfg.num_heads) * t
    )
    return 12 * n_params + activations


def make_kimi_linear_eval_fn(cfg: KimiLinearConfig = KimiLinearConfig(),
                             data_seed: int = 0):
    """``eval_fn(config_vec, budget) -> held-out cross-entropy`` of the
    lane, by the lanes' one trainer (``lane.make_lane_eval_fn``: budget is
    momentum-SGD steps of one ``seq_len``-token sequence);
    ``eval_fn.lane_facts`` states its footprint, its tokens a step and its
    counters: :data:`LANE_COUNTERS` from the device, then
    ``lane.expert_layer_counters``, how the expert layer moves its rows and
    whether its products are the grouped kernels', then
    :data:`KDA_COUNTERS`, how KDA is differentiated, and
    ``delta_rule.solve_counters``, whether its chunks' systems are solved in
    VMEM."""
    init_key = jax.random.key(data_seed + 1)
    layers = _layers(cfg)
    return lane.make_lane_eval_fn(
        init=lane.Init(init_kimi_linear_params, init_key, cfg),
        visits=lane.once_through(layers, counted=len(LANE_COUNTERS)),
        exits=lane.head_exit(len(layers), cfg.rms_norm_eps),
        data=make_token_dataset(jax.random.key(data_seed), cfg),
        lane_bytes=kimi_linear_lane_bytes(cfg),
        counted=lane.expert_counters(
            [ffn == "moe" for _, ffn in cfg.layer_kinds],
            cfg.seq_len * cfg.num_experts_per_token),
        static_counters=lane.expert_layer_counters(
            cfg.seq_len * cfg.num_experts_per_token, cfg.hidden_size,
            cfg.moe_intermediate_size) + KDA_COUNTERS + solve_counters(
                cfg.seq_len, cfg.num_heads, cfg.kda_head_dim, cfg.kda_head_dim,
                cfg.kda_chunk))
