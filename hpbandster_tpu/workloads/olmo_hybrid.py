"""One period of Olmo-Hybrid-7B as a rung's lane: Gated DeltaNet 3 : 1 with
full attention.

The published hybrid (``model_type`` ``olmo_hybrid``, allenai; widths from
the model's ``config.json``): 32 layers of hidden size 3,840 in periods of
four, three ``linear_attention`` layers and one ``full_attention`` layer,
every layer with OLMo 2's norm *after* the sub-layer (arXiv:2501.00656)::

    h = h + RMSNorm(Mixer(h));  h = h + RMSNorm(SwiGLU(h))

a dense SwiGLU of 11,008, a final RMSNorm and an untied head.

* A linear layer is Gated DeltaNet (Yang, Kautz, Hatamizadeh,
  arXiv:2412.06464), 30 heads of ``d_k`` 96 and ``d_v`` 192: ``q =
  l2norm(silu(conv4(x W_q)))``, ``k`` alike, ``v = silu(conv4(x W_v))``; **one
  gate a head and a step**, ``g_t = -exp(A_log) softplus(x_t W_a +
  dt_bias)``, ``a_t = exp(g_t)``; ``beta_t = 2 sigmoid(x_t W_b)``
  (``linear_allow_neg_eigval``: the state's eigenvalues reach -1); ``S_t = (I
  - beta_t k_t k_t^T) a_t S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t /
  sqrt(d_k)``; ``W_o(RMSNorm_head(o) * silu(x W_g))``. The scan is
  ``workloads/delta_rule.py``'s in its form of a gate a head, under that
  module's backward rule.
* A full layer is plain causal attention, 30 heads of 128 with as many
  key/value heads, an RMSNorm over the whole 3,840 of ``q`` and of ``k``
  before the heads are split, and **no rotation** (``rope_theta`` is
  ``null``): ``lane.attention_mixer`` with ``inv_freq=None``.

What trains here is **one chip's share** (:class:`OlmoHybridConfig`'s cut):
layers 0-3, one whole period, at every published width, and ``vocab_rows``,
an eighth of the vocabulary (ids, logits and loss are over the slice). An
evaluation (:func:`make_olmo_hybrid_eval_fn`) is the stateless seam's
``eval_fn(vec, budget)`` by the lanes' one trainer (``workloads/lane.py``).
Parameters, momentum and gradients are float32; matrix-product operands
bfloat16 with float32 accumulation; the scan's state, gates and the solve's
operands, softmax, norms and the loss float32. What ``config.json`` does not
settle (the norm's place, the q/k norm's span, no positions, the gate's
initial leaves, the output gate's SiLU) is ``assumed`` in
``benchmark/configs/olmo-hybrid-sgd.json``.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads.delta_rule import (
    _init_leaf, _l2norm, delta_rule_chunked, solve_counters)
from hpbandster_tpu.workloads.lane import (  # noqa: F401 - the lane's public names
    _causal_conv,
    _mm,
    _mm_beside,
    _rms,
    _swiglu,
    make_token_dataset,
)

__all__ = [
    "GDN_COUNTERS",
    "OlmoHybridConfig",
    "init_olmo_hybrid_params",
    "make_olmo_hybrid_eval_fn",
    "make_token_dataset",
    "olmo_hybrid_forward",
    "olmo_hybrid_lane_bytes",
    "olmo_hybrid_loss",
    "olmo_hybrid_space",
]

#: how the lane computes its linear layers, beside its counted facts
#: (``make_lane_eval_fn(static_counters=...)``): 1 where the scan ran the
#: form of a gate a head (one masked product a chunk, not the per-channel
#: blocks), and 1 where its gradient is ``delta_rule._chunks_backward``
GDN_COUNTERS = (("gdn_gate_per_head", 1), ("gdn_backward_by_rule", 1))

#: lr (log), momentum, weight decay (log), init scale (log): every lane's
olmo_hybrid_space = lane.lane_space


class OlmoHybridConfig(NamedTuple):
    """Published widths as defaults, then the cut, then the data."""

    hidden_size: int = 3840
    num_heads: int = 30               # of a full layer's attention
    num_kv_heads: int = 30
    head_dim: int = 128               # assumed: hidden_size / num_heads
    linear_num_heads: int = 30        # linear_num_key_heads = linear_num_value_heads
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    #: ``beta`` in (0, 2) and not (0, 1)
    linear_allow_neg_eigval: bool = True
    intermediate_size: int = 11008
    rms_norm_eps: float = 1e-6
    #: the cut: the mixer of each layer held, layers 0-3 of 32: one period
    layer_kinds: Tuple[str, ...] = ("gdn", "gdn", "gdn", "gqa")
    vocab_rows: int = 12544
    #: data: tokens a step, sequences to cycle through and held out
    seq_len: int = 2048
    n_train: int = 32
    n_val: int = 1
    #: how the program computes it, not what: the scan's chunk, the block of
    #: queries (the tests' lanes of 32 tokens take 16)
    gdn_chunk: int = 64
    attn_query_block: int = 512


# ------------------------------------------------------------- parameters
def _layer_shapes(cfg: OlmoHybridConfig, mixer: str) -> dict:
    d, f = cfg.hidden_size, cfg.intermediate_size
    shapes = dict(norm1=(d,), norm2=(d,), w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    if mixer == "gdn":
        h, taps = cfg.linear_num_heads, cfg.linear_conv_kernel_dim
        wk, wv = h * cfg.linear_key_head_dim, h * cfg.linear_value_head_dim
        shapes.update(
            wq=(d, wk), wk=(d, wk), wv=(d, wv),
            conv_q=(taps, wk), conv_k=(taps, wk), conv_v=(taps, wv),
            wa=(d, h), A_log=(h,), dt_bias=(h,), wb=(d, h),
            wg=(d, wv), o_norm=(cfg.linear_value_head_dim,), wo=(wv, d))
    else:
        wq, wkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
        shapes.update(wq=(d, wq), wk=(d, wkv), wv=(d, wkv), wo=(wq, d),
                      q_norm=(wq,), k_norm=(wkv,))
    return shapes


def init_olmo_hybrid_params(key: jax.Array, cfg: OlmoHybridConfig, init_scale) -> dict:
    """``embed``, ``norm_f``, ``head`` and ``l<i>``; the gate's ``A_log`` and
    ``dt_bias`` are not drawn (``delta_rule._init_leaf``)."""
    return lane._init_params(
        key, cfg, [_layer_shapes(cfg, mixer) for mixer in cfg.layer_kinds],
        init_scale, _init_leaf)


# ----------------------------------------------------------------- layers
def _gdn(x, p, cfg: OlmoHybridConfig):
    """A linear layer's mixer, from the layer's input to ``W_o``."""
    t, h = x.shape[0], cfg.linear_num_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    q, k, v, gate, a, b = _mm_beside(
        x, p["wq"], p["wk"], p["wv"], p["wg"], p["wa"], p["wb"])
    q, k, v = (jax.nn.silu(_causal_conv(y, p[c])).reshape(t, h, -1)
               for y, c in ((q, "conv_q"), (k, "conv_k"), (v, "conv_v")))
    q, k = _l2norm(q), _l2norm(k)
    # log a_t = -exp(A_log) * softplus(W_a x_t + dt_bias): one number a head
    log_a = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
    o = delta_rule_chunked(
        q, k, v, log_a, beta, cfg.gdn_chunk, scope="lane.gdn") * dk ** -0.5
    o = _rms(o, p["o_norm"], cfg.rms_norm_eps) * jax.nn.silu(gate.reshape(t, h, dv))
    return _mm(o.reshape(t, h * dv), p["wo"])


def _layer(h, p, mixer: str, cfg: OlmoHybridConfig):
    eps = cfg.rms_norm_eps
    with jax.named_scope("lane." + mixer):
        if mixer == "gdn":
            mixed = _gdn(h, p, cfg)
        else:
            mixed = lane.attention_mixer(
                h, p, kv_heads=cfg.num_kv_heads,
                heads_per_kv=cfg.num_heads // cfg.num_kv_heads, head_dim=cfg.head_dim,
                inv_freq=None, factor=1.0, sight=None, block=cfg.attn_query_block,
                scope="lane.gqa", norm_eps=eps)
        h = h + _rms(mixed, p["norm1"], eps)
    with jax.named_scope("lane.dense_ffn"):
        return h + _rms(_swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), p["norm2"], eps), None


def _layers(cfg: OlmoHybridConfig):
    return [lambda h, p, mixer=mixer: _layer(h, p, mixer, cfg) for mixer in cfg.layer_kinds]


def _model(cfg: OlmoHybridConfig):
    """``(visits, exits)``: a plain stack, one exit."""
    layers = _layers(cfg)
    return lane.once_through(layers), lane.head_exit(len(layers), cfg.rms_norm_eps)


def olmo_hybrid_loss(params: dict, tokens: jax.Array, cfg: OlmoHybridConfig):
    """``tokens`` i32[T + 1] -> the mean next-token cross-entropy over the
    vocabulary slice; for ``jax.grad``."""
    return lane._loss(params, tokens, *_model(cfg))[0]


def olmo_hybrid_forward(params: dict, tokens: jax.Array, cfg: OlmoHybridConfig):
    """:func:`olmo_hybrid_loss` with nothing kept for a gradient but the
    input of every layer: ``(loss, [h_0 .. h_L])``, what the lanes' trainer
    takes the gradient from (``lane._forward``)."""
    loss, _, hs, _ = lane._forward(params, tokens, *_model(cfg))
    return loss, hs


# ------------------------------------------------------------- evaluation
def olmo_hybrid_lane_bytes(cfg: OlmoHybridConfig) -> int:
    """Device bytes one lane needs while it trains: float32 parameters and
    momentum (8 bytes a parameter), **one layer's gradient** (the trainer
    steps a layer's leaves where the backward pass leaves them: the largest
    layer's 4 bytes a parameter, and the head's beside the embedding's) and
    the peak of its activations: the logits, their softmax and their
    gradient, a layer's input per layer, one layer's recomputed activations
    (about 40 hidden-sized and 9 feed-forward-sized rows a token: the scan
    keeps its inputs, its solved rows and ``u``) and what attention keeps
    alive of its scores (``lane.attention_alive_bytes``). At the published
    widths it gives 11.2 GB where the chip's allocator peaks at 9.76 GB (PR
    46): one lane fits a 16.9 GB chip, two do not."""
    shapes = jax.eval_shape(lambda: init_olmo_hybrid_params(jax.random.key(0), cfg, 1.0))
    count = lambda tree: sum(int(x.size) for x in jax.tree.leaves(tree))
    largest = max([count(shapes[f"l{i}"]) for i in range(len(cfg.layer_kinds))]
                  + [count(shapes["embed"]) + count(shapes["head"])])
    t = cfg.seq_len
    activations = (
        4 * t * (3 * cfg.vocab_rows + (40 + len(cfg.layer_kinds)) * cfg.hidden_size
                 + 9 * cfg.intermediate_size)
        + lane.attention_alive_bytes(
            t, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim,
            [None], cfg.attn_query_block))
    return 8 * count(shapes) + 4 * largest + activations


def make_olmo_hybrid_eval_fn(cfg: OlmoHybridConfig = OlmoHybridConfig(),
                             data_seed: int = 0):
    """``eval_fn(config_vec, budget) -> held-out cross-entropy`` of the lane,
    by the lanes' one trainer (``lane.make_lane_eval_fn``: budget is
    momentum-SGD steps of one ``seq_len``-token sequence);
    ``eval_fn.lane_facts`` states its footprint, its tokens a step and its
    counters, static facts all: :data:`GDN_COUNTERS`, how the linear layers'
    scan is computed and differentiated, ``delta_rule.solve_counters``,
    whether its chunks' systems are solved in VMEM, and
    ``lane.attention_counters``, whether the full layer's scores stay in
    VMEM (it turns nothing: ``attn_rotation_in_vmem`` reads 0)."""
    init_key = jax.random.key(data_seed + 1)
    visits, exits = _model(cfg)
    return lane.make_lane_eval_fn(
        init=lane.Init(init_olmo_hybrid_params, init_key, cfg),
        visits=visits, exits=exits,
        data=make_token_dataset(jax.random.key(data_seed), cfg),
        lane_bytes=olmo_hybrid_lane_bytes(cfg),
        counted=lane.Counted((), lambda *_: []),
        static_counters=GDN_COUNTERS + solve_counters(
            cfg.seq_len, cfg.linear_num_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.gdn_chunk) + lane.attention_counters(
            cfg.seq_len, cfg.head_dim, cfg.num_heads // cfg.num_kv_heads, cfg.num_kv_heads,
            rotary=0))
