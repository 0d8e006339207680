"""Ouro-2.6B as a rung's lane: layers that run several times.

The published model (``model_type`` ``ouro``; ByteDance's looped language
model, arXiv:2510.25741; widths from its ``config.json``): a stack of
identical layers, full causal attention (16 heads of 128, no sharing, plain
RoPE at theta 1,000,000) and a SwiGLU of 5,632, four RMSNorms a layer (one
before and one after each half: ``h += RMSNorm(Attention(RMSNorm(h))); h +=
RMSNorm(SwiGLU(RMSNorm(h)))``), **run ``total_ut_steps`` = 4 times over with
one set of weights**. The final RMSNorm closes every pass: its output is the
pass's exit and the next pass's input. At every exit one head gives the
logits and a gate one scalar a position, ``g_t = h^t w_gate + b_gate``; the
gates give a distribution over the exits, a position at a time: ``lambda_t
= sigmoid(g_t)``, ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` and the last
exit the remainder ``p_T = prod_{j<T} (1 - lambda_j)``.

**The loss that is trained** is over all the exits: the mean over positions
of ``sum_t p_t l_t - beta H(p)``, ``l_t`` exit ``t``'s next-token
cross-entropy and ``H`` the entropy of ``p``. **The loss a lane reports**
(what BOHB ranks by) is the last exit's mean cross-entropy: at the published
``early_exit_threshold`` of 1 the cumulative exit probability reaches the
threshold at the last pass only, so inference runs every pass and reads the
last. Two functions of one forward pass.

What trains here is **one chip's share** (:class:`OuroConfig`'s cut): the
first ``num_layers`` of the 48 layers, every width, head and vocabulary row
as published. The search space, the rule for a product's operands, the
attention, rotary tables, SwiGLU, embedding, the tokens and **the trainer**
are every lane's (``workloads/lane.py``): this file hands the trainer the
model's visits (``total_ut_steps`` times through ``l0 .. l<L-1>`` and
``norm_f``) and its exits, and keeps the exit distribution, the two losses,
the configuration and the footprint.

Precision as the other lanes state it: float32 parameters, momentum and
gradients; matrix-product operands bfloat16 with float32 accumulation;
softmax, norms, rotary tables, the gate (a float32-operand product), the
exit distribution and the losses float32. What ``config.json`` does not
settle is ``assumed`` in ``benchmark/configs/ouro-sgd.json``: the second
norm of each half, the final norm closing every pass, the gate's shape,
``beta``, rotate-half pairing over the whole head.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads.lane import (  # noqa: F401 - the lane's public names
    _mm,
    _rms,
    _swiglu,
    make_token_dataset,
)

__all__ = [
    "EXIT_COUNTERS",
    "LOOP_COUNTERS",
    "OuroConfig",
    "exit_distribution",
    "init_ouro_params",
    "make_ouro_eval_fn",
    "ouro_forward",
    "ouro_lane_bytes",
    "ouro_losses",
    "ouro_space",
]

#: what an evaluation counts on the device beside its loss, over the
#: positions of its held-out passes: the mean mass the exit distribution
#: leaves to the last exit (``p_T``) and the mean entropy of the
#: distribution over its largest value (``H(p) / ln T``)
EXIT_COUNTERS = ("exit_last_mass", "exit_entropy_share")

#: static facts of the loop that ride beside :data:`EXIT_COUNTERS`: passes
#: through the layers, layer visits of one sequence's pass (layers x
#: passes), exits in the trained loss
LOOP_COUNTERS = ("loop_passes", "layer_visits_per_pass", "exits_trained")

#: lr (log), momentum, weight decay (log), init scale (log): every lane's
ouro_space = lane.lane_space


class OuroConfig(NamedTuple):
    """Published widths as defaults, then the cut, then the data."""

    hidden_size: int = 2048
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    #: passes through the layers with one set of weights, and exits
    total_ut_steps: int = 4
    #: the entropy term's weight in the trained loss (assumed: the family's
    #: first-stage objective)
    exit_entropy_beta: float = 0.1
    #: the cut: layers 0-7 of 48 (every layer is of one kind)
    num_layers: int = 8
    vocab_rows: int = 49152
    #: data: tokens a step, sequences to cycle through and held out
    seq_len: int = 2048
    n_train: int = 32
    n_val: int = 1
    #: how the program computes it, not what: the block of queries (the
    #: tests' lanes of 32 tokens take 16). Read on the chip (PR 34; an
    #: evaluation of nine steps and its held-out pass): 3.31 s at 512, 3.71 s
    #: at 1,024, 4.94 s at 2,048 (one block: the whole square where the
    #: causal half is needed; at 512 five eighths of it)
    attn_query_block: int = 512


# ------------------------------------------------------------- parameters
def _layer_shapes(cfg: OuroConfig) -> dict:
    d, dh, f = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size
    return dict(
        norm1=(d,), norm2=(d,), norm3=(d,), norm4=(d,),
        wq=(d, cfg.num_heads * dh), wk=(d, cfg.num_kv_heads * dh),
        wv=(d, cfg.num_kv_heads * dh), wo=(cfg.num_heads * dh, d),
        w_gate=(d, f), w_up=(d, f), w_down=(f, d),
    )


def init_ouro_params(key: jax.Array, cfg: OuroConfig, init_scale) -> dict:
    """Embedding, final norm, head, the exits' gate (``gate`` f32[D, 1],
    ``gate_bias`` f32[1], zero) and ``layers``: every layer's leaves stacked
    ``[L, ...]``, slice ``i`` drawn as the leaf ``l<i>/<name>``."""
    params = lane._init_params(
        key, cfg, [_layer_shapes(cfg)] * cfg.num_layers, init_scale)
    for name, shape in (("gate", (cfg.hidden_size, 1)), ("gate_bias", (1,))):
        params[name] = lane._init_leaf(key, name, shape, init_scale)
    layers = [params.pop(f"l{i}") for i in range(cfg.num_layers)]
    params["layers"] = jax.tree.map(lambda *slices: jnp.stack(slices), *layers)
    return params


# ------------------------------------------------------------------ layers
def rotary_inv_freq(cfg: OuroConfig):
    """Plain RoPE over the whole head: ``theta^(-2i / d)``."""
    d = cfg.head_dim
    return cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)


def _layer(h, p, cfg: OuroConfig):
    eps = cfg.rms_norm_eps
    with jax.named_scope("lane.gqa"):
        mixed = lane.attention_mixer(
            _rms(h, p["norm1"], eps), p, kv_heads=cfg.num_kv_heads,
            heads_per_kv=cfg.num_heads // cfg.num_kv_heads, head_dim=cfg.head_dim,
            inv_freq=rotary_inv_freq(cfg), factor=1.0, sight=None,
            block=cfg.attn_query_block, scope="lane.gqa")
        h = h + _rms(mixed, p["norm2"], eps)
    with jax.named_scope("lane.dense_ffn"):
        fed = _swiglu(_rms(h, p["norm3"], eps), p["w_gate"], p["w_up"], p["w_down"])
        return h + _rms(fed, p["norm4"], eps), None


def _close_pass(h, norm_f, cfg: OuroConfig):
    """The final norm, which closes every pass."""
    with jax.named_scope("lane.head"):
        return _rms(h, norm_f, cfg.rms_norm_eps), None


#: a layer's leaves, by the part that reads them
_PART_LEAVES = {"lane.gqa": ("norm1", "norm2", "wq", "wk", "wv", "wo"),
                "lane.dense_ffn": ("norm3", "norm4", "w_gate", "w_up", "w_down")}


def _layer_slices(p: dict):
    """``take(i) -> layer i's leaves`` out of the stacked ones, the matrices
    as the products take them (``lane._OPERAND``; :func:`lane._mm` then
    finds nothing to cast): the cast of a whole stack once, every slice
    taken where its part's scope names it. The chip's compiler makes casts
    of whole stacks out of a loop's casts of slices whatever the program
    says (PR 34: 0.46 s a sweep), and what it makes itself carries no
    scope: 13 % of the device's busy time lay in no part."""
    stacks = {}
    for scope, names in _PART_LEAVES.items():
        with jax.named_scope(scope):
            stacks.update({name: p[name].astype(lane._OPERAND) if p[name].ndim == 3
                           else p[name] for name in names})

    def take(i):
        layer = {}
        for scope, names in _PART_LEAVES.items():
            with jax.named_scope(scope):
                layer.update({name: stacks[name][i] for name in names})
        return layer

    return take


def _visits(cfg: OuroConfig):
    """A pass of the trainer: ``total_ut_steps`` times through the layers
    and the final norm, the same leaves every time. A pass's visits of the
    layers are one loop over their stacked leaves. Read on the chip at the
    published size (PR 34) against a trace a visit (``l<i>`` a layer, 32
    visits forward and 32 ``cond``s backward): the program builds in 48.6 s
    against 150.0 s, so that the cell's cold traced run ends in 293 s where
    the unrolled one would need some 390 (the driver allows 360), and a
    sweep takes 12.16 s against 11.23 s: the loop reads a slice of the
    stacked weights and rewrites a slice of the sums a visit."""
    one_pass = (
        lane.Visit("layers", lambda h, p: _layer(h, p, cfg), times=cfg.num_layers,
                   slices=_layer_slices),
        lane.Visit("norm_f", lambda h, w: _close_pass(h, w, cfg)))
    return one_pass * cfg.total_ut_steps


# ------------------------------------------------------------------- exits
def exit_distribution(gates):
    """``gates`` f32[T, ...] (an exit's gate a position) -> ``(log p, p)``
    f32[T, ...] over the exits: ``p_t = sigmoid(g_t) prod_{j<t} (1 -
    sigmoid(g_j))``, the last the remainder ``prod_{j<T} (1 - sigmoid(g_j))``
    (its own gate is not read). In logarithms, so that a saturated gate
    gives a small number and not a zero."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gates[:-1]), axis=0)
    stay = jnp.concatenate([jnp.zeros_like(gates[:1]), stay], axis=0)
    log_p = jnp.concatenate(
        [jax.nn.log_sigmoid(gates[:-1]) + stay[:-1], stay[-1:]], axis=0)
    return log_p, jnp.exp(log_p)


def _exit_cross_entropy(h, head, tokens):
    """Exit's state -> its next-token cross-entropy a position, f32[T]."""
    with jax.named_scope("lane.head"):
        logp = jax.nn.log_softmax(_mm(h, head))
        return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]


def _exit_weights(states, gate, gate_bias):
    """``(p f32[T exits, positions], H(p) f32[positions])`` from the exits'
    states: the gate a float32-operand product, as a router's."""
    with jax.named_scope("lane.exit"):
        gates = jnp.stack([
            jnp.matmul(h, gate, precision=lane._FLOAT32)[:, 0] + gate_bias[0]
            for h in states])
        log_p, p = exit_distribution(gates)
        return p, -(p * log_p).sum(0)


def _exits(cfg: OuroConfig) -> lane.Exits:
    """An exit after every pass. The exits' leaves: the head (one for all
    the exits) and the gate."""
    per_pass = len(_visits(cfg)) // cfg.total_ut_steps
    steps = cfg.total_ut_steps

    def trained(states, leaves, tokens):
        head, gate, gate_bias = leaves
        # an exit's logits live while its own cross-entropy is computed,
        # in the backward pass too
        losses = jnp.stack([
            jax.checkpoint(_exit_cross_entropy)(h, head, tokens) for h in states])
        p, entropy = _exit_weights(states, gate, gate_bias)
        with jax.named_scope("lane.exit"):
            return ((p * losses).sum(0) - cfg.exit_entropy_beta * entropy).mean()

    def reported(states, leaves, tokens):
        head, gate, gate_bias = leaves
        loss = _exit_cross_entropy(states[-1], head, tokens).mean()
        p, entropy = _exit_weights(states, gate, gate_bias)
        with jax.named_scope("lane.exit"):
            return loss, jnp.stack([
                p[-1].mean(), entropy.mean() / max(math.log(steps), 1e-30)])

    return lane.Exits(
        after=tuple(per_pass * (t + 1) for t in range(steps)),
        leaves=("head", "gate", "gate_bias"), trained=trained, reported=reported,
        counted=len(EXIT_COUNTERS))


def ouro_losses(params: dict, tokens: jax.Array, cfg: OuroConfig):
    """``tokens`` i32[T + 1] -> ``(the trained loss, (the reported loss,
    exit counters f32[2]))``; for ``jax.grad``."""
    trained, (reported, _) = lane._loss(params, tokens, _visits(cfg), _exits(cfg))
    return trained, reported


def ouro_forward(params: dict, tokens: jax.Array, cfg: OuroConfig):
    """``(the reported loss, exit counters f32[2], [h_0 .. h_V])``: the
    forward pass as the lanes' trainer runs it (``lane._forward``), the
    input of every visit kept."""
    loss, (_, counted), hs, _ = lane._forward(params, tokens, _visits(cfg), _exits(cfg))
    return loss, counted, hs


# ------------------------------------------------------------- evaluation
def ouro_lane_bytes(cfg: OuroConfig) -> int:
    """Device bytes one lane needs while it trains: float32 parameters,
    momentum and gradients (12 bytes a parameter: the layers' gradients are
    all alive between a leaf's last visit and its first) and the peak of
    its activations: one exit's logits, their softmax and their gradient,
    the input of every visit, one layer's recomputed activations (about 24
    hidden-sized and 9 feed-forward-sized rows a token) and what attention
    keeps alive of its scores (``lane.attention_alive_bytes``). At the
    published widths it gives 10.2 GB (the chip's allocator peaks at 8.7
    GB: PR 34): one lane fits a 16.9 GB chip, two do not."""
    n_params = lane._count_params(
        lambda: init_ouro_params(jax.random.key(0), cfg, 1.0))
    t = cfg.seq_len
    visits = (cfg.num_layers + 1) * cfg.total_ut_steps
    activations = (
        4 * t * (3 * cfg.vocab_rows + (24 + visits) * cfg.hidden_size
                 + 9 * cfg.intermediate_size)
        + lane.attention_alive_bytes(
            t, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim,
            [None], cfg.attn_query_block))
    return 12 * n_params + activations


def make_ouro_eval_fn(cfg: OuroConfig = OuroConfig(), data_seed: int = 0):
    """``eval_fn(config_vec, budget) -> the last exit's held-out
    cross-entropy`` of the lane, by the lanes' one trainer
    (``lane.make_lane_eval_fn``: budget is momentum-SGD steps of one
    ``seq_len``-token sequence, every step all ``total_ut_steps`` passes and
    the loss over all the exits); ``eval_fn.lane_facts`` states its
    footprint, its tokens a step and its counters: :data:`EXIT_COUNTERS`
    from the device, then :data:`LOOP_COUNTERS`, facts of the loop, and
    ``lane.attention_counters``, whether the scores stay in VMEM."""
    init_key = jax.random.key(data_seed + 1)
    visits, exits = _visits(cfg), _exits(cfg)
    loop = (cfg.total_ut_steps, cfg.num_layers * cfg.total_ut_steps, len(exits.after))
    return lane.make_lane_eval_fn(
        init=lane.Init(init_ouro_params, init_key, cfg),
        visits=visits, exits=exits,
        data=make_token_dataset(jax.random.key(data_seed), cfg),
        lane_bytes=ouro_lane_bytes(cfg),
        counted=lane.Counted(
            EXIT_COUNTERS, lambda _, at_exits, n_val: list(at_exits / n_val)),
        static_counters=tuple(zip(LOOP_COUNTERS, loop)) + lane.attention_counters(
            cfg.seq_len, cfg.head_dim, cfg.num_heads // cfg.num_kv_heads, cfg.num_kv_heads))
