"""What the lanes of published blocks share (``kimi_linear.py``,
``mellum2.py``): a lane is one chip's share of a model of layers, trained
from the configuration's key by momentum SGD, one sequence a step.

Here live the search space and its decoding, the rule for a matrix
product's operands, the norm and the SwiGLU, the draw of a leaf, the
synthetic tokens, embedding and head, **the one expert layer**
(:func:`moe_held_experts`: what differs between routers is stated as
:class:`ExpertLayer`, a bias and a shared expert by their leaves) and **the
one lane trainer** (:func:`make_lane_eval_fn`: a model hands it its init
and its layers). A model's own file
keeps its mixers, its configuration and its footprint.
"""

from __future__ import annotations

import functools
import zlib
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hpbandster_tpu.ops.fused import LaneFacts
from hpbandster_tpu.space import ConfigurationSpace, UniformFloatHyperparameter

__all__ = [
    "ExpertLayer",
    "LANE_COUNTERS",
    "MOE_COUNTERS",
    "decode_lane_hparams",
    "lane_space",
    "make_lane_eval_fn",
    "make_token_dataset",
    "moe_held_experts",
]

#: what an evaluation counts on the device beside its loss, over the
#: expert layers of its validation pass: the share of token-choices that
#: fell on held experts (``held / outputs`` if routing is even) and the
#: fullest held expert's load over the mean held load
LANE_COUNTERS = ("moe_held_choice_share", "moe_load_max_over_mean")


def lane_space(seed=None) -> ConfigurationSpace:
    """lr (log), momentum, weight decay (log), init scale (log): the
    ``mlp_space`` axes and ranges."""
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


def decode_lane_hparams(vec: jax.Array):
    """Unit-cube vector -> (lr, momentum, weight_decay, init_scale)."""
    lr = 10.0 ** (-4.0 + 4.0 * vec[0])
    momentum = 0.99 * vec[1]
    wd = 10.0 ** (-7.0 + 5.0 * vec[2])
    init_scale = 10.0 ** (-1.0 + 2.0 * vec[3])
    return lr, momentum, wd, init_scale


# ----------------------------------------------------------------- products
#: what every matrix product's operands are cast to; the accumulation is
#: float32 (``workloads/transformer.py``'s ``_mm``). The tests set float32
#: here to hold the equations to the reference without rounding in the way.
_OPERAND = jnp.bfloat16


#: the few products whose operands stay float32 (a router's, whose top k
#: is a discrete choice, and KDA's blocks under the diagonal, which feed a
#: triangular solve): three bfloat16 passes, 2^-16 of a product. The six
#: passes of ``HIGHEST`` take the chip's compiler four seconds a product
#: and there are some sixty of them in a lane.
_FLOAT32 = jax.lax.Precision.HIGH


def _mm(a, b):
    return jnp.matmul(
        a.astype(_OPERAND), b.astype(_OPERAND), preferred_element_type=jnp.float32)


def _einsum(spec, a, b):
    return jnp.einsum(
        spec, a.astype(_OPERAND), b.astype(_OPERAND),
        preferred_element_type=jnp.float32)


def _mm_beside(x, *weights):
    """``x @ w`` for several ``w`` as ONE product, the weights side by
    side, and the columns handed back apart: the same sums, and one
    product for the compiler (half a second each on the chip's) and for
    the chip in place of several."""
    out = _mm(x, jnp.concatenate([w.astype(_OPERAND) for w in weights], axis=1))
    return jnp.split(out, np.cumsum([w.shape[1] for w in weights])[:-1], axis=1)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(x, w_gate, w_up, w_down):
    gate, up = _mm_beside(x, w_gate, w_up)
    return _mm(jax.nn.silu(gate) * up, w_down)


# --------------------------------------------------------------- parameters
def _init_leaf(key, name: str, shape, init_scale):
    """One leaf from the key and its own name, so that the draw does not
    depend on which other leaves exist. Matrices (and depthwise
    convolutions) are ``init_scale / sqrt(fan_in) * N(0, 1)``, the
    embedding ``init_scale * N(0, 1)`` (a lookup's fan-in is one: a
    smaller embedding only has the first norm multiply its gradient up),
    norm weights one, a balancing bias zero."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf.startswith("norm") or leaf.endswith("_norm"):
        return jnp.ones(shape, jnp.float32)
    if leaf == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    # drawn as a matrix and folded: the same numbers in the same order (the
    # chip's compiler takes fourteen seconds over a three-dimensional draw)
    draw = jax.random.normal(
        jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
        (int(np.prod(shape[:-1])), shape[-1]), jnp.float32).reshape(shape)
    fan_in = 1 if leaf == "embed" else shape[-2]
    return (init_scale * fan_in ** -0.5) * draw


def _init_params(key, cfg, layer_shapes, init_scale, init_leaf=_init_leaf) -> dict:
    """Embedding, final norm, head and ``l<i>`` for each of ``layer_shapes``
    (a ``{leaf: shape}`` a layer)."""
    shapes = {
        "embed": (cfg.vocab_rows, cfg.hidden_size),
        "norm_f": (cfg.hidden_size,),
        "head": (cfg.hidden_size, cfg.vocab_rows),
    }
    params = {n: init_leaf(key, n, s, init_scale) for n, s in shapes.items()}
    for i, layer in enumerate(layer_shapes):
        params[f"l{i}"] = {
            n: init_leaf(key, f"l{i}/{n}", s, init_scale) for n, s in layer.items()}
    return params


def _count_params(init) -> int:
    """Parameters of ``init() -> params``, from its shapes alone."""
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(jax.eval_shape(init)))


# ------------------------------------------------------------ expert layer
class ExpertLayer(NamedTuple):
    """What the expert layer is told of its router and of this chip's
    share. Whether the router has a balancing bias and whether a shared
    expert stands beside the routed ones is stated by the layer's leaves
    (``router_bias``; ``shared_gate``, ``shared_up``, ``shared_down``)."""

    #: the router's outputs: all the model's routed experts
    outputs: int
    top_k: int
    #: which of them this chip holds, in the order of the leaves' first axis
    held: Tuple[int, ...]
    #: ``"sigmoid"`` or ``"softmax"`` (over all the outputs, before the top k)
    score: str = "sigmoid"
    #: the chosen scores, renormalised to sum to one, times this
    scaling: float = 1.0


#: a tile of the grouped product is four times the even load, and no more
#: rows than this. Measured on the chip at 65,536 token-choices (PR 33,
#: the rows moved by gathers; a layer's forward and backward pass, with
#: 16,087 / 17,450 choices held: just under and just over the even load of
#: 16,384, which the lane's measured share of 25.1 % straddles): tiles of
#: 4,096 rows 45 / 52 ms, 8,192: 41 / 52, 16,384: 39 / 58, 32,768: 58 / 58,
#: one of 65,536: 87; a whole sweep of the Mellum2 lane 15.06 s at 4,096,
#: 14.79 s at 8,192, 16.53 s at 32,768 (all read while the loop's buffers
#: were still filled with zeros first: 0.2 s a sweep whatever the tile;
#: 14.55 s at 8,192 without the fill). A reached tile pays for all its
#: rows, the closing group's too (the last reached tile is half empty on
#: average), and per tile for a pass over the experts' gradient and a
#: change of layout of their weights (1.9 ms at these widths). With the
#: scatter-adds of PR 32 a tile cost a pass over the layer's output besides,
#: and 32,768 rows read best (67 ms against 70 at 8,192)
_TILE_ROWS = 8192

#: how the expert layer moves its rows, beside a lane's counted facts
#: (``make_lane_eval_fn(static_counters=...)``): 1 where dispatch, combine
#: and their transposes are gathers by the counting sort's two permutations
MOE_COUNTERS = (("moe_combine_by_gather", 1),)


def _tile_sizes(ends, lo, rows: int):
    """The rows of the tile at ``lo`` by group: each held expert's (the
    sorted rows up to ``ends[e]`` are experts ``0 .. e``'s), then the
    closing group's: every row of a tile belongs to a group."""
    return jnp.diff(jnp.clip(ends - lo, 0, rows), prepend=0, append=rows)


def _tile_experts(xs, e_in, e_down, sizes):
    """A tile's rows through their experts: gate and up side by side as one
    grouped product, then down."""
    dot = lambda a, w: jax.lax.ragged_dot(
        a.astype(_OPERAND), w, sizes, preferred_element_type=jnp.float32)
    gate_up = dot(xs, e_in)
    f = e_down.shape[1]
    return dot(jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:], e_down)


def _sum_of_choices(sorted_rows, place, held, top_k: int, weight=None):
    """``out[t] = sum_j weight[t, j] * sorted_rows[place[t, j]]`` over the
    held choices of token ``t`` (``weight`` one where there is none),
    float32: a gather by ``place`` (choice -> sorted row). A ``where`` and
    not a product by zero: what a row holds that is no held choice's (of
    the closing group, or of a tile that was not reached) never reaches
    the sum, whatever a diverged lane left there."""
    picked = jnp.where(held[:, None], sorted_rows[place].astype(jnp.float32), 0.0)
    picked = picked.reshape(-1, top_k, picked.shape[-1])
    return (picked if weight is None else picked * weight[:, :, None]).sum(1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _routed(x, weight, e_in, e_down, order, place, ends, top_k, rows):
    """``y[t] = sum_j weight[t, j] * E(x[t])`` over the held choices of
    token ``t``: the rows move from token order to expert order and back by
    gathers alone, in both passes. ``order`` (sorted row -> choice) and
    ``place`` (choice -> sorted row) are the counting sort's permutation
    and its inverse, so each movement's transpose is a gather by the other
    one, and is written so here: what autodiff makes of a gather is a
    scatter-add, which sorts its indices on the device."""
    return _routed_forward(x, weight, e_in, e_down, order, place, ends, top_k, rows)[0]


def _routed_forward(x, weight, e_in, e_down, order, place, ends, top_k, rows):
    """``(y, what the backward rule keeps: the inputs)``. The rules name
    their own scope: the backward one is traced where the layer's caller
    has none."""
    with jax.named_scope("lane.moe"):
        d = x.shape[1]
        xb = x.astype(_OPERAND)
        n_held = ends[-1]

        def tile(i, ys):
            # dispatch: a gather by ``order``
            take = jax.lax.dynamic_slice(order, (i * rows,), (rows,))
            return jax.lax.dynamic_update_slice(ys, _tile_experts(
                xb[take // top_k], e_in, e_down, _tile_sizes(ends, i * rows, rows)),
                (i * rows, 0))

        # what the tiles produce is the sorted rows, and only the tiles
        # that a held choice reaches are computed. The rows start as the
        # device finds them (no fill: a pass over 0.6 GB a layer at the
        # Mellum2 lane's size): a reached tile writes all of its rows, and
        # the combine reads no row of another
        ys = jax.lax.fori_loop(
            0, -(-n_held // rows), tile, jax.lax.empty((order.shape[0], d), jnp.float32))
        # combine: a gather by ``place``, summed over the top k
        y = _sum_of_choices(ys, place, place < n_held, top_k, weight)
    return y, (x, weight, e_in, e_down, order, place, ends)


def _routed_backward(top_k, rows, kept, dy):
    """Each reached tile's rows are computed again from the layer's inputs
    (what ``jax.checkpoint`` around a tile did) and differentiated; the
    combine's transpose is a gather by ``order`` and the dispatch's a
    gather by ``place``."""
    x, weight, e_in, e_down, order, place, ends = kept
    with jax.named_scope("lane.moe"):
        d = x.shape[1]
        xb = x.astype(_OPERAND)
        n_held = ends[-1]
        weight_of = weight.reshape(-1)

        def tile(i, grads):
            g_in, g_down, dxs, dweights = grads
            take = jax.lax.dynamic_slice(order, (i * rows,), (rows,))
            token = take // top_k
            sizes = _tile_sizes(ends, i * rows, rows)
            ys, pull = jax.vjp(
                lambda xs, e_in, e_down: _tile_experts(xs, e_in, e_down, sizes),
                xb[token], e_in, e_down)
            dy_rows = dy[token]
            t_xs, t_in, t_down = pull(dy_rows * weight_of[take][:, None])
            return (g_in + t_in, g_down + t_down,
                    jax.lax.dynamic_update_slice(dxs, t_xs, (i * rows, 0)),
                    jax.lax.dynamic_update_slice(
                        dweights, (dy_rows * ys).sum(-1), (i * rows,)))

        g_in, g_down, dxs, dweights = jax.lax.fori_loop(
            0, -(-n_held // rows), tile,
            (jnp.zeros_like(e_in), jnp.zeros_like(e_down),
             jax.lax.empty((order.shape[0], d), xb.dtype),
             jax.lax.empty(order.shape, jnp.float32)))
        held = place < n_held
        dx = _sum_of_choices(dxs, place, held, top_k)
        dweight = jnp.where(held, dweights[place], 0.0).reshape(weight.shape)
    return dx, dweight, g_in, g_down, None, None, None


_routed.defvjp(_routed_forward, _routed_backward)


def moe_held_experts(x, p, layer: ExpertLayer):
    """This chip's part of the expert layer: ``w_e * E_e(x)`` for each
    chosen expert it holds, and the shared expert once where the layer has
    one. Returns ``(y f32[T, D], counters f32[2])``, the counters being
    (token-choices on held experts, fullest held expert's load over the
    mean held load).

    The router scores all ``outputs`` (``s``: a sigmoid each, or a softmax
    over them all), chooses the top k of ``s`` (``s + b`` with a bias) and
    weighs them ``s_e / sum(chosen s) * scaling``. Token-choices are sorted
    by held expert (the others last) and the held ones go through
    ``jax.lax.ragged_dot``, one group an expert, in tiles of four times the
    even load (at most ``_TILE_ROWS`` rows), the rows that are not for this
    chip in a last group of zero weights: only the tiles that a held choice
    reaches are computed, so the work follows the load and no token is
    dropped whatever the load. Rows move to expert order and back by
    gathers alone (:func:`_routed`)."""
    t = x.shape[0]
    top_k, held = layer.top_k, len(layer.held)
    logits = jnp.matmul(x, p["router"], precision=_FLOAT32)
    s = (jax.nn.sigmoid(logits) if layer.score == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    _, chosen = jax.lax.top_k(s + p["router_bias"] if "router_bias" in p else s, top_k)
    # the chosen scores by comparison and not by index: the transpose of
    # ``take_along_axis`` is a scatter-add too
    s_chosen = jnp.where(
        chosen[:, :, None] == jnp.arange(layer.outputs), s[:, None, :], 0.0).sum(-1)
    weight = s_chosen / s_chosen.sum(-1, keepdims=True)
    if layer.scaling != 1.0:
        weight = weight * layer.scaling
    slot_of = np.full((layer.outputs,), held, np.int32)          # held = "not here"
    slot_of[list(layer.held)] = np.arange(held)
    slot = jnp.asarray(slot_of)[chosen].reshape(-1)              # [T * k]
    # a counting sort, stable: a choice's place is its slot's start plus the
    # earlier choices of its slot (the chip's compiler takes ten seconds
    # over an ``argsort`` of this length, and there is one a layer and pass)
    in_slot = (slot[:, None] == jnp.arange(held + 1)[None, :]).astype(jnp.int32)
    all_loads = in_slot.sum(0)
    place = (in_slot * (jnp.cumsum(in_slot, 0) - in_slot
                        + (jnp.cumsum(all_loads) - all_loads)[None, :])).sum(1)
    loads = all_loads[:held]
    rows = min(t * top_k,
               max(min(4 * t * top_k * held // layer.outputs, _TILE_ROWS), 8))
    n_tiles = -(-t * top_k // rows)
    order = jnp.zeros((n_tiles * rows,), jnp.int32).at[place].set(
        jnp.arange(t * top_k, dtype=jnp.int32), unique_indices=True)

    # every row of a tile belongs to a group: after the held experts comes
    # one whose weights are zero and takes the rows that are not for this
    # chip. On the chip ``ragged_dot`` leaves the rows that no group holds
    # as it finds them, in the backward pass too, where a mask on its
    # output cannot reach.
    with_rest = lambda w: jnp.concatenate([w, jnp.zeros_like(w[:1])]).astype(_OPERAND)
    # gate and up side by side: one grouped product for the two
    y = _routed(x, weight, with_rest(jnp.concatenate([p["e_gate"], p["e_up"]], -1)),
                with_rest(p["e_down"]), order, place, jnp.cumsum(loads), top_k, rows)
    if "shared_gate" in p:
        y = y + _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    load = loads.astype(jnp.float32)
    counters = jnp.stack([load.sum(), load.max() / jnp.maximum(load.mean(), 1e-9)])
    return y, counters


# ------------------------------------------------------- embedding and head
def _embed(params, tokens):
    with jax.named_scope("lane.head"):
        return params["embed"][tokens[:-1]]


def _head_loss(h, norm_f, head, tokens, eps):
    with jax.named_scope("lane.head"):
        logits = _mm(_rms(h, norm_f, eps), head)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0].mean()


def _loss(params: dict, tokens, layers, eps):
    """``tokens`` i32[T + 1] -> ``(mean next-token cross-entropy over the
    vocabulary slice, counters f32[n_layers, 2])`` through ``layers``, one
    ``(h, p_i) -> (h, counters f32[2])`` a layer; for ``jax.grad``: each
    layer's inside is recomputed in the backward pass."""
    h = _embed(params, tokens)
    counters = []
    for i, layer in enumerate(layers):
        h, c = jax.checkpoint(layer)(h, params[f"l{i}"])
        counters.append(c)
    return _head_loss(h, params["norm_f"], params["head"], tokens, eps), jnp.stack(counters)


def _forward(params: dict, tokens, layers, eps):
    """:func:`_loss` with nothing kept for a gradient but the input of
    every layer: ``(loss, counters, [h_0 .. h_L])``. An evaluation takes the
    gradient from these by the chain rule, a layer at a time, each layer's
    inside computed again (what ``jax.grad`` does with ``jax.checkpoint``
    around every layer), so that a pass that needs no gradient (a held-out
    sequence) is the same trace as one that does."""
    hs, counters = [_embed(params, tokens)], []
    for i, layer in enumerate(layers):
        h, c = layer(hs[-1], params[f"l{i}"])
        hs.append(h)
        counters.append(c)
    loss = _head_loss(hs[-1], params["norm_f"], params["head"], tokens, eps)
    return loss, jnp.stack(counters), hs


# ------------------------------------------------------------------- data
def make_token_dataset(key: jax.Array, cfg):
    """``(train i32[n_train, T + 1], val i32[n_val, T + 1])``: ids over
    the vocabulary slice (``cfg.vocab_rows``), Zipf-distributed (``p(rank
    r) ~ 1 / r``, by inverse CDF from uniform draws), the second half of
    each sequence repeating its first, so that a lane predicts it only
    through state and attention."""
    cdf = np.cumsum(1.0 / np.arange(1, cfg.vocab_rows + 1, dtype=np.float64))
    cdf = jnp.asarray((cdf / cdf[-1]).astype(np.float32))
    half = cfg.seq_len // 2 + 1

    def draw(k, n):
        ids = jnp.searchsorted(cdf, jax.random.uniform(k, (n, half)))
        ids = jnp.minimum(ids, cfg.vocab_rows - 1).astype(jnp.int32)
        return jnp.concatenate([ids, ids[:, :cfg.seq_len + 1 - half]], axis=1)

    kt, kv = jax.random.split(key)
    return draw(kt, cfg.n_train), draw(kv, cfg.n_val)


# ------------------------------------------------------------- evaluation
def make_lane_eval_fn(*, init, layers, moe_layers, eps, data,
                      choices_per_pass: int, lane_bytes: int,
                      static_counters=()):
    """``eval_fn(config_vec, budget) -> held-out cross-entropy`` of a lane
    of layers, handed to ``FusedBOHB(eval_fn=...)`` as
    ``make_transformer_eval_fn``'s is. Budget is momentum-SGD steps of one
    sequence; step ``t`` trains on sequence ``t mod n_train``; ``v <- m v +
    g + wd p; p <- p - lr v``. ``eval_fn.lane_facts`` states the lane's
    footprint, its tokens a step and its device counters
    (:data:`LANE_COUNTERS`, then ``static_counters``), which the rung's
    evaluation (``ops.fused.eval_lanes``) reads.

    The model is what it hands over:

    * ``init(init_scale) -> params``: ``embed``, ``norm_f``, ``head`` and
      one ``l<i>`` a layer, from the configuration's key;
    * ``layers``: one ``(h, p_i) -> (h, counters f32[2])`` a layer, the
      counters those of :func:`moe_held_experts` (zeros where it has no
      experts); a pass is :func:`_forward` through them, and a layer's
      gradient is taken from the layer alone, its inside computed again;
    * ``moe_layers``: per layer, whether it has experts; ``eps`` the final
      norm's; ``data = (train, val)`` of :func:`make_token_dataset`;
      ``choices_per_pass`` the token-choices of one expert layer a pass;
      ``lane_bytes`` the device bytes a lane needs while it trains;
    * ``static_counters``: ``((name, value), ...)`` facts of how the lane
      is computed that ride beside the counted ones."""
    train, val = data
    n_train, n_val = train.shape[0], val.shape[0]
    n_layers = len(layers)

    def with_counters(vec: jax.Array, budget):
        lr, momentum, wd, init_scale = decode_lane_hparams(vec)
        params = init(init_scale)
        steps = jnp.asarray(budget, jnp.float32).round().astype(jnp.int32)

        # ONE loop over the training sequences and then the held-out ones:
        # each pass runs the forward trace, and a training pass the backward
        # one and the update besides, so the program holds the forward pass
        # once for both (a quarter of its compilation). The backward pass
        # and the update are a ``lax.cond`` a layer (the head, each layer
        # from the last, the embedding): parameters and momentum through
        # ONE ``cond`` would be held twice over, and all the gradient's
        # leaves would be alive at once.
        def update(p, v, g):
            with jax.named_scope("lane.update"):
                v = jax.tree.map(lambda vi, gi, pi: momentum * vi + gi + wd * pi, v, g, p)
                return jax.tree.map(lambda pi, vi: pi - lr * vi, p, v), v

        def one_pass(t, carry):
            p, v, held_loss, held_counters = carry
            training = t < steps
            seq = jnp.where(training, train[t % n_train],
                            val[jnp.clip(t - steps, 0, n_val - 1)])
            loss, counters, hs = _forward(p, seq, layers, eps)
            p, v = dict(p), dict(v)

            def if_training(step, *state):
                return jax.lax.cond(training, step, lambda *same: same, *state)

            def head_step(dh, pn, vn, ph, vh):
                dh, g_norm, g_head = jax.grad(_head_loss, argnums=(0, 1, 2))(
                    hs[-1], pn, ph, seq, eps)
                return (dh,) + update(pn, vn, g_norm) + update(ph, vh, g_head)

            dh, p["norm_f"], v["norm_f"], p["head"], v["head"] = if_training(
                head_step, jnp.zeros_like(hs[-1]), p["norm_f"], v["norm_f"],
                p["head"], v["head"])
            for i in reversed(range(n_layers)):
                def layer_step(dh, pl, vl, i=i):
                    _, pull = jax.vjp(lambda h, q: layers[i](h, q)[0], hs[i], pl)
                    dh, g = pull(dh)
                    return (dh,) + update(pl, vl, g)

                dh, p[f"l{i}"], v[f"l{i}"] = if_training(
                    layer_step, dh, p[f"l{i}"], v[f"l{i}"])

            def embed_step(pe, ve):
                with jax.named_scope("lane.head"):
                    g = jnp.zeros_like(pe).at[seq[:-1]].add(dh)
                return update(pe, ve, g)

            p["embed"], v["embed"] = if_training(embed_step, p["embed"], v["embed"])
            held = jnp.where(training, 0.0, 1.0)
            return p, v, held_loss + held * loss, held_counters + held * counters

        _, _, loss, counters = jax.lax.fori_loop(0, steps + n_val, one_pass, (
            params, jax.tree.map(jnp.zeros_like, params), jnp.float32(0.0),
            jnp.zeros((n_layers, 2), jnp.float32)))
        loss = loss / n_val
        moe = counters[np.asarray(moe_layers, bool)]
        # a lane whose training diverged has no number for a loss: it
        # reports the worst one, infinity; NaN is the sweep's mask for a crash
        return jnp.where(jnp.isnan(loss), jnp.inf, loss), jnp.stack([
            moe[:, 0].sum() / max(moe.shape[0] * n_val * choices_per_pass, 1),
            moe[:, 1].mean() / n_val if moe.shape[0] else jnp.float32(0.0),
        ] + [jnp.float32(value) for _, value in static_counters])

    def eval_fn(vec: jax.Array, budget) -> jax.Array:
        return with_counters(vec, budget)[0]

    eval_fn.lane_facts = LaneFacts(
        bytes=lane_bytes, tokens_per_step=train.shape[1] - 1,
        counters=LANE_COUNTERS + tuple(name for name, _ in static_counters),
        with_counters=with_counters, traced_budget=True)
    return eval_fn
