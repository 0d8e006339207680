"""Plain reference of ``laguna-xs2-sgd``: one chip's share of Laguna-XS.2
(``configs/laguna-xs2-sgd.json``: the leading dense layer and one whole period
of the four that follow it), its tokens, its loss, the loss's gradients and
momentum SGD, written from the layer equations in straightforward
``jax.numpy``. Imports nothing of the program and takes nothing it made:
tokens and initial weights come from the seed again. What a lane is made of
whatever its model (the draw of a leaf, the tokens, the norm, the SwiGLU, the
head's loss, the gap) is the ``kimi-linear-sgd`` reference's, and how a
sweep's record is read lane by lane, which lanes are retrained and the norms
of a step's change the ``ouro-sgd`` reference's, both loaded from beside this
file.

float32 under ``jax.default_matmul_precision("highest")``. Attention is one
masked softmax over the whole ``S x S`` square, head by head, the key/value
heads repeated outright for their query heads; the window is a mask; the
rotary tables are built from the formulas and **the partial rotation is
written channel by channel** (:func:`rope`); the gate is a multiply a head;
the expert layer is a loop over the held experts with a mask and the shared
expert added once: no blocks, no tiles, no sorting, no grouped product. The
gradient is ``jax.grad`` of the whole loss (:func:`loss_fn`) at the tests'
size, and at the published widths the same gradient by the chain rule, **a
layer at a time** (``jax.vjp`` of :func:`layer` and of ``head_loss``, a layer
stepped as soon as its gradient is known), so that it fits the chip beside
2.77 GB of reference parameters and as much momentum. Each layer and each
head of attention recomputes its activations in the backward pass
(``jax.checkpoint``): that changes what is kept, not what is computed.

The equations (D = ``hidden_size``, ``G = num_key_value_heads`` heads of ``d =
head_dim``, layer ``l`` of kind ``layer_types[l]`` with ``H_l =
num_attention_heads_per_layer[l]`` query heads, ``R_l = H_l / G``; RMSNorm eps
``rms_norm_eps``, no bias anywhere, untied head):

* a layer on ``h`` f32[S, D]: ``h <- h + Attn_l(rmsnorm(h; n1))``; ``h <- h +
  F_l(rmsnorm(h; n2))``, ``F_l`` a SwiGLU of ``intermediate_size`` where
  ``mlp_layer_types[l]`` is ``dense``, else the expert layer;
* ``Attn_l(x)``: ``q = x W_q`` as [S, H_l, d], ``k = x W_k``, ``v = x W_v`` as
  [S, G, d]. With ``w = partial_rotary_factor_l d`` (the kind's entry of
  ``rope_parameters``: 128 in a window layer, 64 in a full one) and ``i <
  w / 2``: ``y_i = x_i cos(t f_i) - x_{i + w/2} sin(t f_i)``, ``y_{i + w/2} =
  x_{i + w/2} cos(t f_i) + x_i sin(t f_i)`` at position ``t``, ``y_j = x_j``
  for ``j >= w``, for every head of ``q`` and ``k``. A window layer
  (``rope_type`` ``default``): ``f_i = theta^(-2i / w)``. A full layer
  (``yarn``): ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``
  with ``c(r) = w ln(L / (2 pi r)) / (2 ln theta)``, ``ramp_i = clip((i -
  low) / (high - low), 0, 1)``, ``f_i = (1 - ramp_i) theta^(-2i / w) + ramp_i
  theta^(-2i / w) / factor``, and cos and sin both times
  ``attention_factor`` (the channels that are not turned do not carry it);
* ``s_ij = q_i . k_j / sqrt(d)``, query head ``a`` against key/value head ``a
  // R_l``; seen where ``j <= i`` and, in a window layer, ``i - j <
  sliding_window``; ``o = softmax(s) v``; **``g = sigmoid(x W_g)``, ``W_g``
  [D, H_l], one number a head and position; ``Attn_l(x) = concat_a(g_a o_a)
  W_o``**;
* the expert layer: ``s = sigmoid(x W_r)`` over all the router's outputs; the
  top ``num_experts_per_tok`` of ``s``; weights ``moe_routed_scaling_factor
  s_e / sum(chosen s)``; this chip adds ``w_e E_e(x)`` for chosen experts it
  holds and ``E_shared(x)`` once, every ``E`` a SwiGLU;
* ``logits = rmsnorm(h; n_f) W_head``; the loss the mean next-token
  cross-entropy over the vocabulary slice.

The comparison is the ``ouro-sgd`` reference's in its lanes: from the sweep of
the window that the seed draws, the lane that reached the top rung is
retrained as far as its second rung (losses after 1 and 3 steps) and, of the
other lanes of a regular init scale, the one of the smallest learning rate as
far as its first, both against the reported losses. What decides is **what
that lane's first step changes at a learning rate of** :data:`STEP_LR` (and
an init scale of at most :data:`STEP_INIT_SCALE_MOST`, which a regular lane's is;
``lane_change`` of the record: the program's trainer, the parameters after
the step less the parameters at initialisation), held against the reference's
own first step there as the norm of the difference over the norm of the
reference's change, by group of leaves: attention (with the gate), the dense
feed-forward, the experts and their routers, embedding and head. A step that
is lost reads 1. The change is read from the state as it is stored, by a
program of its own (``change_of``): taken inside the update's program it did
not show a bfloat16 state's rounding on the chip. The losses are a net beside
it. The readings are at the limits below. The reference's own change goes to
the host before the program's trainer runs (``olmo-hybrid-sgd``'s way: that
trainer holds the parameters at initialisation, after the step and their
difference beside the momentum).
"""

import functools
import importlib.util
import json
import math
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np


def _beside(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + name.split("-")[0], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_lane = _beside("kimi-linear-sgd.py")
_steps = _beside("ouro-sgd.py")
HPARAMS = _lane.HPARAMS
dataset, gap, init_leaf = _lane.dataset, _lane.gap, _lane.init_leaf
rmsnorm, swiglu, head_loss = _lane.rmsnorm, _lane.swiglu, _lane.head_loss
sample_lanes, squares = _steps.sample_lanes, _steps.squares

KINDS = {"full_attention": "full", "sliding_attention": "sliding"}
MLPS = ("dense", "sparse")


# ------------------------------------------------------------- configuration
def layer_kinds(config):
    """``[(mixer kind, feed-forward kind, query heads)]`` of the layers held."""
    n = config["num_hidden_layers"]
    lists = [config[k] for k in ("layer_types", "mlp_layer_types",
                                 "num_attention_heads_per_layer")]
    assert all(len(x) == n for x in lists), "one entry a layer held"
    assert all(mlp in MLPS for mlp in lists[1])
    assert config["gating"] and not config["attention_bias"]
    assert not config["tie_word_embeddings"]
    assert not config["moe_apply_router_weight_on_input"]
    return [(KINDS[kind], mlp, heads) for kind, mlp, heads in zip(*lists)]


def rope_of(config, kind):
    """The kind's entry of ``rope_parameters``: its own ``partial_rotary_factor``
    wins over the top-level one."""
    return config["rope_parameters"][
        "sliding_attention" if kind == "sliding" else "full_attention"]


def layer_shapes(config, kind, mlp, heads):
    d, dh, hk = config["hidden_size"], config["head_dim"], config["num_key_value_heads"]
    shapes = {"norm1": (d,), "norm2": (d,), "wq": (d, heads * dh), "wk": (d, hk * dh),
              "wv": (d, hk * dh), "w_head_gate": (d, heads), "wo": (heads * dh, d)}
    if mlp == "dense":
        f = config["intermediate_size"]
        shapes.update({"ffn_gate": (d, f), "ffn_up": (d, f), "ffn_down": (f, d)})
    else:
        f, fs = config["moe_intermediate_size"], config["shared_expert_intermediate_size"]
        held, outputs = len(config["cut"]["experts_held"]), config["cut"]["router_outputs"]
        shapes.update({
            "router": (d, outputs),
            "shared_gate": (d, fs), "shared_up": (d, fs), "shared_down": (fs, d),
            "e_gate": (held, d, f), "e_up": (held, d, f), "e_down": (held, f, d)})
    return shapes


def init_params(config, key, init_scale, dtype=jnp.float32):
    d, rows = config["hidden_size"], config["vocab_size"]
    shapes = {"embed": (rows, d), "norm_f": (d,), "head": (d, rows)}
    params = {n: init_leaf(key, n, s, init_scale) for n, s in shapes.items()}
    for i, kinds in enumerate(layer_kinds(config)):
        params["l%d" % i] = {
            n: init_leaf(key, "l%d/%s" % (i, n), s, init_scale)
            for n, s in layer_shapes(config, *kinds).items()}
    return jax.tree.map(lambda x: x.astype(dtype), params)


# -------------------------------------------------------------------- layers
def yarn_range(rope, width):
    """``(low, high)`` of the ramp over ``width`` channels: the channels at
    which ``beta_fast`` and ``beta_slow`` turns over the original context are
    reached."""
    def channel(turns):
        return (width * math.log(rope["original_max_position_embeddings"]
                                 / (turns * 2 * math.pi))
                / (2 * math.log(rope["rope_theta"])))

    low = max(math.floor(channel(rope["beta_fast"])), 0)
    high = min(math.ceil(channel(rope["beta_slow"])), width - 1)
    return low, high


def rotary(config, kind, t):
    """``(cos, sin)`` f32[T, w / 2] of a layer of ``kind``, one column a pair
    of channels, ``w`` the channels of a head that the kind turns."""
    rope = rope_of(config, kind)
    width = int(round(config["head_dim"] * rope["partial_rotary_factor"]))
    inv_freq = rope["rope_theta"] ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    factor = 1.0
    if rope["rope_type"] == "yarn":
        low, high = yarn_range(rope, width)
        ramp = np.clip((np.arange(width // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
        inv_freq = (1.0 - ramp) * inv_freq + ramp * inv_freq / rope["factor"]
        factor = rope["attention_factor"]
    else:
        assert rope["rope_type"] == "default"
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32)[None, :])
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def rope(x, cos, sin):
    """``x`` [T, H, d], the tables [T, w / 2]: channel ``i < w / 2`` turns
    with channel ``i + w / 2``; the channels from ``w`` on pass as they
    are."""
    half = cos.shape[1]
    c, s = cos[:, None, :], sin[:, None, :]
    first, second, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [first * c - second * s, second * c + first * s, rest], axis=-1)


def attention(x, p, kind, heads, config):
    t, dh, hk = x.shape[0], config["head_dim"], config["num_key_value_heads"]
    cos, sin = (table.astype(x.dtype) for table in rotary(config, kind, t))
    q = rope((x @ p["wq"]).reshape(t, heads, dh), cos, sin)
    k = rope((x @ p["wk"]).reshape(t, hk, dh), cos, sin)
    v = (x @ p["wv"]).reshape(t, hk, dh)
    # query head a on key/value head a // (heads / hk): repeated outright
    k, v = (jnp.repeat(y, heads // hk, axis=1) for y in (k, v))
    at, key = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = key <= at
    if kind == "sliding":
        mask = mask & (at - key < config["sliding_window"])

    @jax.checkpoint
    def head(qh, kh, vh):
        scores = (qh @ kh.T / dh ** 0.5).astype(jnp.float32)
        return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(x.dtype) @ vh

    out = jax.lax.map(lambda a: head(*a), tuple(y.swapaxes(0, 1) for y in (q, k, v)))
    gate = jax.nn.sigmoid(x @ p["w_head_gate"])                         # [T, heads]
    return (out.swapaxes(0, 1) * gate[:, :, None]).reshape(t, heads * dh) @ p["wo"]


def router_weights(x, p, config):
    """``(chosen i32[T, k], weight [T, k])``: sigmoid scores over all the
    router's outputs, the top k, renormalised and scaled."""
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    s_chosen, chosen = jax.lax.top_k(s, config["num_experts_per_tok"])
    return chosen, (s_chosen / s_chosen.sum(-1, keepdims=True)
                    * config["moe_routed_scaling_factor"])


def experts(x, p, config, held=None, shared=True):
    """The share of the expert layer that holds ``held`` (global expert
    ids, in the order of the leaves' leading axis); default the
    configuration's. The held experts one after the other, each over every
    token with its weight or zero; the shared expert once (``shared``
    False: without it, for the sum over the shares)."""
    held = config["cut"]["experts_held"] if held is None else held
    chosen, weight = router_weights(x, p, config)
    ids = jnp.asarray(held, chosen.dtype)[:, None, None]
    w = jnp.where(chosen[None] == ids, weight[None], 0.0).sum(-1).astype(x.dtype)  # [held, T]

    def add_expert(y, e):
        w_e, gate, up, down = e
        return y + w_e[:, None] * swiglu(x, gate, up, down), None

    start = (swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"]) if shared
             else jnp.zeros_like(x))
    y, _ = jax.lax.scan(add_expert, start, (w, p["e_gate"], p["e_up"], p["e_down"]))
    return y


def layer(h, p, kinds, config):
    kind, mlp, heads = kinds
    eps = config["rms_norm_eps"]
    h = h + attention(rmsnorm(h, p["norm1"], eps), p, kind, heads, config)
    x = rmsnorm(h, p["norm2"], eps)
    if mlp == "dense":
        return h + swiglu(x, p["ffn_gate"], p["ffn_up"], p["ffn_down"])
    return h + experts(x, p, config)


def loss_fn(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens`` i32[S + 1]; for
    ``jax.grad``, whole."""
    h = params["embed"][tokens[:-1]]
    for i, kinds in enumerate(layer_kinds(config)):
        h = jax.checkpoint(functools.partial(layer, kinds=kinds, config=config))(
            h, params["l%d" % i])
    return head_loss(h, params["norm_f"], params["head"], tokens, config)


# ------------------------------------------------------------------ training
_LANE_FUNCTIONS = {}
#: the check compiles beside the program it checks: quickly, not for speed
_COMPILE = {"exec_time_optimization_effort": -1.0}


def lane_functions(config, dtype):
    """A lane's functions, made once per configuration and precision:
    ``init(init_scale) -> p``, ``step(p, v, t, lr, momentum, wd, changed=None)
    -> (p, v)``, ``held_out(p) -> loss``, ``compile_ahead()``.

    The gradient is :func:`loss_fn`'s by the chain rule, a layer at a time
    (``jax.vjp`` of the same :func:`layer` and ``head_loss``, each layer's
    input kept and its inside recomputed), and a layer's parameters and
    momentum are updated as soon as its gradient is known: layers of one
    kind share one compiled function, and the lane's old state makes room
    for the new a layer at a time. ``changed``, a dictionary, is filled leaf
    by leaf with what the step changed, the parameters after it less the
    parameters before, float32."""
    key = (json.dumps(config, sort_keys=True), jnp.dtype(dtype).name)
    if key in _LANE_FUNCTIONS:
        return _LANE_FUNCTIONS[key]
    train, val = dataset(config)
    n_train = config["train"]["n_train"]
    kinds = layer_kinds(config)
    jit = functools.partial(jax.jit, compiler_options=_COMPILE)
    on_chip = jax.default_backend() != "cpu"  # the CPU cannot donate and would warn

    def back(h, p, dh, kinds):
        _, pull = jax.vjp(functools.partial(layer, kinds=kinds, config=config), h, p)
        return pull(dh)

    forward = {k: jit(functools.partial(layer, kinds=k, config=config)) for k in set(kinds)}
    backward = {k: jit(functools.partial(back, kinds=k)) for k in set(kinds)}
    head = jit(functools.partial(head_loss, config=config))
    head_grad = jit(jax.grad(functools.partial(head_loss, config=config), argnums=(0, 1, 2)))
    embed_grad = jit(lambda like, ids, dh: jnp.zeros_like(like).at[ids].add(dh))

    def updated(p, v, g, lr, momentum, wd):
        v = jax.tree.map(lambda vi, gi, pi: (momentum * vi + gi + wd * pi).astype(dtype),
                         v, g, p)
        return jax.tree.map(lambda pi, vi: (pi - lr * vi).astype(dtype), p, v), v

    # on the chip a leaf's old value and momentum make room for the new
    update = functools.partial(jit, donate_argnums=(0, 1) if on_chip else ())(updated)
    # the step whose change is read keeps the old value for ``change_of``,
    # whose result takes its place. **What a step changed is read from the
    # state as it is stored**, the arrays after the step less the arrays
    # before it, by a program of its own: taken inside the update's program
    # the difference showed no rounding of a bfloat16 state on the chip
    # (PR 50: steps of a few millionths of the weights read 0.005 to 0.05
    # where they read 1 on the CPU)
    update_keeping = functools.partial(jit, donate_argnums=(1,) if on_chip else ())(updated)
    change_of = functools.partial(jit, donate_argnums=(1,) if on_chip else ())(
        lambda new, old: jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old))

    @jit
    def init(init_scale):
        return init_params(config, jax.random.key(config["data_seed"] + 1), init_scale, dtype)

    def hidden(p, tokens):
        """The input of every layer, and the last one's output."""
        hs = [p["embed"][tokens[:-1]]]
        for i, kind in enumerate(kinds):
            hs.append(forward[kind](hs[-1], p["l%d" % i]))
        return hs

    def step(p, v, t, lr, momentum, wd, changed=None):
        tokens = train[t % n_train]
        hs = hidden(p, tokens)
        dh, g_norm, g_head = head_grad(hs[-1], p["norm_f"], p["head"], tokens)
        new_p, new_v = {}, {}

        def move(name, g):
            if changed is None:
                new_p[name], new_v[name] = update(p[name], v[name], g, lr, momentum, wd)
            else:
                new_p[name], new_v[name] = update_keeping(
                    p[name], v[name], g, lr, momentum, wd)
                changed[name] = change_of(new_p[name], p[name])

        for i in reversed(range(len(kinds))):
            dh, g_layer = backward[kinds[i]](hs[i], p["l%d" % i], dh)
            move("l%d" % i, g_layer)
        g_embed = embed_grad(p["embed"], tokens[:-1], dh)
        for name, g in (("embed", g_embed), ("norm_f", g_norm), ("head", g_head)):
            move(name, g)
        return new_p, new_v

    def held_out(p):
        return jnp.mean(jnp.stack([
            head(hidden(p, val[i])[-1], p["norm_f"], p["head"], val[i])
            for i in range(val.shape[0])]).astype(jnp.float32))

    def compile_ahead():
        """Every compiled function above, at the lane's shapes, with no
        work on the device."""
        t, d, rows = config["train"]["seq_len"], config["hidden_size"], config["vocab_size"]
        sds = lambda shape, kind=dtype: jax.ShapeDtypeStruct(shape, kind)
        h, scalar, tokens = sds((t, d)), sds((), jnp.float32), sds((t + 1,), jnp.int32)
        leaves = {"embed": sds((rows, d)), "norm_f": sds((d,)), "head": sds((d, rows))}
        init.lower(scalar).compile()
        trees = list(leaves.values())
        for k in set(kinds):
            p = {n: sds(shape) for n, shape in layer_shapes(config, *k).items()}
            forward[k].lower(h, p).compile()
            backward[k].lower(h, p, h).compile()
            trees.append(p)
        for tree in trees:
            update.lower(tree, tree, tree, scalar, scalar, scalar).compile()
            update_keeping.lower(tree, tree, tree, scalar, scalar, scalar).compile()
            change_of.lower(tree, tree).compile()
        head.lower(h, leaves["norm_f"], leaves["head"], tokens).compile()
        head_grad.lower(h, leaves["norm_f"], leaves["head"], tokens).compile()
        embed_grad.lower(leaves["embed"], sds((t,), jnp.int32), h).compile()
        if dtype == jnp.float32:
            params = jax.eval_shape(init, scalar)
            squares.lower(params, params).compile()

    _LANE_FUNCTIONS[key] = types.SimpleNamespace(
        init=init, step=step, held_out=held_out, compile_ahead=compile_ahead)
    return _LANE_FUNCTIONS[key]


def compile_ahead(config):
    """Compile the lane's functions without running them. Where the process
    keeps a compile cache on disk, the comparison that comes after the
    window finds them there: the benchmark's builder calls this beside the
    program's own, much longer compilation (``configs/laguna-xs2-sgd.py``)."""
    with jax.default_matmul_precision("highest"):
        lane_functions(config, jnp.float32).compile_ahead()


def reference_losses(config, hparams, marks, dtype=jnp.float32):
    """``f[len(marks)]``: the held-out loss after each mark of cumulative
    steps of the lane trained from ``hparams = (lr, momentum, weight_decay,
    init_scale)``. ``v <- m v + g + wd p; p <- p - lr v``; step ``t`` trains
    on sequence ``t mod n_train``. A loss that is no number (the training
    diverged) is infinity."""
    lr, momentum, wd, init_scale = (jnp.asarray(x, jnp.float32) for x in hparams)
    with jax.default_matmul_precision("highest"):
        lane = lane_functions(config, dtype)
        p, done, out = lane.init(init_scale), 0, []
        v = jax.tree.map(jnp.zeros_like, p)
        for mark in marks:
            for t in range(done, mark):
                p, v = lane.step(p, v, t, lr, momentum, wd)
            done = mark
            loss = float(lane.held_out(p))
            # a lane whose training diverged reports the worst loss
            out.append(np.inf if np.isnan(loss) else loss)
    return np.asarray(out, np.float64)


def first_step_change(config, hparams, dtype=jnp.float32):
    """What the first step of the lane of ``hparams`` changed, leaf by leaf:
    the parameters after it less the parameters at initialisation, float32,
    read from the state as it is stored."""
    lr, momentum, wd, init_scale = (jnp.asarray(x, jnp.float32) for x in hparams)
    changed = {}
    with jax.default_matmul_precision("highest"):
        lane = lane_functions(config, dtype)
        p = lane.init(init_scale)
        lane.step(p, jax.tree.map(jnp.zeros_like, p), 0, lr, momentum, wd, changed)
    return changed


# -------------------------------------------------------------- the change
#: a layer's leaves by the half that reads them; ``norm2`` goes with the
#: feed-forward of its layer
ATTENTION_LEAVES = ("norm1", "wq", "wk", "wv", "w_head_gate", "wo")
DENSE_LEAVES = ("ffn_gate", "ffn_up", "ffn_down")


def groups(config):
    """``{group: path -> whether the leaf is of the group}``: the attention
    mixers' leaves of both kinds of layer (projections, the gate, the norm
    before them), the dense feed-forward's and its norm, the expert layers'
    (router, shared expert, held experts, their norm), and embedding, final
    norm and head. Every leaf is of one group."""
    dense = {"l%d" % i for i, (_, mlp, _) in enumerate(layer_kinds(config)) if mlp == "dense"}
    layers = {"l%d" % i for i in range(config["num_hidden_layers"])}
    ffn = lambda path: path[0] in layers and path[-1] not in ATTENTION_LEAVES
    return {
        "attention": lambda path: path[0] in layers and path[-1] in ATTENTION_LEAVES,
        "dense_ffn": lambda path: ffn(path) and path[0] in dense,
        "experts": lambda path: ffn(path) and path[0] not in dense,
        "embed_head": lambda path: path[0] not in layers,
    }


def change_gaps(got, want, config):
    """``{group: |got - want| / |want|}`` of two changes of the parameters
    (trees of ``embed``, ``norm_f``, ``head`` and ``l<i>``), the norms over
    all the leaves of a group of :func:`groups`: 0 where the steps agree, 1
    where ``got`` did not move. A change that is no number anywhere reads
    infinity."""
    leaves = [([k.key for k in path], np.asarray(pair, np.float64))
              for path, pair in jax.tree_util.tree_leaves_with_path(squares(got, want))]
    gaps = {}
    for group, holds in groups(config).items():
        off, whole = np.sum([pair for path, pair in leaves if holds(path)], axis=0)
        value = np.sqrt(off / whole) if whole > 0 else np.inf
        gaps[group] = float(value) if np.isfinite(value) else np.inf
    return gaps


# ---------------------------------------------------------------- the limits
# All of it read on the chip at the published widths (PR 50, ``PERF.md``
# sections 2 and 6), through ``compare`` and ``control.py``.
#
#: the learning rate at which the small-step lane's first step is read, in
#: the program and in the reference alike, whatever the lane's own: a first
#: step is ``-lr (g + wd p)``, linear in the learning rate, so every rate
#: holds the same gradient to the reference. At this one float32's own
#: rounding of ``p - lr v`` is a thousandth of the step (at a lane's own
#: 2.4e-4 and an init scale of 0.8 it read 0.009 in ``embed_head``, nine times
#: the operands' rounding), and a bfloat16 state loses the step at every init
#: scale. A sweep's smallest learning rate cannot be counted on for that:
#: two sweeps in thirteen drew no regular lane under 0.6 (read there, at a
#: learning rate of 1, the control's ``attention`` is 0.10 and its
#: ``embed_head`` 0.025). Momentum and decay are the lane's, and its init
#: scale as far as :data:`STEP_INIT_SCALE_MOST`
STEP_LR = 1e-2
#: the ``ouro-sgd`` reference's ``REGULAR_INIT_SCALE``: beyond it a lane is
#: chaos at initialisation (at 3.0 bfloat16 operands alone put the program's
#: step 0.73 from the reference's) and no step is told from rounding. The
#: small-step lane is a regular one but in one sweep in a thousand, which
#: draws none: its step is then read at this init scale
STEP_INIT_SCALE_MOST = _steps.REGULAR_INIT_SCALE

#: ``|program's change - reference's| / |reference's change|`` after the
#: small-step lane's first step at :data:`STEP_LR` (:func:`change_gaps`), by
#: group. A state left unchanged reads 1 in every group. **The program, ten
#: sweeps on ten seeds and three lanes of a grid** (init scales 0.1 to 1.5):
#: ``attention`` 0.0010-0.0031 up to an init scale of 0.49, 0.0197 at 1.16 and
#: **0.085 at 1.5**; ``dense_ffn`` 0.0005-0.0059, 0.0250, **0.070**;
#: ``experts`` 0.0003-0.0093, 0.0287, **0.071**; ``embed_head``
#: 0.0001-0.0024, 0.0164, **0.036**: bfloat16 operands' rounding, which grows
#: with the init scale (the least readings on a lane whose decay of 9e-3 is
#: most of its step). **The control, bfloat16 parameters and momentum, three
#: seeds and three lanes of the grid** (init scales 0.1 to 1.5): ``attention``
#: 0.947-0.967, ``dense_ffn`` 0.924-0.995, ``experts`` 0.957-0.994: the step
#: is lost; ``embed_head`` **0.365** (init scale 0.1), 0.375, 0.461, 0.512,
#: 0.513, 0.716 (1.5): Zipf's frequent rows of the head, whose step is large
#: beside their weights, survive and carry most of the norm. Each limit lies
#: between the program's largest reading and the control's least, with the
#: more room above the reading since fresh seeds read higher: 3.5, 4.3 and
#: 4.2 times the program's at an init scale of 1.5 (fifteen, twelve and ten
#: times its largest below 1.2) and a third of the control's least in three
#: groups; 3.4 times and a third in ``embed_head``. **All four fail the
#: control on every seed and lane read**
CHANGE_GAP_LIMITS = {"attention": 0.3, "dense_ffn": 0.3, "experts": 0.3,
                     "embed_head": 0.12}
#: ``gap`` of every loss read: the limit of the accepted lane cells, a net
#: for a loss that is wrong outright or a number on one side only. The
#: program read 0 to 3.5e-3 over 23 sweeps (a top lane of init scale 1.81
#: after three steps; 1.4e-4 at most elsewhere), seventy times of room; the
#: control 1.0e-5 to 1.6e-2: decides nothing
LOSS_GAP_MAX_LIMIT = 0.25


def compare(config, traffic, records, seed, control=False):
    """``[(name, value, limit)]``, on the sweep of the window that the seed
    draws. With ``control`` the reference computed with bfloat16 parameters
    and momentum stands in the program's place, its losses for the reported
    ones and its first step for the program's (``lane_change`` of the
    record: ``(hyperparameters, steps) -> the parameters' change``)."""
    t0 = time.perf_counter()
    rec = records[np.random.default_rng(seed).integers(len(records))]
    who = "control" if control else "reported"
    lanes, loss_gap = sample_lanes(rec), 0.0
    for role, (hparams, reported) in lanes.items():
        marks = sorted(reported)
        want = reference_losses(config, hparams, marks)
        got = (reference_losses(config, hparams, marks, dtype=jnp.bfloat16) if control
               else [reported[m] for m in marks])
        for mark, g, w in zip(marks, got, want):
            print("laguna-xs2-sgd %s, %s lane: lr %.3g momentum %.3g wd %.3g init %.3g, "
                  "%d steps: %.6f against the reference's %.6f, gap %.3g"
                  % ((who, role) + tuple(hparams) + (mark, g, w, gap(g, w))))
            loss_gap = max(loss_gap, gap(g, w))
    # the small-step lane's first step at STEP_LR: the reference's (to the
    # host: see the module's last paragraph), then the one held against it
    _, momentum, wd, init_scale = lanes["small_step"][0]
    hparams = (STEP_LR, momentum, wd, min(init_scale, STEP_INIT_SCALE_MOST))
    fine = jax.device_get(first_step_change(config, hparams))
    stepped = (first_step_change(config, hparams, jnp.bfloat16) if control
               else rec["lane_change"](hparams, 1))
    change = change_gaps(stepped, fine, config)
    print("laguna-xs2-sgd %s, small_step lane at lr %.3g (momentum %.3g wd %.3g init %.3g): "
          "the first step's change against the reference's: %s"
          % (("control" if control else "program",) + hparams
             + (", ".join("%s %.4g" % item for item in change.items()),)))
    print("laguna-xs2-sgd reference: %.1f s" % (time.perf_counter() - t0))
    return ([("change_gap_" + group, change[group], CHANGE_GAP_LIMITS[group])
             for group in change] + [("loss_gap_max", float(loss_gap), LOSS_GAP_MAX_LIMIT)])
