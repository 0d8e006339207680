"""Plain reference of ``olmo-hybrid-sgd``: one chip's share of Olmo-Hybrid-7B
(``configs/olmo-hybrid-sgd.json``: one period, three Gated-DeltaNet layers to
one full-attention layer), its tokens, its loss, the loss's gradients and
momentum SGD, written from the layer equations in straightforward
``jax.numpy``. Imports nothing of the program and takes nothing it made:
tokens and initial weights come from the seed again. What a lane is made of
whatever its model (the draw of a leaf, the tokens, the norm, the SwiGLU, the
causal convolution, the gap) is the ``kimi-linear-sgd`` reference's, and how a
sweep's record is read lane by lane and the norms of a step's change the
``ouro-sgd`` reference's, both loaded from beside this file.

float32 under ``jax.default_matmul_precision("highest")``. **The delta rule is
the recurrence, step by step**: a ``lax.scan`` over positions of the state
``S`` f32[H, d_k, d_v], no chunks, no WY form, no solve. Attention is one full
masked softmax over the whole ``S x S`` square, head by head, no positions.
The gradient is ``jax.grad`` of the whole loss (:func:`loss_fn`) at the tests'
size, and at the published widths the same gradient by the chain rule, **a
layer at a time** (``jax.vjp`` of :func:`layer` and of :func:`head_loss`, a
layer stepped as soon as its gradient is known), so that it fits the chip
beside 3.7 GB of reference parameters and as much momentum. Each layer, each
head of attention and each block of ``RECURRENCE_BLOCK`` steps of the
recurrence recomputes its activations in the backward pass
(``jax.checkpoint``): that changes what is kept, not what is computed.

The equations (hidden size D, RMSNorm eps ``rms_norm_eps``, no bias but the
gate's ``dt_bias``, untied head):

* a layer on ``h`` f32[S, D], the norm after the sub-layer (OLMo 2's): ``h <-
  h + rmsnorm(Mixer(h); n1)``; ``h <- h + rmsnorm((silu(h W_g) * h W_u) W_d;
  n2)``;
* a ``linear_attention`` mixer, H heads of ``d_k`` and ``d_v``: ``q =
  l2norm(silu(conv(x W_q)))``, ``k`` alike, ``v = silu(conv(x W_v))``, ``conv``
  depthwise and causal over ``linear_conv_kernel_dim`` taps; ``g_t =
  -exp(A_log_h) softplus(x_t W_a + dt_bias_h)``, one number a head, ``a_t =
  exp(g_t)``; ``beta_t = 2 sigmoid(x_t W_b)`` (``linear_allow_neg_eigval``;
  ``sigmoid`` alone without it); ``S_t = (I - beta_t k_t k_t^T) a_t S_{t-1} +
  beta_t k_t v_t^T`` from ``S = 0``; ``o_t = S_t^T q_t / sqrt(d_k)``;
  ``(rmsnorm(o_t; o_norm f32[d_v]) * silu(x_t W_gate)) W_o``;
* a ``full_attention`` mixer: ``q = rmsnorm(x W_q; q_norm)``, ``k = rmsnorm(x
  W_k; k_norm)``, each over the projection's whole width, ``v = x W_v``, split
  into heads of ``D / num_attention_heads``; nothing is rotated; ``o =
  softmax(q k^T / sqrt(head_dim), causal) v``; ``o W_o``;
* ``logits = rmsnorm(h; n_f) W_head``; the loss the mean next-token
  cross-entropy over the vocabulary slice.

The comparison is the ``ouro-sgd`` reference's in what decides: from the
sweep of the window that the seed draws, the lane that reached the top rung is
retrained as far as its second rung (losses after 1 and 3 steps) and one other
lane as far as its first; **what that lane's first step changed**
(``lane_change`` of the record: the program's trainer, the parameters after
the step less the parameters at initialisation) is held against the
reference's own first step as the norm of the difference over the norm of the
reference's change, by group of leaves: the linear mixers', the attention
mixer's, the feed-forwards', embedding and head. A step that is lost reads 1
whatever the learning rate. **Which other lane** is this model's own choice
(:func:`sample_lanes`): a first step's change scales with the learning rate
and its reading does not depend on it (read on the chip: the same to four
digits at learning rates of 0.01 and 1), it depends on the init scale, so the
step is read on the lane whose init scale is nearest the one at which it
tells a float32 training state from a bfloat16 one best. The losses are a net
beside it. The readings are at the limits below. The reference's own change
goes to the host before the program's trainer runs: that trainer holds the
parameters at initialisation, the parameters after the step and their
difference beside the momentum (the comparison's steps peaked at 15.2 GB of
the chip's 16.9 so), and 3.7 GB more do not fit beside it.
"""

import functools
import importlib.util
import json
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
dataset, gap = _lane.dataset, _lane.gap
rmsnorm, swiglu, conv, l2norm = _lane.rmsnorm, _lane.swiglu, _lane.conv, _lane.l2norm
lanes_of, squares, TOP_LANE_RUNGS = _steps.lanes_of, _steps.squares, _steps.TOP_LANE_RUNGS

RECURRENCE_BLOCK = 64
KINDS = {"linear_attention": "gdn", "full_attention": "attention"}


# ------------------------------------------------------------- configuration
def layer_kinds(config):
    """The mixer of each layer held: ``layer_types`` lists them."""
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    assert config["rope_parameters"]["rope_theta"] is None    # no positions
    assert not config["attention_bias"] and not config["tie_word_embeddings"]
    assert config["linear_num_key_heads"] == config["linear_num_value_heads"]
    return [KINDS[kind] for kind in config["layer_types"]]


def head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def layer_shapes(config, mixer):
    d, f = config["hidden_size"], config["intermediate_size"]
    shapes = {"norm1": (d,), "norm2": (d,), "w_gate": (d, f), "w_up": (d, f),
              "w_down": (f, d)}
    if mixer == "gdn":
        h, taps = config["linear_num_key_heads"], config["linear_conv_kernel_dim"]
        wk, wv = h * config["linear_key_head_dim"], h * config["linear_value_head_dim"]
        shapes.update({
            "wq": (d, wk), "wk": (d, wk), "wv": (d, wv),
            "conv_q": (taps, wk), "conv_k": (taps, wk), "conv_v": (taps, wv),
            "wa": (d, h), "A_log": (h,), "dt_bias": (h,), "wb": (d, h),
            "wg": (d, wv), "o_norm": (config["linear_value_head_dim"],), "wo": (wv, d)})
    else:
        wq = config["num_attention_heads"] * head_dim(config)
        wkv = config["num_key_value_heads"] * head_dim(config)
        shapes.update({"wq": (d, wq), "wk": (d, wkv), "wv": (d, wkv), "wo": (wq, d),
                       "q_norm": (wq,), "k_norm": (wkv,)})
    return shapes


def init_leaf(key, name, shape, init_scale):
    """The lanes' draw of a leaf (``A_log`` the log of 1..16 over the heads,
    ``dt_bias`` the inverse softplus of 0.001..0.1 over the heads); the q/k
    norms' weights are one."""
    if name.rsplit("/", 1)[-1] in ("q_norm", "k_norm"):
        return jnp.ones(shape, jnp.float32)
    return _lane.init_leaf(key, name, shape, init_scale)


def init_params(config, key, init_scale, dtype=jnp.float32):
    d, rows = config["hidden_size"], config["vocab_size"]
    shapes = {"embed": (rows, d), "norm_f": (d,), "head": (d, rows)}
    params = {n: init_leaf(key, n, s, init_scale) for n, s in shapes.items()}
    for i, mixer in enumerate(layer_kinds(config)):
        params["l%d" % i] = {
            n: init_leaf(key, "l%d/%s" % (i, n), s, init_scale)
            for n, s in layer_shapes(config, mixer).items()}
    return jax.tree.map(lambda x: x.astype(dtype), params)


# -------------------------------------------------------------------- layers
def delta_rule(q, k, v, a, beta):
    """The recurrence, one position at a time: ``q, k`` [T, H, d_k], ``v``
    [T, H, d_v], ``a, beta`` [T, H] -> ``o`` [T, H, d_v]: ``S <- a_t S``;
    ``u = beta_t (v_t - S^T k_t)``; ``S <- S + k_t u^T``; ``o_t = S^T q_t``
    (which is ``S_t = (I - beta_t k_t k_t^T) a_t S_{t-1} + beta_t k_t
    v_t^T``)."""
    t, h, dk = q.shape

    def position(state, x):
        qt, kt, vt, at, bt = x
        state = at[:, None, None] * state
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", state, kt))
        state = state + kt[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(position, state, xs)

    pad = -t % RECURRENCE_BLOCK  # steps that leave the state alone
    xs = [jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
          for x, fill in ((q, 0), (k, 0), (v, 0), (a, 1), (beta, 0))]
    xs = tuple(x.reshape((-1, RECURRENCE_BLOCK) + x.shape[1:]) for x in xs)
    _, out = jax.lax.scan(block, jnp.zeros((h, dk, v.shape[-1]), q.dtype), xs)
    return out.reshape((t + pad, h, -1))[:t]


def gated_delta_net(x, p, config):
    t, h = x.shape[0], config["linear_num_key_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    q = l2norm(jax.nn.silu(conv(x @ p["wq"], p["conv_q"])).reshape(t, h, dk))
    k = l2norm(jax.nn.silu(conv(x @ p["wk"], p["conv_k"])).reshape(t, h, dk))
    v = jax.nn.silu(conv(x @ p["wv"], p["conv_v"])).reshape(t, h, dv)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(x @ p["wa"] + p["dt_bias"])   # [T, H]
    beta = jax.nn.sigmoid(x @ p["wb"])
    if config["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    o = delta_rule(q, k, v, jnp.exp(g), beta) / dk ** 0.5
    o = rmsnorm(o, p["o_norm"], config["rms_norm_eps"]) * jax.nn.silu(
        (x @ p["wg"]).reshape(t, h, dv))
    return o.reshape(t, h * dv) @ p["wo"]


def attention(x, p, config):
    t, dh, eps = x.shape[0], head_dim(config), config["rms_norm_eps"]
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    q = rmsnorm(x @ p["wq"], p["q_norm"], eps).reshape(t, hq, dh)
    k = rmsnorm(x @ p["wk"], p["k_norm"], eps).reshape(t, hk, dh)
    v = (x @ p["wv"]).reshape(t, hk, dh)
    k, v = (jnp.repeat(y, hq // hk, axis=1) for y in (k, v))
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    @jax.checkpoint
    def head(qh, kh, vh):
        scores = (qh @ kh.T / dh ** 0.5).astype(jnp.float32)
        return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(x.dtype) @ vh

    out = jax.lax.map(lambda a: head(*a), tuple(y.swapaxes(0, 1) for y in (q, k, v)))
    return out.swapaxes(0, 1).reshape(t, hq * dh) @ p["wo"]


def layer(h, p, mixer, config):
    eps = config["rms_norm_eps"]
    mixed = (gated_delta_net if mixer == "gdn" else attention)(h, p, config)
    h = h + rmsnorm(mixed, p["norm1"], eps)
    return h + rmsnorm(swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), p["norm2"], eps)


def head_loss(h, norm_f, head, tokens, config):
    """Final norm, head, mean cross-entropy of ``tokens[1:]``."""
    logits = (rmsnorm(h, norm_f, config["rms_norm_eps"]) @ head).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


def loss_fn(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens`` i32[S + 1]; for
    ``jax.grad``, whole."""
    h = params["embed"][tokens[:-1]]
    for i, mixer in enumerate(layer_kinds(config)):
        h = jax.checkpoint(functools.partial(layer, mixer=mixer, config=config))(
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
    (``jax.vjp`` of the same :func:`layer` and :func:`head_loss`, each layer's
    input kept and its inside recomputed), and a layer's parameters and
    momentum are updated as soon as its gradient is known: layers of one kind
    share one compiled function, and the lane's old state makes room for the
    new a layer at a time. ``changed``, a dictionary, is filled leaf by leaf
    with what the step changed, the parameters after it less the parameters
    before, float32."""
    key = (json.dumps(config, sort_keys=True), jnp.dtype(dtype).name)
    if key in _LANE_FUNCTIONS:
        return _LANE_FUNCTIONS[key]
    train, val = dataset(config)
    n_train = config["train"]["n_train"]
    kinds = layer_kinds(config)
    jit = functools.partial(jax.jit, compiler_options=_COMPILE)
    on_chip = jax.default_backend() != "cpu"  # the CPU cannot donate and would warn

    def back(h, p, dh, mixer):
        _, pull = jax.vjp(functools.partial(layer, mixer=mixer, config=config), h, p)
        return pull(dh)

    forward = {k: jit(functools.partial(layer, mixer=k, config=config)) for k in set(kinds)}
    backward = {k: jit(functools.partial(back, mixer=k)) for k in set(kinds)}
    head = jit(functools.partial(head_loss, config=config))
    head_grad = jit(jax.grad(functools.partial(head_loss, config=config), argnums=(0, 1, 2)))
    embed_grad = jit(lambda like, ids, dh: jnp.zeros_like(like).at[ids].add(dh))

    def updated(p, v, g, lr, momentum, wd):
        v = jax.tree.map(lambda vi, gi, pi: (momentum * vi + gi + wd * pi).astype(dtype),
                         v, g, p)
        return jax.tree.map(lambda pi, vi: (pi - lr * vi).astype(dtype), p, v), v

    # on the chip a leaf's old value and momentum make room for the new
    donating = functools.partial(jit, donate_argnums=(0, 1) if on_chip else ())
    update = donating(updated)

    @donating
    def update_and_change(p, v, g, lr, momentum, wd):
        new_p, new_v = updated(p, v, g, lr, momentum, wd)
        return new_p, new_v, jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new_p, p)

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
                new_p[name], new_v[name], changed[name] = update_and_change(
                    p[name], v[name], g, lr, momentum, wd)

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
            p = {n: sds(shape) for n, shape in layer_shapes(config, k).items()}
            forward[k].lower(h, p).compile()
            backward[k].lower(h, p, h).compile()
            trees.append(p)
        for tree in trees:
            update.lower(tree, tree, tree, scalar, scalar, scalar).compile()
            update_and_change.lower(tree, tree, tree, scalar, scalar, scalar).compile()
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
    program's own, much longer compilation (``configs/olmo-hybrid-sgd.py``)."""
    with jax.default_matmul_precision("highest"):
        lane_functions(config, jnp.float32).compile_ahead()


def reference_losses(config, hparams, marks, dtype=jnp.float32, first_step=None):
    """``f[len(marks)]``: the held-out loss after each mark of cumulative
    steps of the lane trained from ``hparams = (lr, momentum, weight_decay,
    init_scale)``. ``v <- m v + g + wd p; p <- p - lr v``; step ``t`` trains
    on sequence ``t mod n_train``. A loss that is no number (the training
    diverged) is infinity. ``first_step(change)`` is handed what the first
    step changed: the parameters after it less the parameters at
    initialisation, float32."""
    lr, momentum, wd, init_scale = (jnp.asarray(x, jnp.float32) for x in hparams)
    with jax.default_matmul_precision("highest"):
        lane = lane_functions(config, dtype)
        p, done, out = lane.init(init_scale), 0, []
        v = jax.tree.map(jnp.zeros_like, p)
        for mark in marks:
            for t in range(done, mark):
                changed = {} if t == 0 and first_step is not None else None
                p, v = lane.step(p, v, t, lr, momentum, wd, changed)
                if changed is not None:
                    first_step(changed)
            done = mark
            loss = float(lane.held_out(p))
            # a lane whose training diverged reports the worst loss
            out.append(np.inf if np.isnan(loss) else loss)
    return np.asarray(out, np.float64)


# -------------------------------------------------------------- the change
#: a layer's leaves that its feed-forward half reads; the others are its mixer's
FFN_LEAVES = ("w_gate", "w_up", "w_down", "norm2")


def groups(config):
    """``{group: path -> whether the leaf is of the group}``: the linear
    mixers' leaves (projections, taps, the gate's ``wa``, ``A_log``,
    ``dt_bias``, ``wb``, the output gate, the head norm, ``wo`` and the norm
    after the mixer), the attention mixer's, the feed-forwards' and their
    norms, and embedding, final norm and head. Every leaf is of one group."""
    kind_of = {"l%d" % i: kind for i, kind in enumerate(layer_kinds(config))}
    mixer = lambda kind: lambda path: (
        kind_of.get(path[0]) == kind and path[-1] not in FFN_LEAVES)
    return {
        "gdn": mixer("gdn"),
        "attention": mixer("attention"),
        "ffn": lambda path: path[0] in kind_of and path[-1] in FFN_LEAVES,
        "embed_head": lambda path: path[0] not in kind_of,
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
# All of it read on the chip at the published widths (PR 46, ``PERF.md``
# section 2), through ``compare`` and ``_only/readings46.py``: 14 sweeps on 11
# seeds by the program, 3 of the seeds by the control too (bfloat16 parameters
# and momentum, and with them the activations), and 21 lanes chosen over the
# space by both. A lane's weights and tokens are the configuration's and a
# first step's change scales with the learning rate, so a reading is a
# function of the lane's init scale (and, for embedding and head, of its
# decay) alone: the same to four digits at learning rates of 0.01 and 1.
#
# No layer here has a norm before its mixer: the mixers read the stream as it
# is, and each sub-layer's output goes through a norm whose eps (1e-6) is of
# the size of a small-init mixer's output. Under an init scale of about 0.13
# the norms hardly normalise and nothing tells the states apart but the linear
# mixers (the control's ``gdn`` 0.11-0.17, its other groups 0.012-0.024, where
# the program reads 0.002-0.010); from 0.8 on a weight's gradient is so large
# beside the weight that bfloat16 state moves the step little more than
# bfloat16 operands do (the control's ``gdn`` 0.081 at 0.8, 0.049 at 1.0 and
# 0.017-0.027 at 1.5; the program's 0.034, 0.027 and 0.011-0.020). Between
# them the control stands clear of the program in every group, thirteen times
# at 0.16-0.19 (``gdn`` 0.65-0.75 against 0.051-0.056).

#: the init scale at which the first step's change tells float32 state from
#: bfloat16 state best: the lane of a sweep whose step is read is the one
#: nearest it (:func:`sample_lanes`)
TELLING_INIT_SCALE = 0.18

#: ``|program's change - reference's| / |reference's change|`` after the
#: chosen lane's first step (:func:`change_gaps`), by group. A state left
#: unchanged reads 1 in every group. **The program over the whole space, its
#: largest readings** (all at init scales of 0.16-0.18, where the norms start
#: to normalise): ``gdn`` 0.0558, ``attention`` 0.0394, ``ffn`` 0.0551,
#: ``embed_head`` 0.0343. **The control**: ``gdn`` over 0.12 at every init scale
#: from 0.1 to 0.65 (0.167 at 0.1, 0.26 at 0.13, 0.39 at 0.14, 0.75 at 0.16,
#: 0.52 at 0.22, 0.27 at 0.32, 0.18 at 0.41, 0.144 at 0.6), ``ffn`` from 0.135
#: to 0.65 (within 4 % of ``gdn`` there), ``attention`` over 0.08 from 0.135 to
#: 0.42 (0.136 at 0.14, 0.48 at 0.16, 0.0875 at 0.41), ``embed_head`` over 0.07
#: from 0.135 to 0.45 where the decay is small (0.068 at the largest decay,
#: 0.01: the embedding's unread rows change by their decay alone, alike on
#: both sides). Each limit has twice its program's largest reading of room
#: under it (fresh seeds read higher) and lies under the control's readings
#: wherever a sweep's chosen lane falls on 98 sweeps of 100: of eight lanes
#: drawn log-uniformly over 0.1-10 one lies in 0.1-0.65 unless none does,
#: 0.594^8. ``gdn`` and ``ffn`` decide; the other two are tighter nets for
#: their own groups of leaves
CHANGE_GAP_LIMITS = {"gdn": 0.12, "attention": 0.08, "ffn": 0.12, "embed_head": 0.07}
#: ``gap`` of every loss read (the limit of the accepted lane cells; the
#: program 1.1e-6 to 7.2e-4 and once 0.023, a top lane on its way up; the
#: control 1.2e-3 to 3.1e-2: decides nothing): a net for a loss that is a
#: number on one side only
LOSS_GAP_MAX_LIMIT = 0.25


def sample_lanes(rec):
    """``{role: (hyperparameters, {steps: reported loss})}`` of a sweep.
    ``top``: the lane that reached the top rung, with its loss at its first
    ``TOP_LANE_RUNGS`` rungs. ``step``: of the other lanes (promoted once or
    not) the one whose init scale is nearest :data:`TELLING_INIT_SCALE`, by
    ratio, with its loss at the first rung: the lane whose first step is
    read. Every sweep has both: no reading is left out."""
    lanes = lanes_of(rec)
    top = max(lanes, key=lambda lane: len(lane[1]))
    step = min((lane for lane in lanes if lane is not top),
               key=lambda lane: abs(np.log(lane[0]["init_scale"] / TELLING_INIT_SCALE)))
    picked = {"top": (top, TOP_LANE_RUNGS), "step": (step, 1)}
    return {role: ([hparams[n] for n in HPARAMS],
                   {steps: reported[steps] for steps in sorted(reported)[:rungs]})
            for role, ((hparams, reported), rungs) in picked.items()}


def compare(config, traffic, records, seed, control=False):
    """``[(name, value, limit)]``, on the sweep of the window that the seed
    draws. With ``control`` the reference computed with bfloat16 parameters
    and momentum stands in the program's place, its losses for the reported
    ones and its first step for the program's (``lane_change`` of the
    record: ``(hyperparameters, steps) -> the parameters' change``)."""
    t0 = time.perf_counter()
    rec = records[np.random.default_rng(seed).integers(len(records))]
    loss_gap, change = 0.0, {}
    for role, (hparams, reported) in sample_lanes(rec).items():
        marks, steps = sorted(reported), []
        # the chosen lane's step: the reference's first (kept on the host:
        # see the module's last paragraph), then the one held against it
        keep = None
        if role == "step":
            keep = lambda tree: steps.append(tree if steps else jax.device_get(tree))
        want = reference_losses(config, hparams, marks, first_step=keep)
        if control:
            got = reference_losses(config, hparams, marks, dtype=jnp.bfloat16, first_step=keep)
        else:
            got = [reported[m] for m in marks]
            if keep:
                keep(rec["lane_change"](hparams, 1))
        if steps:
            fine, stepped = steps
            change = change_gaps(stepped, fine, config)
        for mark, g, w in zip(marks, got, want):
            print("olmo-hybrid-sgd %s, %s lane: lr %.3g momentum %.3g wd %.3g init %.3g, "
                  "%d steps: %.6f against the reference's %.6f, gap %.3g"
                  % (("control" if control else "reported", role) + tuple(hparams)
                     + (mark, g, w, gap(g, w))))
            loss_gap = max(loss_gap, gap(g, w))
    print("olmo-hybrid-sgd %s, step lane: the first step's change against the "
          "reference's: %s" % ("control" if control else "program",
                               ", ".join("%s %.4g" % item for item in change.items())))
    print("olmo-hybrid-sgd reference: %.1f s" % (time.perf_counter() - t0))
    return ([("change_gap_" + group, change[group], CHANGE_GAP_LIMITS[group])
             for group in change] + [("loss_gap_max", float(loss_gap), LOSS_GAP_MAX_LIMIT)])
