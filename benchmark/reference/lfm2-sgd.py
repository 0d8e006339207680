"""Plain reference of ``lfm2-sgd``: one chip's share of LFM2-8B-A1B
(``configs/lfm2-sgd.json``), its tokens, its loss, the loss's gradients and
momentum SGD, written from the layer equations in straightforward
``jax.numpy``. Imports nothing of the program and takes nothing it made:
tokens and initial weights come from the seed again. What a lane is made of
whatever its model (the draw of a leaf, the tokens, the norm, the SwiGLU, the
gap) is the ``kimi-linear-sgd`` reference's, and which lanes of a sweep are
retrained and the norms of a step's change the ``ouro-sgd`` reference's, both
loaded from beside this file.

float32 under ``jax.default_matmul_precision("highest")``. The convolution is
three shifted sums; attention one full masked softmax over the whole ``S x
S`` square, head by head, each head of the queries and of the keys through
its RMSNorm first, the key/value heads repeated outright; the router a
``top_k`` and the experts a loop over the held ones with a mask: no blocks,
no sorting, no grouped product. **The head is written as ``E.T`` of the one
embedding matrix, and the gradient is ``jax.grad`` of the whole loss**
(:func:`loss_fn`), at the tests' size and at the published widths alike: the
differentiation itself adds what the lookup and the head give the matrix,
and shares nothing with a trainer that keeps one of the two and adds the
other. Each layer and each head of attention recomputes its activations in
the backward pass (``jax.checkpoint``): that changes what is kept, not what
is computed.

The equations (hidden size D, RMSNorm eps ``norm_eps``, no bias anywhere):

* a layer on ``h`` f32[S, D]: ``h <- h + Mixer(rmsnorm(h; n1))``; ``h <- h +
  FFN(rmsnorm(h; n2))``;
* a ``conv`` mixer on ``a``: ``B, C, x`` = the three D-wide thirds of ``a
  W_in``, in that order; ``z = B * x``; ``c_t = sum_{i<K} w[i] z_{t-K+1+i}``,
  zeros before the sequence (depthwise, causal, no activation); ``(C * c)
  W_out``;
* a ``full_attention`` mixer: ``q, k, v = a W_q, a W_k, a W_v`` as [S, 32 |
  8 | 8, 64]; every head of ``q`` and of ``k`` through ``rmsnorm(.; q_norm |
  k_norm)`` over its 64 channels; plain RoPE in the rotate-half form over
  the whole head (``inv_freq_i = theta^(-2i / 64)``, positions 0..S-1); ``o
  = softmax(q k^T / sqrt(64), causal) v``, query head ``a`` on key/value
  head ``a // 4``; ``o W_o``;
* the FFN of a leading dense layer: ``(silu(b W_g) * b W_u) W_d``; of the
  others: ``s = sigmoid(b W_r)`` (float32 operands); the top 4 of ``s +
  e_bias``; weights ``s_e / (sum of the chosen s + router_epsilon) *
  routed_scaling_factor`` (the bias chooses and does not weigh); this chip
  adds ``w_e W_d,e (silu(b W_g,e) * b W_u,e)`` for chosen experts it holds;
* ``logits = rmsnorm(h; n_f) E^T`` with ``E`` the embedding; the loss the
  mean next-token cross-entropy over the vocabulary slice.

The comparison is the ``ouro-sgd`` reference's in its lanes and in what
decides: from the sweep of the window that the seed draws, the lane that
reached the top rung is retrained as far as its second rung (losses after 1
and 3 steps) and one other lane of a regular init scale, the one of the
smallest learning rate, as far as its first; **what that lane's first step
changed** (``lane_change`` of the record: the program's trainer, the
parameters after the step less the parameters at initialisation) is held
against the reference's own first step as the norm of the difference over
the norm of the reference's change: over all the leaves, over the tied
matrix, over the expert layers' and over the convolution mixers'. A step
that is lost reads 1 whatever the learning rate; so does a training state
kept in bfloat16 where the step is small beside the weights. The losses are
a net beside it. The readings are at the limits below.
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
rmsnorm, swiglu = _lane.rmsnorm, _lane.swiglu
sample_lanes, squares = _steps.sample_lanes, _steps.squares

KINDS = {"conv": "conv", "full_attention": "attention"}


# ------------------------------------------------------------- configuration
def layer_kinds(config):
    """``[(mixer, ffn)]`` of the layers held: ``layer_types`` lists them, the
    first ``num_dense_layers`` of them feed forward through the dense SwiGLU."""
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    assert not config["conv_bias"] and config["norm_topk_prob"] and config["use_expert_bias"]
    return [(KINDS[kind], "dense" if i < config["num_dense_layers"] else "moe")
            for i, kind in enumerate(config["layer_types"])]


def head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def layer_shapes(config, mixer, ffn):
    d, dh = config["hidden_size"], head_dim(config)
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    shapes = {"norm1": (d,), "norm2": (d,)}
    if mixer == "conv":
        shapes.update({"w_in": (d, 3 * d), "conv": (config["conv_L_cache"], d),
                       "w_out": (d, d)})
    else:
        shapes.update({"wq": (d, hq * dh), "wk": (d, hk * dh), "wv": (d, hk * dh),
                       "wo": (hq * dh, d), "q_norm": (dh,), "k_norm": (dh,)})
    if ffn == "dense":
        f = config["intermediate_size"]
        shapes.update({"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)})
    else:
        f, held = config["moe_intermediate_size"], len(config["cut"]["experts_held"])
        outputs = config["cut"]["router_outputs"]
        shapes.update({"router": (d, outputs), "router_bias": (outputs,),
                       "e_gate": (held, d, f), "e_up": (held, d, f),
                       "e_down": (held, f, d)})
    return shapes


def init_leaf(key, name, shape, init_scale):
    """The lanes' draw of a leaf; the per-head norms' weights are one."""
    if name.rsplit("/", 1)[-1] in ("q_norm", "k_norm"):
        return jnp.ones(shape, jnp.float32)
    return _lane.init_leaf(key, name, shape, init_scale)


def init_params(config, key, init_scale, dtype=jnp.float32):
    """``embed`` (the head too: drawn as the head it is, ``init_scale /
    sqrt(D) * N(0, 1)``), ``norm_f`` and ``l<i>``; no leaf ``head``."""
    d, rows = config["hidden_size"], config["vocab_size"]
    params = {"embed": init_leaf(key, "embed", (rows, d), init_scale * d ** -0.5),
              "norm_f": init_leaf(key, "norm_f", (d,), init_scale)}
    for i, kind in enumerate(layer_kinds(config)):
        params["l%d" % i] = {
            n: init_leaf(key, "l%d/%s" % (i, n), s, init_scale)
            for n, s in layer_shapes(config, *kind).items()}
    return jax.tree.map(lambda x: x.astype(dtype), params)


# -------------------------------------------------------------------- layers
def short_conv(x, p):
    """The gated short convolution: three shifted sums between two gates."""
    d = x.shape[1]
    u = x @ p["w_in"]
    b, c, y = u[:, :d], u[:, d:2 * d], u[:, 2 * d:]
    z = b * y
    taps = p["conv"].shape[0]
    # tap i weighs the position K - 1 - i back
    shifted = lambda back: jnp.concatenate(
        [jnp.zeros((back, d), z.dtype), z[:z.shape[0] - back]]) if back else z
    mixed = sum(p["conv"][i] * shifted(taps - 1 - i) for i in range(taps))
    return (c * mixed) @ p["w_out"]


def rotary(config, t):
    """``(cos, sin)`` f32[T, head_dim]: plain RoPE."""
    dim = head_dim(config)
    inv_freq = config["rope_theta"] ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32)[None, :])
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def rope(x, cos, sin):
    """``x`` [T, H, d]: channel ``i`` turns with ``i + d / 2``."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def attention(x, p, config):
    t, dh, eps = x.shape[0], head_dim(config), config["norm_eps"]
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    cos, sin = (table.astype(x.dtype) for table in rotary(config, t))
    q = rope(rmsnorm((x @ p["wq"]).reshape(t, hq, dh), p["q_norm"], eps), cos, sin)
    k = rope(rmsnorm((x @ p["wk"]).reshape(t, hk, dh), p["k_norm"], eps), cos, sin)
    v = (x @ p["wv"]).reshape(t, hk, dh)
    # query head a on key/value head a // (hq / hk): repeated outright
    k, v = (jnp.repeat(y, hq // hk, axis=1) for y in (k, v))
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    @jax.checkpoint
    def head(qh, kh, vh):
        scores = (qh @ kh.T / dh ** 0.5).astype(jnp.float32)
        return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(x.dtype) @ vh

    out = jax.lax.map(lambda a: head(*a), tuple(y.swapaxes(0, 1) for y in (q, k, v)))
    return out.swapaxes(0, 1).reshape(t, hq * dh) @ p["wo"]


def router_weights(x, p, config):
    """``(chosen i32[T, k], weight f32[T, k])``: the top k of ``s + bias``,
    weighed by ``s`` alone."""
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32),
                              config["num_experts_per_tok"])
    s_chosen = jnp.take_along_axis(s, chosen, axis=1)
    weight = s_chosen / (s_chosen.sum(-1, keepdims=True) + config["router_epsilon"])
    return chosen, weight * config["routed_scaling_factor"]


def experts(x, p, config, held=None):
    """The share of the expert layer that holds ``held`` (global expert
    ids, in the order of the leaves' leading axis); default the
    configuration's. The held experts one after the other, each over every
    token with its weight or zero."""
    held = config["cut"]["experts_held"] if held is None else held
    chosen, weight = router_weights(x, p, config)
    ids = jnp.asarray(held, chosen.dtype)[:, None, None]
    w = jnp.where(chosen[None] == ids, weight[None], 0.0).sum(-1).astype(x.dtype)  # [held, T]

    def add_expert(y, e):
        w_e, gate, up, down = e
        return y + w_e[:, None] * swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (w, p["e_gate"], p["e_up"], p["e_down"]))
    return y


def layer(h, p, mixer, ffn, config):
    eps = config["norm_eps"]
    a = rmsnorm(h, p["norm1"], eps)
    h = h + (short_conv(a, p) if mixer == "conv" else attention(a, p, config))
    b = rmsnorm(h, p["norm2"], eps)
    if ffn == "dense":
        return h + swiglu(b, p["w_gate"], p["w_up"], p["w_down"])
    return h + experts(b, p, config)


def hidden(params, tokens, config):
    """The last layer's output."""
    h = params["embed"][tokens[:-1]]
    for i, (mixer, ffn) in enumerate(layer_kinds(config)):
        h = jax.checkpoint(functools.partial(layer, mixer=mixer, ffn=ffn, config=config))(
            h, params["l%d" % i])
    return h


def loss_fn(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens`` i32[S + 1]; the head is
    the embedding, transposed. For ``jax.grad``, whole."""
    h = rmsnorm(hidden(params, tokens, config), params["norm_f"], config["norm_eps"])
    logp = jax.nn.log_softmax((h @ params["embed"].T).astype(jnp.float32))
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


# ------------------------------------------------------------------ training
_LANE_FUNCTIONS = {}
#: the check compiles beside the program it checks: quickly, not for speed
_COMPILE = {"exec_time_optimization_effort": -1.0}


def lane_functions(config, dtype):
    """A lane's functions, made once per configuration and precision:
    ``init(init_scale) -> p``, ``step(p, v, t, lr, momentum, wd) -> (p,
    v)``, ``held_out(p) -> loss``, ``change_of(p, p0) -> p - p0`` in float32
    (``p0`` is given up), ``compile_ahead()``. A step is ``jax.grad`` of
    :func:`loss_fn` over the whole tree of parameters and the update of
    every leaf, one compiled function."""
    key = (json.dumps(config, sort_keys=True), jnp.dtype(dtype).name)
    if key in _LANE_FUNCTIONS:
        return _LANE_FUNCTIONS[key]
    train, val = dataset(config)
    n_train = config["train"]["n_train"]
    jit = functools.partial(jax.jit, compiler_options=_COMPILE)
    on_chip = jax.default_backend() != "cpu"  # the CPU cannot donate and would warn

    @functools.partial(jit, donate_argnums=(0, 1) if on_chip else ())
    def one_step(p, v, tokens, lr, momentum, wd):
        g = jax.grad(functools.partial(loss_fn, config=config))(p, tokens)
        v = jax.tree.map(lambda vi, gi, pi: (momentum * vi + gi + wd * pi).astype(dtype),
                         v, g, p)
        return jax.tree.map(lambda pi, vi: (pi - lr * vi).astype(dtype), p, v), v

    loss = jit(functools.partial(loss_fn, config=config))
    change_of = functools.partial(jit, donate_argnums=(1,) if on_chip else ())(
        lambda p, p0: jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))

    @jit
    def init(init_scale):
        return init_params(config, jax.random.key(config["data_seed"] + 1), init_scale, dtype)

    def step(p, v, t, lr, momentum, wd):
        return one_step(p, v, train[t % n_train], lr, momentum, wd)

    def held_out(p):
        return jnp.mean(jnp.stack(
            [loss(p, val[i]) for i in range(val.shape[0])]).astype(jnp.float32))

    def compile_ahead():
        """Every compiled function above, at the lane's shapes, with no
        work on the device."""
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        tokens = jax.ShapeDtypeStruct((config["train"]["seq_len"] + 1,), jnp.int32)
        params = jax.eval_shape(init, scalar)
        init.lower(scalar).compile()
        one_step.lower(params, params, tokens, scalar, scalar, scalar).compile()
        loss.lower(params, tokens).compile()
        change_of.lower(params, params).compile()
        if dtype == jnp.float32:
            squares.lower(params, params).compile()

    _LANE_FUNCTIONS[key] = types.SimpleNamespace(
        init=init, step=step, held_out=held_out, change_of=change_of,
        compile_ahead=compile_ahead)
    return _LANE_FUNCTIONS[key]


def compile_ahead(config):
    """Compile the lane's functions without running them. Where the process
    keeps a compile cache on disk, the comparison that comes after the
    window finds them there: the benchmark's builder calls this beside the
    program's own, much longer compilation (``configs/lfm2-sgd.py``)."""
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
                p, v = lane.step(p, v, t, lr, momentum, wd)
                if t == 0 and first_step is not None:
                    first_step(lane.change_of(p, lane.init(init_scale)))
            done = mark
            loss = float(lane.held_out(p))
            # a lane whose training diverged reports the worst loss
            out.append(np.inf if np.isnan(loss) else loss)
    return np.asarray(out, np.float64)


# -------------------------------------------------------------- the change
#: the leaves a reading is taken over: every one; the tied matrix alone,
#: whose gradient is the sum of the lookup's and the head's; the expert
#: layers' alone (the router, which only the weights reach, and the held
#: experts); the convolution mixers' alone
GROUPS = {
    "all": lambda path: True,
    "embed": lambda path: path[0] == "embed",
    "experts": lambda path: path[-1] in ("router", "e_gate", "e_up", "e_down"),
    "conv": lambda path: path[-1] in ("w_in", "conv", "w_out"),
}


def change_gaps(got, want):
    """``{group: |got - want| / |want|}`` of two changes of the parameters
    (trees of ``embed``, ``norm_f`` and ``l<i>``), the norms over all the
    leaves of a group of :data:`GROUPS`: 0 where the steps agree, 1 where
    ``got`` did not move. A change that is no number anywhere reads
    infinity."""
    leaves = [([k.key for k in path], np.asarray(pair, np.float64))
              for path, pair in jax.tree_util.tree_leaves_with_path(squares(got, want))]
    gaps = {}
    for group, holds in GROUPS.items():
        off, whole = np.sum([pair for path, pair in leaves if holds(path)], axis=0)
        value = np.sqrt(off / whole) if whole > 0 else np.inf
        gaps[group] = float(value) if np.isfinite(value) else np.inf
    return gaps


# ---------------------------------------------------------------- the limits
# All of it read on the chip at the published widths (PR 40, ``PERF.md``
# section 2), through ``compare``: 24 sweeps on 24 seeds by the program, 16
# of them by the control too (bfloat16 parameters and momentum, and with them
# the activations), 3 by three planted faults (the tied matrix stepped by the
# lookup's gradient alone; the taps one position late; the chosen experts
# weighed by ``s + bias`` with no epsilon, a bias planted on both sides), and
# eleven lanes chosen at the corners that the seeds did not draw (init scales
# of 0.1 and 1.1 to 1.5, learning rates of 1e-4 to 1). The limits were set
# on the first 16 seeds and the chosen lanes. A lane's weights and tokens are
# the configuration's, so a reading is a function of the lane's learning
# rate, decay and init scale alone.

#: ``|program's change - reference's| / |reference's change|`` after the
#: small-step lane's first step (:func:`change_gaps`). ``all``: the program
#: 0.0032 to 0.064 (0.003-0.008 at init scales to 0.3, 0.016-0.064 from 0.39
#: to 1.5: the rounding of bfloat16 operands through five layers), a state
#: left unchanged 1, the control 0.69 and more on 12 seeds of 16 (it loses
#: the step) and 0.024 to 0.31 where the least learning rate a sweep drew for
#: a regular lane was 0.012 to 0.64: the limit lies between the reading and
#: 1 with the more room above the reading. ``embed``: the program 0.0030 to
#: 0.061, the lookup's gradient alone 0.256 to 0.310 on every lane read (the
#: head's half is a quarter to a third of the matrix's step). ``experts``:
#: the program 0.026 to 0.187 on the seeds and 0.253 at the corner of the
#: space (lr 1e-4 at init scale 1.5: a choice that bfloat16 operands
#: upstream flip sends a token's gradient to another expert, and at the
#: least learning rate an expert's step is a few float32 units of its
#: weights, on both sides); the control 0.81 and more on 15 seeds of 16, and
#: 0.314, 0.47 and 0.52 at lanes of lr 0.64, 0.3 and 1.0, whose step
#: bfloat16 state does not lose. ``conv``: the program 0.0093 to 0.065 and
#: 0.078 at the corner of lr 1e-4 and init scale 0.1, the taps one position
#: late 1.42, the control 0.146 to 1.0 (0.264 at the lr 0.64 lane, where it
#: is what fails it). The third fault reads 2.4 to 2.9 times the sound
#: trainer's reading under the same planted bias (all 0.106-0.111 against
#: 0.039-0.046; experts 0.152-0.331 against 0.061-0.170): reported, and under
#: these limits on two lanes of three
CHANGE_GAP_LIMITS = {"all": 0.3, "embed": 0.15, "experts": 0.4, "conv": 0.15}
#: ``gap`` of every loss read (the limit of the accepted lane cells; the
#: program 4.1e-6 to 5.2e-4, the control 5.0e-4 to 2.1e-2): a net for a loss
#: that is a number on one side only
LOSS_GAP_MAX_LIMIT = 0.25


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
        # the step is read where the init scale is regular: the reference's
        # first, then the one held against it
        keep = steps.append if role == "small_step" else None
        want = reference_losses(config, hparams, marks, first_step=keep)
        if control:
            got = reference_losses(config, hparams, marks, dtype=jnp.bfloat16, first_step=keep)
        else:
            got = [reported[m] for m in marks]
            if keep:
                keep(rec["lane_change"](hparams, 1))
        if steps:
            fine, stepped = steps
            change = change_gaps(stepped, fine)
        for mark, g, w in zip(marks, got, want):
            print("lfm2-sgd %s, %s lane: lr %.3g momentum %.3g wd %.3g init %.3g, %d steps: "
                  "%.6f against the reference's %.6f, gap %.3g"
                  % (("control" if control else "reported", role) + tuple(hparams)
                     + (mark, g, w, gap(g, w))))
            loss_gap = max(loss_gap, gap(g, w))
    print("lfm2-sgd %s, small_step lane: the first step's change against the reference's: %s"
          % ("control" if control else "program",
             ", ".join("%s %.4g" % item for item in change.items())))
    print("lfm2-sgd reference: %.1f s" % (time.perf_counter() - t0))
    return ([("change_gap_" + group, change[group], CHANGE_GAP_LIMITS[group])
             for group in GROUPS] + [("loss_gap_max", float(loss_gap), LOSS_GAP_MAX_LIMIT)])
