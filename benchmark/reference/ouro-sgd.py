"""Plain reference of ``ouro-sgd``: one chip's share of Ouro-2.6B
(``configs/ouro-sgd.json``), its tokens, its two losses, the trained loss's
gradients and momentum SGD, written from the equations in straightforward
``jax.numpy``. Imports nothing of the program and takes nothing it made:
tokens and initial weights come from the seed again. What a lane is made of
whatever its model (the draw of a leaf, the tokens, the norm, the SwiGLU,
which lanes of a sweep are retrained, the gap) is the ``kimi-linear-sgd``
reference's, loaded from beside this file.

float32 under ``jax.default_matmul_precision("highest")``. Attention is one
full masked softmax over the whole ``S x S`` square, head by head; the rotary
tables are built from the formulas. **The loop is written as
``total_ut_steps`` passes over one dictionary of weights** (:func:`one_pass`,
:func:`looped_losses`), and the gradient is that of the whole looped loss:
``jax.grad`` of :func:`looped_losses` at a small size (the tests), and at the
published widths the same gradient **in blocks, a pass each** (``jax.vjp`` of
:func:`one_pass`, whose own differentiation visits a pass's layers; the
passes' cotangents of the one dictionary are added whole, four dictionaries
to one) so that it fits the chip: no gradient is summed visit by visit here.
Each layer and each head of attention recomputes its activations in the
backward pass (``jax.checkpoint``): that changes what is kept, not what is
computed.

The equations (hidden size D, ``total_ut_steps`` T, L layers held, RMSNorm
eps 1e-6, no bias but the gate's, untied head):

* a layer on ``h`` f32[S, D]: ``a = rmsnorm(h; n1)``; ``q, k, v = a W_q, a
  W_k, a W_v`` as [S, 16, 128]; ``q, k`` turned by plain RoPE in the
  rotate-half form over the whole 128 (``inv_freq_i = theta^(-2i / 128)``,
  positions 0..S-1); ``o = softmax(q k^T / sqrt(128), causal) v``; ``h <- h +
  rmsnorm(o W_o; n2)``; ``b = rmsnorm(h; n3)``; ``f = (silu(b W_g) * b W_u)
  W_d``; ``h <- h + rmsnorm(f; n4)``;
* the loop: ``h^0 = E[x]``; for ``t = 1..T``: ``h^(t-1)`` through layers
  ``0..L-1`` with the same weights every pass, then ``h^t = rmsnorm(.;
  n_f)``: exit ``t``'s state and pass ``t + 1``'s input;
* exit ``t``: ``logits_t = h^t W_head``; gate ``g_t = h^t w_gate + b_gate``, a
  scalar a position; ``lambda_t = sigmoid(g_t)``; ``p_t = lambda_t prod_{j<t}
  (1 - lambda_j)`` for ``t < T``, ``p_T = prod_{j<T} (1 - lambda_j)``;
* trained: the mean over positions of ``sum_t p_t l_t - beta H(p)``, ``l_t``
  the next-token cross-entropy of ``logits_t``, ``H`` the entropy of ``p``;
  reported: the mean of ``l_T``.

The comparison is the other lane cells' in its lanes and cost (from the
sweep of the window that the seed draws, the lane that reached the top rung
is retrained as far as its second rung, losses after 1 and 3 steps of the
one trajectory that the stateless seam restarts, and one other lane as far
as its first: four lane-steps at the published widths) and not in
what decides: **what the first step changed**. The record of a sweep hands
over, beside the losses, the program's trainer itself (``lane_change``: the
parameters after so many steps less the parameters at initialisation, leaf
by leaf), and the second lane's first step is held against the reference's
own, as the norm of the difference over the norm of the reference's change:
over all the leaves, over the layers' and over the exit gate's. A step that
is lost reads 1 whatever the learning rate; so does a training state kept in
bfloat16 where the step is small beside the weights. The losses are a net
beside it. Why, and the readings, at the limits below.
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
    spec = importlib.util.spec_from_file_location("bench_reference_lane", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_lane = _beside("kimi-linear-sgd.py")
HPARAMS = _lane.HPARAMS
dataset, gap = _lane.dataset, _lane.gap
rmsnorm, swiglu = _lane.rmsnorm, _lane.swiglu

#: rungs of the top lane that the reference retrains
TOP_LANE_RUNGS = 2


# ------------------------------------------------------------- configuration
def n_layers(config):
    assert set(config["layer_types"]) == {"full_attention"}
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    assert config["rope_scaling"] is None and not config["use_sliding_window"]
    return config["num_hidden_layers"]


def layer_shapes(config):
    d, dh, f = config["hidden_size"], config["head_dim"], config["intermediate_size"]
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return {
        "norm1": (d,), "norm2": (d,), "norm3": (d,), "norm4": (d,),
        "wq": (d, hq * dh), "wk": (d, hk * dh), "wv": (d, hk * dh), "wo": (hq * dh, d),
        "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def init_params(config, key, init_scale, dtype=jnp.float32):
    """``embed``, ``norm_f``, ``head``, ``gate`` (D x 1), ``gate_bias`` (zero)
    and one dictionary ``layers`` of ``l<i>``: the weights every pass uses."""
    d, rows = config["hidden_size"], config["vocab_size"]
    shapes = {"embed": (rows, d), "norm_f": (d,), "head": (d, rows), "gate": (d, 1)}
    params = {n: _lane.init_leaf(key, n, s, init_scale) for n, s in shapes.items()}
    params["gate_bias"] = jnp.zeros((1,), jnp.float32)
    params["layers"] = {
        "l%d" % i: {n: _lane.init_leaf(key, "l%d/%s" % (i, n), s, init_scale)
                    for n, s in layer_shapes(config).items()}
        for i in range(n_layers(config))}
    return jax.tree.map(lambda x: x.astype(dtype), params)


# -------------------------------------------------------------------- layers
def rotary(config, t):
    """``(cos, sin)`` f32[T, head_dim]: plain RoPE."""
    dim = config["head_dim"]
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
    t, dh = x.shape[0], config["head_dim"]
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    cos, sin = (table.astype(x.dtype) for table in rotary(config, t))
    q = rope((x @ p["wq"]).reshape(t, hq, dh), cos, sin)
    k = rope((x @ p["wk"]).reshape(t, hk, dh), cos, sin)
    v = (x @ p["wv"]).reshape(t, hk, dh)
    k, v = (jnp.repeat(y, hq // hk, axis=1) for y in (k, v))
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    @jax.checkpoint
    def head(qh, kh, vh):
        scores = (qh @ kh.T / dh ** 0.5).astype(jnp.float32)
        return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(x.dtype) @ vh

    out = jax.lax.map(lambda a: head(*a), tuple(y.swapaxes(0, 1) for y in (q, k, v)))
    return out.swapaxes(0, 1).reshape(t, hq * dh) @ p["wo"]


def layer(h, p, config):
    eps = config["rms_norm_eps"]
    h = h + rmsnorm(attention(rmsnorm(h, p["norm1"], eps), p, config), p["norm2"], eps)
    fed = swiglu(rmsnorm(h, p["norm3"], eps), p["w_gate"], p["w_up"], p["w_down"])
    return h + rmsnorm(fed, p["norm4"], eps)


def one_pass(layers, norm_f, h, config):
    """``h^(t-1) -> h^t``: through every layer of the one dictionary, then
    the final norm."""
    for i in range(n_layers(config)):
        h = jax.checkpoint(functools.partial(layer, config=config))(h, layers["l%d" % i])
    return rmsnorm(h, norm_f, config["rms_norm_eps"])


# --------------------------------------------------------------------- exits
def exit_cross_entropy(h, head, tokens):
    """An exit's next-token cross-entropy a position, f32[S]."""
    logp = jax.nn.log_softmax((h @ head).astype(jnp.float32))
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]


def exit_log_probabilities(gates):
    """``[log p_t]`` from the exits' gates, a list of f32[S]: ``log p_t = log
    sigmoid(g_t) + sum_{j<t} log(1 - sigmoid(g_j))``, the last the remainder
    ``sum_{j<T} log(1 - sigmoid(g_j))``."""
    log_stay, out = jnp.zeros_like(gates[0]), []
    for g in gates[:-1]:
        out.append(jax.nn.log_sigmoid(g) + log_stay)
        log_stay = log_stay + jax.nn.log_sigmoid(-g)
    return out + [log_stay]


def exits_loss(states, head, gate, gate_bias, tokens, config):
    """The trained loss from the exits' states: the mean over positions of
    ``sum_t p_t l_t - beta H(p)``."""
    losses = [jax.checkpoint(exit_cross_entropy)(h, head, tokens) for h in states]
    gates = [(h @ gate)[:, 0].astype(jnp.float32) + gate_bias[0].astype(jnp.float32)
             for h in states]
    log_p = exit_log_probabilities(gates)
    expected = sum(jnp.exp(lp) * l for lp, l in zip(log_p, losses))
    entropy = -sum(jnp.exp(lp) * lp for lp in log_p)
    return (expected - config["exit_entropy_beta"] * entropy).mean()


def looped_states(params, tokens, config):
    """``[h^1 .. h^T]``: four passes over one dictionary of weights."""
    h, states = params["embed"][tokens[:-1]], []
    for _ in range(config["total_ut_steps"]):
        h = one_pass(params["layers"], params["norm_f"], h, config)
        states.append(h)
    return states


def looped_losses(params, tokens, config):
    """``(the trained loss, the reported loss)`` of ``tokens`` i32[S + 1]:
    the whole looped model, for ``jax.grad``."""
    states = looped_states(params, tokens, config)
    trained = exits_loss(states, params["head"], params["gate"], params["gate_bias"],
                         tokens, config)
    return trained, exit_cross_entropy(states[-1], params["head"], tokens).mean()


# ------------------------------------------------------------------ training
_LANE_FUNCTIONS = {}
#: the check compiles beside the program it checks: quickly, not for speed
_COMPILE = {"exec_time_optimization_effort": -1.0}
EXIT_LEAVES = ("head", "gate", "gate_bias")


def lane_functions(config, dtype):
    """A lane's functions, made once per configuration and precision:
    ``init(init_scale) -> p``, ``step(p, v, t, lr, momentum, wd) -> (p,
    v)``, ``held_out(p) -> the reported loss``, ``change_of(p, p0) -> p -
    p0`` in float32 (``p0`` is given up), ``compile_ahead()``.

    The gradient is :func:`looped_losses`' first output's, in blocks of one
    pass: the exits' states are kept, ``jax.grad`` of :func:`exits_loss`
    gives every exit's cotangent and the exits' leaves' gradients, and from
    the last pass to the first ``jax.vjp`` of :func:`one_pass` takes the
    cotangent of a pass's output (the next pass's, plus its own exit's) to
    that of its input and to one gradient of the whole dictionary of layers
    and of the final norm; the passes' dictionaries are added. One compiled
    function serves all the passes."""
    key = (json.dumps(config, sort_keys=True), jnp.dtype(dtype).name)
    if key in _LANE_FUNCTIONS:
        return _LANE_FUNCTIONS[key]
    train, val = dataset(config)
    n_train, passes = config["train"]["n_train"], config["total_ut_steps"]
    jit = functools.partial(jax.jit, compiler_options=_COMPILE)
    on_chip = jax.default_backend() != "cpu"  # the CPU cannot donate and would warn

    forward = jit(functools.partial(one_pass, config=config))

    @jit
    def backward(layers, norm_f, h, dh):
        _, pull = jax.vjp(functools.partial(one_pass, config=config), layers, norm_f, h)
        return pull(dh)

    exits_grad = jit(jax.grad(
        functools.partial(exits_loss, config=config), argnums=(0, 1, 2, 3)))
    last_exit = jit(lambda h, head, tokens: exit_cross_entropy(h, head, tokens).mean())
    embed_grad = jit(lambda like, ids, dh: jnp.zeros_like(like).at[ids].add(dh))
    add = functools.partial(jit, donate_argnums=(0,) if on_chip else ())(
        lambda total, g: jax.tree.map(jnp.add, total, g))

    @functools.partial(jit, donate_argnums=(0, 1) if on_chip else ())
    def update(p, v, g, lr, momentum, wd):
        v = jax.tree.map(lambda vi, gi, pi: (momentum * vi + gi + wd * pi).astype(dtype),
                         v, g, p)
        return jax.tree.map(lambda pi, vi: (pi - lr * vi).astype(dtype), p, v), v

    change_of = functools.partial(jit, donate_argnums=(1,) if on_chip else ())(
        lambda p, p0: jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))

    @jit
    def init(init_scale):
        return init_params(config, jax.random.key(config["data_seed"] + 1), init_scale, dtype)

    def states_of(p, tokens):
        """``[h^0 .. h^T]``."""
        hs = [p["embed"][tokens[:-1]]]
        for _ in range(passes):
            hs.append(forward(p["layers"], p["norm_f"], hs[-1]))
        return hs

    def step(p, v, t, lr, momentum, wd):
        tokens = train[t % n_train]
        hs = states_of(p, tokens)
        d_states, *g_exits = exits_grad(hs[1:], p["head"], p["gate"], p["gate_bias"], tokens)
        g = dict(zip(EXIT_LEAVES, g_exits))
        dh = jnp.zeros_like(hs[-1])
        for n in reversed(range(passes)):
            g_layers, g_norm, dh = backward(
                p["layers"], p["norm_f"], hs[n], dh + d_states[n])
            g["layers"] = add(g["layers"], g_layers) if "layers" in g else g_layers
            g["norm_f"] = g["norm_f"] + g_norm if "norm_f" in g else g_norm
        g["embed"] = embed_grad(p["embed"], tokens[:-1], dh)
        new_p, new_v = {}, {}
        for name in p:
            new_p[name], new_v[name] = update(p[name], v[name], g.pop(name), lr, momentum, wd)
        return new_p, new_v

    def held_out(p):
        return jnp.mean(jnp.stack([
            last_exit(states_of(p, val[i])[-1], p["head"], val[i])
            for i in range(val.shape[0])]).astype(jnp.float32))

    def compile_ahead():
        """Every compiled function above, at the lane's shapes, with no
        work on the device."""
        t, d, rows = config["train"]["seq_len"], config["hidden_size"], config["vocab_size"]
        sds = lambda shape, kind=dtype: jax.ShapeDtypeStruct(shape, kind)
        h, scalar, tokens = sds((t, d)), sds((), jnp.float32), sds((t + 1,), jnp.int32)
        leaves = {"embed": sds((rows, d)), "norm_f": sds((d,)), "head": sds((d, rows)),
                  "gate": sds((d, 1)), "gate_bias": sds((1,))}
        layers = {"l%d" % i: {n: sds(s) for n, s in layer_shapes(config).items()}
                  for i in range(n_layers(config))}
        init.lower(scalar).compile()
        params = dict(leaves, layers=layers)
        change_of.lower(params, params).compile()
        if dtype == jnp.float32:
            squares.lower(params, params).compile()
        forward.lower(layers, leaves["norm_f"], h).compile()
        backward.lower(layers, leaves["norm_f"], h, h).compile()
        add.lower(layers, layers).compile()
        exits_grad.lower([h] * passes, leaves["head"], leaves["gate"], leaves["gate_bias"],
                         tokens).compile()
        last_exit.lower(h, leaves["head"], tokens).compile()
        embed_grad.lower(leaves["embed"], sds((t,), jnp.int32), h).compile()
        for leaf in [layers] + list(leaves.values()):
            update.lower(leaf, leaf, leaf, scalar, scalar, scalar).compile()

    _LANE_FUNCTIONS[key] = types.SimpleNamespace(
        init=init, step=step, held_out=held_out, change_of=change_of,
        compile_ahead=compile_ahead)
    return _LANE_FUNCTIONS[key]


def compile_ahead(config):
    """Compile the lane's functions without running them. Where the process
    keeps a compile cache on disk, the comparison that comes after the
    window finds them there: the benchmark's builder calls this beside the
    program's own, much longer compilation (``configs/ouro-sgd.py``)."""
    with jax.default_matmul_precision("highest"):
        lane_functions(config, jnp.float32).compile_ahead()


def reference_losses(config, hparams, marks, dtype=jnp.float32, first_step=None):
    """``f[len(marks)]``: the reported (last exit's held-out) loss after
    each mark of cumulative steps of the lane trained from ``hparams = (lr,
    momentum, weight_decay, init_scale)`` on the trained loss. ``v <- m v +
    g + wd p; p <- p - lr v``; step ``t`` trains on sequence ``t mod
    n_train``. A loss that is no number (the training diverged) is infinity.
    ``first_step(change)`` is handed what the first step changed: the
    parameters after it less the parameters at initialisation, float32."""
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
#: the leaves a reading is taken over: every one; the layers' alone, which
#: every pass visits (their gradient is a sum over the passes); the exit
#: gate's alone, which only the exit distribution reaches (through every
#: ``p_t`` and the entropy)
GROUPS = {
    "all": lambda path: True,
    "layers": lambda path: path[0] == "layers",
    "gate": lambda path: path[0] in ("gate", "gate_bias"),
}


@functools.partial(jax.jit, compiler_options=_COMPILE)
def squares(got, want):
    """Leaf by leaf ``(|got - want|^2, |want|^2)``."""
    return jax.tree.map(
        lambda g, w: jnp.stack([jnp.sum(jnp.square(g - w)), jnp.sum(jnp.square(w))]),
        got, want)


def change_gaps(got, want):
    """``{group: |got - want| / |want|}`` of two changes of the parameters
    (trees of ``embed``, ``norm_f``, ``head``, ``gate``, ``gate_bias`` and
    ``layers`` of ``l<i>``), the norms over all the leaves of a group of
    :data:`GROUPS`: 0 where the steps agree, 1 where ``got`` did not move. A
    change that is no number anywhere reads infinity."""
    leaves = [([k.key for k in path], np.asarray(pair, np.float64))
              for path, pair in jax.tree_util.tree_leaves_with_path(squares(got, want))]
    gaps = {}
    for group, holds in GROUPS.items():
        off, whole = np.sum([pair for path, pair in leaves if holds(path)], axis=0)
        gap = np.sqrt(off / whole) if whole > 0 else np.inf
        gaps[group] = float(gap) if np.isfinite(gap) else np.inf
    return gaps


# ---------------------------------------------------------------- the limits
# All of it read on the chip at the published widths (PR 34, ``PERF.md``
# section 2), through ``compare``: 30 sweeps on 30 seeds by the program, 15
# of them by the control too (bfloat16 parameters and momentum, and with them
# the activations), 3 by three planted faults (a state left unchanged; a
# shared leaf's gradient summed over three of its four visits; the entropy
# term dropped). The limits were set on the first 12 seeds.
#
# At initialisation the loss is ln(vocabulary) whatever the weights, one or
# three steps move it by 0.001 to 1 nat, and bfloat16 operands alone move a
# held-out loss of 10.8 by up to 0.005: over 75 lanes read one by one, no
# limit on a loss's gap had the program under it and the control over it
# with room on both sides. What tells them apart is the step itself: on a
# lane of a regular init scale the program's differs from the reference's by
# the rounding of bfloat16 operands through 32 layer visits, 0.0035 to 0.023
# of the step's norm whatever the learning rate (1.0e-4 to 0.35) and the
# init scale (0.10 to 1.41). Beyond that it is rounding's own: a top lane of
# an init scale of 1.91 (one sweep in 65 promoted such a lane) read 1.35,
# further from the reference's step than no step at all. So the step is read
# on the small-step lane alone, whose init scale is regular by choice.

#: ``|program's change - reference's| / |reference's change|`` after the
#: small-step lane's first step (:func:`change_gaps`). ``all``: the program
#: 0.022 at most; a state left unchanged reads 1; the control 0.55 at least
#: (0.89 to 1.0 on 13 seeds of 15: it loses the step; 0.55 and 0.63 where the
#: least learning rate a sweep drew was 0.0028 and more): the limit lies
#: between the program's reading and 1 with the more room above the
#: reading. ``layers``: the program 0.0089 to 0.0225 (the largest at init
#: scales of 1.39 and 1.41), the lost visit 0.063 to 0.078 on every lane,
#: the control 0.56 at least. ``gate``: the program 0.007 to 0.057, the
#: entropy term dropped 1.1 to 3.1 on every lane (and 0.23 to 0.28 over all
#: the leaves: the gates' gradient reaches every state), the control 0.08 at
#: least
CHANGE_GAP_LIMITS = {"all": 0.3, "layers": 0.04, "gate": 0.2}
#: ``gap`` of every loss read (the limit of the accepted lane cells; the
#: program 0.080 at most, a top lane of lr 0.32 whose loss rises to 18 in
#: three steps, 6.1e-3 else; 0.076 over the 75 lanes; the control 2.9e-4 to
#: 1.7e-2): a net for a loss that is a number on one side only
LOSS_GAP_MAX_LIMIT = 0.25


def lanes_of(rec):
    """``[(hyperparameters by name, {steps: reported loss})]`` of a sweep."""
    lane_of = list(zip(rec["bracket"].tolist(), rec["lane"].tolist()))
    rungs = {}
    for row, lane in enumerate(lane_of):
        rungs.setdefault(lane, {})[int(round(rec["budget"][row]))] = rec["loss"][row]
    return [(dict(zip(HPARAMS, (rec["config"][n][lane_of.index(lane)] for n in HPARAMS))),
             reported) for lane, reported in rungs.items()]


#: beyond this init scale a lane is chaos at initialisation already
#: (bfloat16 operands alone put the program 0.08 to 0.6 nats from the
#: reference before any step, at an init scale of 1.9 to 2.7: 75 lanes read
#: one by one), and no step is told from rounding there
REGULAR_INIT_SCALE = 1.5


def sample_lanes(rec):
    """``{role: (hyperparameters, {steps: reported loss})}`` of a sweep.
    ``top``: the lane that reached the top rung, with its loss at its first
    :data:`TOP_LANE_RUNGS` rungs. ``small_step``: of the other lanes whose
    init scale is at most :data:`REGULAR_INIT_SCALE` (promoted once or not:
    halving promotes the small init scales, so the lanes that ran the first
    rung only are mostly the chaotic ones), the one of the smallest
    learning rate, with its loss at the first rung: where a step is smallest
    beside the weights it moves, and a training state kept in bfloat16 loses
    it. One sweep in a thousand draws no such lane: then the one of the
    smallest init scale."""
    lanes = lanes_of(rec)
    top = max(lanes, key=lambda lane: len(lane[1]))
    others = [lane for lane in lanes if lane is not top]
    regular = [lane for lane in others if lane[0]["init_scale"] <= REGULAR_INIT_SCALE]
    small_step = (min(regular, key=lambda lane: lane[0]["lr"]) if regular
                  else min(others, key=lambda lane: lane[0]["init_scale"]))
    picked = {"top": (top, TOP_LANE_RUNGS), "small_step": (small_step, 1)}
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
            print("ouro-sgd %s, %s lane: lr %.3g momentum %.3g wd %.3g init %.3g, %d steps: "
                  "%.6f against the reference's %.6f, gap %.3g"
                  % (("control" if control else "reported", role) + tuple(hparams)
                     + (mark, g, w, gap(g, w))))
            loss_gap = max(loss_gap, gap(g, w))
    print("ouro-sgd %s, small_step lane: the first step's change against the reference's: %s"
          % ("control" if control else "program",
             ", ".join("%s %.4g" % item for item in change.items())))
    print("ouro-sgd reference: %.1f s" % (time.perf_counter() - t0))
    return ([("change_gap_" + group, change[group], CHANGE_GAP_LIMITS[group])
             for group in GROUPS] + [("loss_gap_max", float(loss_gap), LOSS_GAP_MAX_LIMIT)])
