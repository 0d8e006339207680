"""Plain reference of ``sdar-sgd``: one chip's share of SDAR-30B-A3B-Chat
(``configs/sdar-sgd.json``) trained by masked diffusion over blocks: its
tokens, its masks and noise levels, its loss, the loss's gradients and
momentum SGD, written from the equations in straightforward ``jax.numpy``.
Imports nothing of the program and takes nothing it made: tokens, noise and
initial weights come from the seed again. What a lane is made of whatever
its model (the draw of a leaf, the tokens, the norm, the SwiGLU, the gap) is
the ``kimi-linear-sgd`` reference's, and which lanes of a sweep are retrained
and the norms of a step's change the ``ouro-sgd`` reference's, both loaded
from beside this file.

float32 under ``jax.default_matmul_precision("highest")``. **The 2 S rows
outright** (the clean copy, then the masked one, row ``i`` and row ``S + i``
both at position ``i``) **and the rule of sight as one explicit boolean array**
f32[2 S, 2 S] (:func:`sight_mask`), built from the four sentences below and
from nothing else; attention one full masked softmax over that square, head
by head, each head of the queries and of the keys through its RMSNorm first,
the key/value heads repeated outright; the router a ``top_k`` and the experts
a loop over the held ones with a mask: no blocks, no spans, no sorting, no
grouped product. The gradient is ``jax.grad`` of the whole loss
(:func:`loss_fn`). Each layer and each head of attention recomputes its
activations in the backward pass (``jax.checkpoint``): that changes what is
kept, not what is computed.

The equations (hidden size D, RMSNorm eps ``rms_norm_eps``, no bias
anywhere):

* a sequence is ``x`` i32[S] in blocks of ``L = block_length``, ``B(i) = i
  div L``; block ``b`` has a noise level ``t_b``, position ``i`` is masked
  (``m_i``) with probability ``t_B(i)``, ``x~_i = MASK if m_i else x_i``
  with ``MASK`` the last id of the slice; the rows are ``c_i = E[x_i]`` and
  ``n_i = E[x~_i]``, ``i = 0..S-1``;
* a layer on ``h`` f32[2 S, D]: ``h <- h + Attn(rmsnorm(h; n1))``; ``h <- h
  + Experts(rmsnorm(h; n2))``, row by row but for the softmax;
* attention: ``q, k, v = a W_q, a W_k, a W_v`` as [2 S, 32 | 4 | 4, 128];
  every head of ``q`` and of ``k`` through ``rmsnorm(.; q_norm | k_norm)``
  over its 128 channels; plain RoPE in the rotate-half form over the whole
  head at the row's position (``inv_freq_i = theta^(-2i / 128)``); ``o =
  softmax(q k^T / sqrt(128) over the keys the row sees) v``, query head
  ``a`` on key/value head ``a // 8``; ``o W_o``. **Sight**: a clean query
  ``i`` sees clean key ``j`` iff ``B(j) <= B(i)``, and no masked row; a masked
  query ``i`` sees clean key ``j`` iff ``B(j) < B(i)``, and masked key ``j``
  iff ``B(j) = B(i)``;
* experts: ``s = softmax(b W_r)`` over all the router's outputs (float32
  operands); the top 8 of ``s``, renormalised to sum 1; this chip adds ``w_e
  W_d,e (silu(b W_g,e) * b W_u,e)`` for chosen experts it holds;
* ``z_i = rmsnorm(h_{n_i}; n_f) W_head`` over the masked rows alone; ``loss
  = (1 / S) sum_i (m_i / t_B(i)) * (-log softmax(z_i)[x_i])``: no shift.

The comparison is the ``lfm2-sgd`` reference's in its lanes and in what
decides: from the sweep of the window that the seed draws, the lane that
reached the top rung is retrained as far as its second rung (losses after 1
and 3 steps) and one other lane of a regular init scale, the one of the
smallest learning rate, as far as its first; **what that lane's first step
changed** (``lane_change`` of the record: the program's trainer, the
parameters after the step less the parameters at initialisation) is held
against the reference's own first step as the norm of the difference over
the norm of the reference's change: over all the leaves, over embedding and
head, over the expert layers' and over the attention mixers'. A step that
is lost reads 1 whatever the learning rate; so does a training state kept in
bfloat16 where the step is small beside the weights. **The rule of sight is
read outright** beside it (:func:`sight_leak`): the program's masked rows'
last states (``masked_states`` of the record) must stay as they are when
the clean tokens under one block's masks change, in that block and in every
block before it; at random weights a step's change hardly shows such a leak,
a row's own state shows it whole. The losses are held to the limit of the
accepted lane cells: a loss that is wrong outright. The readings are at the
limits below.
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
gap, rmsnorm, swiglu = _lane.gap, _lane.rmsnorm, _lane.swiglu
sample_lanes, squares = _steps.sample_lanes, _steps.squares


# ------------------------------------------------------------- configuration
def n_layers(config):
    assert config["decoder_sparse_step"] == 1 and not config["mlp_only_layers"]
    assert config["norm_topk_prob"] and not config["tie_word_embeddings"]
    assert config["rope_scaling"] is None and not config["use_sliding_window"]
    assert len(config["cut"]["experts_held"]) == config["num_experts"]
    return config["num_hidden_layers"]


def mask_id(config):
    """``MASK``: the last id of the vocabulary's slice."""
    return config["vocab_size"] - 1


def layer_shapes(config):
    d, dh = config["hidden_size"], config["head_dim"]
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    f, held = config["moe_intermediate_size"], len(config["cut"]["experts_held"])
    return {"norm1": (d,), "norm2": (d,),
            "wq": (d, hq * dh), "wk": (d, hk * dh), "wv": (d, hk * dh), "wo": (hq * dh, d),
            "q_norm": (dh,), "k_norm": (dh,),
            "router": (d, config["cut"]["router_outputs"]),
            "e_gate": (held, d, f), "e_up": (held, d, f), "e_down": (held, f, d)}


def init_leaf(key, name, shape, init_scale):
    """The lanes' draw of a leaf; the per-head norms' weights are one."""
    if name.rsplit("/", 1)[-1] in ("q_norm", "k_norm"):
        return jnp.ones(shape, jnp.float32)
    return _lane.init_leaf(key, name, shape, init_scale)


def init_params(config, key, init_scale, dtype=jnp.float32):
    """``embed``, ``norm_f``, ``head`` and ``l<i>``."""
    d, rows = config["hidden_size"], config["vocab_size"]
    shapes = {"embed": (rows, d), "norm_f": (d,), "head": (d, rows)}
    params = {n: init_leaf(key, n, s, init_scale) for n, s in shapes.items()}
    for i in range(n_layers(config)):
        params["l%d" % i] = {n: init_leaf(key, "l%d/%s" % (i, n), s, init_scale)
                             for n, s in layer_shapes(config).items()}
    return jax.tree.map(lambda x: x.astype(dtype), params)


def dataset(config):
    """``(train, val)``, each ``(tokens i32[n, S], mask bool[n, S], weight
    f32[n, S])``: the lanes' tokens over the slice less ``MASK`` (Zipf, the
    second half of a sequence repeating its first), and beside them, from
    the same seed, a noise level ``t`` uniform on ``[noise_floor, 1]`` a
    block, a mask ``m`` that holds a position with its block's probability
    ``t``, and the weight ``m / t``."""
    s, length = config["train"]["seq_len"], config["train"]["block_length"]
    floor = config["train"]["noise_floor"]
    tokens = _lane.dataset(dict(config, vocab_size=config["vocab_size"] - 1))

    def record(ids, key):
        k_level, k_mask = jax.random.split(key)
        level = jax.random.uniform(k_level, (ids.shape[0], s // length), minval=floor, maxval=1.0)
        level = jnp.repeat(level, length, axis=1)
        mask = jax.random.uniform(k_mask, (ids.shape[0], s)) < level
        return ids[:, :s], mask, jnp.where(mask, 1.0 / level, 0.0)

    k_train, k_val = jax.random.split(
        jax.random.fold_in(jax.random.key(config["data_seed"]), 1))
    return record(tokens[0], k_train), record(tokens[1], k_val)


# -------------------------------------------------------------------- layers
def sight_mask(s, length):
    """bool[2 S, 2 S], ``[query row, key row]``: rows ``0..S-1`` the clean
    copy, ``S..2S-1`` the masked one, blocks of ``length``. The four
    sentences, one line each."""
    row = jnp.arange(2 * s)
    masked, block = row >= s, (row % s) // length
    q_masked, k_masked = masked[:, None], masked[None, :]
    q_block, k_block = block[:, None], block[None, :]
    clean_sees_clean = ~q_masked & ~k_masked & (k_block <= q_block)
    # a clean query sees no masked row: no term for it
    masked_sees_clean = q_masked & ~k_masked & (k_block < q_block)
    masked_sees_masked = q_masked & k_masked & (k_block == q_block)
    return clean_sees_clean | masked_sees_clean | masked_sees_masked


def rotary(config, positions):
    """``(cos, sin)`` f32[rows, head_dim] at the rows' positions: plain RoPE."""
    dim = config["head_dim"]
    inv_freq = config["rope_theta"] ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def rope(x, cos, sin):
    """``x`` [rows, H, d]: channel ``i`` turns with ``i + d / 2``."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def attention(x, p, config):
    rows, dh, eps = x.shape[0], config["head_dim"], config["rms_norm_eps"]
    s = rows // 2
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    # row i and row S + i stand at position i
    positions = jnp.concatenate([jnp.arange(s), jnp.arange(s)])
    cos, sin = (table.astype(x.dtype) for table in rotary(config, positions))
    q = rope(rmsnorm((x @ p["wq"]).reshape(rows, hq, dh), p["q_norm"], eps), cos, sin)
    k = rope(rmsnorm((x @ p["wk"]).reshape(rows, hk, dh), p["k_norm"], eps), cos, sin)
    v = (x @ p["wv"]).reshape(rows, hk, dh)
    # query head a on key/value head a // (hq / hk): repeated outright
    k, v = (jnp.repeat(y, hq // hk, axis=1) for y in (k, v))
    mask = sight_mask(s, config["train"]["block_length"])

    @jax.checkpoint
    def head(qh, kh, vh):
        scores = (qh @ kh.T / dh ** 0.5).astype(jnp.float32)
        return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1).astype(x.dtype) @ vh

    out = jax.lax.map(lambda a: head(*a), tuple(y.swapaxes(0, 1) for y in (q, k, v)))
    return out.swapaxes(0, 1).reshape(rows, hq * dh) @ p["wo"]


def router_weights(x, p, config):
    """``(chosen i32[rows, k], weight f32[rows, k])``: the top k of the
    softmax over all the router's outputs, renormalised to sum 1."""
    s = jax.nn.softmax(x.astype(jnp.float32) @ p["router"].astype(jnp.float32), axis=-1)
    s_chosen, chosen = jax.lax.top_k(s, config["num_experts_per_tok"])
    return chosen, s_chosen / s_chosen.sum(-1, keepdims=True)


def experts(x, p, config, held=None):
    """The share of the expert layer that holds ``held`` (global expert
    ids, in the order of the leaves' leading axis); default the
    configuration's. The held experts one after the other, each over every
    row with its weight or zero."""
    held = config["cut"]["experts_held"] if held is None else held
    chosen, weight = router_weights(x, p, config)
    ids = jnp.asarray(held, chosen.dtype)[:, None, None]
    w = jnp.where(chosen[None] == ids, weight[None], 0.0).sum(-1).astype(x.dtype)  # [held, rows]

    def add_expert(y, e):
        w_e, gate, up, down = e
        return y + w_e[:, None] * swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (w, p["e_gate"], p["e_up"], p["e_down"]))
    return y


def layer(h, p, config):
    eps = config["rms_norm_eps"]
    h = h + attention(rmsnorm(h, p["norm1"], eps), p, config)
    return h + experts(rmsnorm(h, p["norm2"], eps), p, config)


def rows_in(params, tokens, mask, config):
    """The first state f32[2 S, D]: the clean copy's embeddings, then the
    masked copy's."""
    noised = jnp.where(mask, mask_id(config), tokens)
    return params["embed"][jnp.concatenate([tokens, noised])]


def hidden(params, tokens, mask, config):
    """The last layer's output, all ``2 S`` rows."""
    h = rows_in(params, tokens, mask, config)
    for i in range(n_layers(config)):
        h = jax.checkpoint(functools.partial(layer, config=config))(h, params["l%d" % i])
    return h


def logits(params, tokens, mask, config):
    """``z`` f32[S, vocabulary slice] of the masked rows."""
    s = tokens.shape[0]
    h = hidden(params, tokens, mask, config)[s:]
    return (rmsnorm(h, params["norm_f"], config["rms_norm_eps"]) @ params["head"]
            ).astype(jnp.float32)


def loss_fn(params, seq, config):
    """The weighted cross-entropy of the masked rows at their own tokens,
    over ``S``; ``seq = (tokens i32[S], mask bool[S], weight f32[S])``. For
    ``jax.grad``, whole."""
    tokens, mask, weight = seq
    logp = jax.nn.log_softmax(logits(params, tokens, mask, config))
    nll = -jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
    return jnp.sum(weight.astype(jnp.float32) * nll) / tokens.shape[0]


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
    def one_step(p, v, seq, lr, momentum, wd):
        g = jax.grad(functools.partial(loss_fn, config=config))(p, seq)
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

    def sequence(data, i):
        return tuple(x[i] for x in data)

    def step(p, v, t, lr, momentum, wd):
        return one_step(p, v, sequence(train, t % n_train), lr, momentum, wd)

    def held_out(p):
        return jnp.mean(jnp.stack(
            [loss(p, sequence(val, i)) for i in range(val[0].shape[0])]).astype(jnp.float32))

    def compile_ahead():
        """Every compiled function above, at the lane's shapes, with no
        work on the device."""
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        seq = tuple(jax.ShapeDtypeStruct(x.shape[1:], x.dtype) for x in train)
        params = jax.eval_shape(init, scalar)
        init.lower(scalar).compile()
        one_step.lower(params, params, seq, scalar, scalar, scalar).compile()
        loss.lower(params, seq).compile()
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
    program's own, much longer compilation (``configs/sdar-sgd.py``)."""
    with jax.default_matmul_precision("highest"):
        lane_functions(config, jnp.float32).compile_ahead()


def reference_losses(config, hparams, marks, dtype=jnp.float32, first_step=None):
    """``f[len(marks)]``: the held-out loss after each mark of cumulative
    steps of the lane trained from ``hparams = (lr, momentum, weight_decay,
    init_scale)``. ``v <- m v + g + wd p; p <- p - lr v``; step ``t`` trains
    on sequence ``t mod n_train`` under that sequence's own masks and noise
    levels. A loss that is no number (the training diverged) is infinity.
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
#: the leaves a reading is taken over: every one; embedding and head (the
#: two ends of the pass: the lookup of both copies, the masked rows' logits);
#: the expert layers' alone (the router, which only the weights reach, and
#: the held experts); the attention mixers' alone (the projections and the
#: per-head norms: what the rule of sight reaches first)
GROUPS = {
    "all": lambda path: True,
    "ends": lambda path: path[0] in ("embed", "head"),
    "experts": lambda path: path[-1] in ("router", "e_gate", "e_up", "e_down"),
    "attention": lambda path: path[-1] in ("wq", "wk", "wv", "wo", "q_norm", "k_norm"),
}


def change_gaps(got, want):
    """``{group: |got - want| / |want|}`` of two changes of the parameters
    (trees of ``embed``, ``norm_f``, ``head`` and ``l<i>``), the norms over
    all the leaves of a group of :data:`GROUPS`: 0 where the steps agree, 1
    where ``got`` did not move. A change that is no number anywhere reads
    infinity."""
    leaves = [([k.key for k in path], np.asarray(pair, np.float64))
              for path, pair in jax.tree_util.tree_leaves_with_path(squares(got, want))]
    gaps = {}
    for group, holds in GROUPS.items():
        off, whole = np.sum([pair for path, pair in leaves if holds(path)], axis=0)
        value = np.sqrt(off / whole) if whole > 0 else np.inf
        gaps[group] = float(value) if np.isfinite(value) else np.inf
    return gaps


# ------------------------------------------------------ the rule of sight
def sight_leak(config, masked_states, hparams):
    """What the masked rows may not see, read off the program's own rows at
    the cell's size: ``masked_states(hparams, tokens i32[S], mask bool[S])
    -> f32[S, D]``, the last layer's output for the masked rows of the lane
    of ``hparams`` at its initial weights. In the held-out sequence, the
    clean tokens **under the masks** of one block ``b`` are replaced by
    other ids (the masked copy stays as it was: it shows ``MASK`` there). By
    the four sentences no masked row of a block ``<= b`` sees them: the rows
    of ``b`` see their own block's masked copy and the clean blocks before
    it, the earlier ones less. Returns the largest shift of any such row's
    state over the largest state, for ``b`` the first, the middle and the
    last of the blocks that mask a position: 0 for a rule that holds (the
    rows' inputs are the same numbers, a key that is not seen weighs exactly
    0), and of the states' own size for a masked row that sees its own
    block's clean copy (the first block's rows see four keys, or eight). A
    probe that moves no later row either reaches no state: infinity."""
    length = config["train"]["block_length"]
    tokens, mask, _ = (np.asarray(x[0]) for x in dataset(config)[1])
    base = np.asarray(masked_states(hparams, tokens, mask), np.float64)
    holds = np.flatnonzero(mask.reshape(-1, length).any(axis=1))
    worst, reached = 0.0, False
    for b in sorted({int(holds[0]), int(holds[len(holds) // 2]), int(holds[-1])}):
        under = np.zeros_like(mask)
        under[b * length:(b + 1) * length] = mask[b * length:(b + 1) * length]
        # another id of the slice, never MASK
        other = np.where(under, (tokens + 1 + b) % mask_id(config), tokens).astype(tokens.dtype)
        shift = np.abs(np.asarray(masked_states(hparams, other, mask), np.float64) - base)
        to = (b + 1) * length
        worst = max(worst, float(shift[:to].max()))
        later = float(shift[to:].max()) if to < len(tokens) else 0.0
        reached = reached or later > 0
        print("sdar-sgd sight: clean tokens under the masks of block %d changed: rows of blocks "
              "0..%d shift %.3g, later rows %.3g (states to %.3g)"
              % (b, b, shift[:to].max(), later, np.abs(base).max()))
    return worst / float(np.abs(base).max()) if reached else np.inf


# ---------------------------------------------------------------- the limits
# All of it read on the chip at the published widths (PR 42, ``PERF.md``
# section 2), through ``compare``: 23 sweeps on 23 seeds by the program, 11
# of them by the control too (bfloat16 parameters and momentum, and with them
# the activations), 3 by two planted faults (a masked row that sees the clean
# copy of its own block; the loss without its weights ``1 / t``), and seven
# lanes chosen at the corners that the seeds did not draw (init scales of 0.1
# to 1.5, learning rates of 1e-4 to 1), sound and control. The limits were
# set on the first 16 seeds and the chosen lanes; the last seven seeds, and
# the twelve read since (``PERF.md`` section 2), inside them. A lane's weights, tokens, masks and noise levels are
# the configuration's, so a reading is a function of the lane's learning
# rate, decay and init scale alone.

#: ``|program's change - reference's| / |reference's change|`` after the
#: small-step lane's first step (:func:`change_gaps`). **The weights can
#: reach 1 / t = 1,000 on a rare row** (a block whose noise level is at the
#: floor and that masks a position all the same). In the configuration's own
#: draw the largest is 177 over the 32 training sequences and 122 in the
#: first, the one a first step reads: one row carries 3 % of that step, the
#: 19 rows of weight 10 and more a sequence 9 %. Such a row's gradient is not
#: averaged with its neighbours', so the rounding of bfloat16 operands on
#: that one row reaches the step whole. It does so alike on every lane (the
#: rows are the configuration's), which is why the readings below hardly
#: spread; and the reference reads the same row, so the gap stays rounding's
#: and an error on that row alone would show as 0.03. ``experts``
#: (the routers and the held experts) **decides**: the program 0.0035 to
#: 0.0173 on the seeds and 0.0333 at the corner of the space (lr 1e-4 at
#: init scale 0.1: a choice that bfloat16 operands upstream flip sends a
#: row's gradient to another expert, and at the least learning rate an
#: expert's step is a few float32 units of its weights, on both sides); the
#: control 0.52 and more on the 11 seeds and, on the chosen lanes, 0.13 and
#: 0.27 at lanes of lr 1.0 and 0.3 at init scales 1.0 and 1.4, whose step
#: bfloat16 state does not lose: over this limit on all 18. ``attention``
#: (the mixers' projections and per-head norms): the program 0.0037 to
#: 0.0074, 0.0082 at the corners; the control 0.037 and more; a masked row
#: that sees its own block's clean copy 0.0160 to 0.0266 (3.6 to 4.5 times
#: the sound reading of the same lane: at random weights a row's own token
#: tells a step little yet; over this limit on two lanes of three, which is
#: why the rule of sight is read outright, ``SIGHT_LEAK_LIMIT`` below).
#: ``all`` and ``ends`` (embedding and
#: head): the program 0.0007 to 0.0047 and 0.0005 to 0.0028, 0.0087 and
#: 0.0054 at the corner of lr 1e-4 and init scale 1.5; a state left
#: unchanged 1; the loss without its weights 0.556 in both on every lane
#: read; the control 0.0074 to 0.996 (over 0.1 on 15 of 18: a lane of lr 1.0
#: keeps its step in bfloat16): between the reading and 1 with the more room
#: above the reading, for a step that is lost or weighed wrongly
CHANGE_GAP_LIMITS = {"all": 0.1, "ends": 0.1, "experts": 0.08, "attention": 0.02}
#: :func:`sight_leak`, the shift of masked rows' states that may not move
#: over the largest state. A rule that holds reads exactly 0: the rows'
#: inputs are the same numbers and a key that is not seen weighs exactly 0,
#: whatever the operands' precision: 0 on the chip at the published widths
#: in every run read (twelve seeds, init scales 0.14 to 1.2) and on the CPU
#: at the tests' size. A masked row that sees its own block's clean copy
#: reads a share of the states' own size: on the chip 0.039, 0.118 and 0.247
#: at init scales 0.14, 0.419 and 1.2 (``PERF.md`` section 2). The limit is
#: 386 times under the least of those
SIGHT_LEAK_LIMIT = 1e-4
#: ``gap`` of every loss read: the limit of the accepted lane cells, for a
#: loss that is wrong outright or a number on one side only. The program
#: read 1.7e-6 to 3.5e-4 (35 sweeps); a loss reported and trained without
#: its weights ``1 / t`` is about half the reference's (the mean of ``t`` is
#: a half): 0.452 to 0.804 on five lanes, over on every one. A state left
#: unchanged (0.0011 to 0.031 after a step where the lane learns, 0.63 and
#: more where its loss runs away) and the control (4.1e-4 to 5.1e-2) stay
#: under it on the lanes a sweep promotes: the steps' change decides those
LOSS_GAP_MAX_LIMIT = 0.25


def compare(config, traffic, records, seed, control=False):
    """``[(name, value, limit)]``, on the sweep of the window that the seed
    draws. With ``control`` the reference computed with bfloat16 parameters
    and momentum stands in the program's place, its losses for the reported
    ones and its first step for the program's (``lane_change`` of the
    record: ``(hyperparameters, steps) -> the parameters' change``); what
    the masked rows see is no matter of precision, and stays the program's
    (``masked_states`` of the record, :func:`sight_leak`)."""
    t0 = time.perf_counter()
    rec = records[np.random.default_rng(seed).integers(len(records))]
    loss_gap, change = 0.0, {}
    leak = sight_leak(config, rec["masked_states"], sample_lanes(rec)["small_step"][0])
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
            print("sdar-sgd %s, %s lane: lr %.3g momentum %.3g wd %.3g init %.3g, %d steps: "
                  "%.6f against the reference's %.6f, gap %.3g"
                  % (("control" if control else "reported", role) + tuple(hparams)
                     + (mark, g, w, gap(g, w))))
            loss_gap = max(loss_gap, gap(g, w))
    print("sdar-sgd %s, small_step lane: the first step's change against the reference's: %s"
          % ("control" if control else "program",
             ", ".join("%s %.4g" % item for item in change.items())))
    print("sdar-sgd reference: %.1f s" % (time.perf_counter() - t0))
    return ([("change_gap_" + group, change[group], CHANGE_GAP_LIMITS[group])
             for group in GROUPS]
            + [("sight_leak", leak, SIGHT_LEAK_LIMIT),
               ("loss_gap_max", float(loss_gap), LOSS_GAP_MAX_LIMIT)])
