"""Plain reference of ``kimi-linear-sgd``: one chip's share of a
Kimi-Linear block (``configs/kimi-linear-sgd.json``), its tokens, its loss
and gradients and momentum SGD, written from the layer equations in
straightforward ``jax.numpy``. Imports nothing of the program and takes
nothing it made: tokens and initial weights come from the seed again.

float32 under ``jax.default_matmul_precision("highest")``. KDA is the
recurrence, token by token; attention a full masked softmax, head by head;
the expert layer a loop over the held experts with a mask: no sorting, no
chunked scan, no grouped product. So that a lane at the published widths
fits one chip beside its gradients, each layer, each head of attention and
each block of ``RECURRENCE_BLOCK`` steps of the recurrence recomputes its
activations in the backward pass (``jax.checkpoint``): that changes what is
kept, not what is computed.

Layer equations (pre-norm residual, ``h += Mixer(RMSNorm(h)); h +=
FFN(RMSNorm(h))``, final RMSNorm, untied head, mean next-token
cross-entropy over the vocabulary slice):

* KDA, per head: ``q = l2norm(silu(conv4(W_q x)))``, ``k`` alike, ``v =
  silu(conv4(W_v x))``; ``a_t = exp(-exp(A_log) * softplus(W_a2 W_a1 x_t +
  dt_bias))``; ``beta_t = sigmoid(W_b x_t)``; ``S_t = (I - beta_t k_t
  k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T``; ``o_t = S_t^T q_t /
  sqrt(d_k)``; out ``W_o (rmsnorm(o_t) * sigmoid(W_g2 W_g1 x_t))``.
* MLA without positions: ``q = W_q x`` as heads of 128 + 64; ``[c, k_pe] =
  W_kva x``; ``c = rmsnorm(c)``; ``[k_nope, v] = W_kvb c``; ``k = [k_nope,
  k_pe]``, ``k_pe`` shared by the heads; causal softmax of ``q k^T /
  sqrt(192)``; ``W_o`` over the values.
* Experts: ``s = sigmoid(W_r x)`` over all the router's outputs; the top 8
  of ``s + b``; weights ``s_e / sum(chosen s) * routed_scaling_factor``;
  this chip adds ``w_e E_e(x)`` for chosen experts it holds and the shared
  expert once; ``E(x) = W_down(silu(W_gate x) * W_up x)``.

The comparison retrains, from their reported hyperparameters, the lane of
a seeded sweep that reached the top rung, as far as its second rung (losses
after 1 and 3 steps: the stateless seam restarts a promoted lane from the
key, so every rung's loss of that lane is a loss of the one trajectory), and
one more lane that ran the first rung only: four reference lane-steps, three
losses. What decides is the top lane's first rung, before any step has
amplified rounding: the lane that halving promotes is as a rule one with a
large learning rate, and from its second rung on it can be on its way to
divergence, where rounding grows without bound (a loss that is no number is
infinity on both sides). The top rung (9 steps) is not retrained: six more
lane-steps are twenty seconds that a cold traced run's 360 s do not have,
and half the top lanes seen had left the region where they learn by then.
"""

import functools
import json
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

RECURRENCE_BLOCK = 64
#: per loss: gap = |reported - reference| / (1 + |reference|). Readings the
#: limits were set from (PERF.md section 2, my chip runs of PR 28, 13
#: sweeps). The program's matrix products have bfloat16 operands, so a sound
#: gap is bfloat16's rounding, and steps amplify it. The top lane's first
#: rung (1 step) decides: sound runs at most 1.4e-5 (the others 0 to 9.8e-6),
#: the control (bfloat16 parameters and momentum) at least 1.6e-4 over 2
#: seeds (the other 1.3e-2).
LOSS_GAP_EARLY_LIMIT = 5e-5
#: every compared loss: catches a gradient or a step that is wrong outright
#: (the fault this PR found on the chip read 2.5 and inf). Sound at most
#: 1.7e-3 (a first-rung lane with init scale 3.5 and learning rate 0.43);
#: a top lane's second rung at most 1.8e-4, on lanes that a few steps
#: later diverge.
LOSS_GAP_MAX_LIMIT = 5e-2
#: rungs of the top lane that the reference retrains, and of these how many
#: decide by the tight limit
TOP_LANE_RUNGS = 2
EARLY_RUNGS = 1
HPARAMS = ("lr", "momentum", "weight_decay", "init_scale")


# ------------------------------------------------------------- configuration
def layer_kinds(config):
    """``[(mixer, ffn)]`` of the layers held, from the published lists."""
    linear = config["linear_attn_config"]
    kinds = []
    for number in config["cut"]["layers"]:
        mixer = "kda" if number in linear["kda_layers"] else "mla"
        assert mixer == "kda" or number in linear["full_attn_layers"]
        kinds.append((mixer, "dense" if number <= config["first_k_dense_replace"]
                      else "moe"))
    return kinds


def layer_shapes(config, mixer, ffn):
    d, h = config["hidden_size"], config["num_attention_heads"]
    shapes = {"norm1": (d,), "norm2": (d,)}
    if mixer == "kda":
        linear = config["linear_attn_config"]
        h, dk, kernel = linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]
        shapes.update({
            "wq": (d, h * dk), "wk": (d, h * dk), "wv": (d, h * dk),
            "conv_q": (kernel, h * dk), "conv_k": (kernel, h * dk),
            "conv_v": (kernel, h * dk), "wa1": (d, dk), "wa2": (dk, h * dk),
            "A_log": (h,), "dt_bias": (h * dk,), "wb": (d, h),
            "wg1": (d, dk), "wg2": (dk, h * dk), "o_norm": (dk,), "wo": (h * dk, d)})
    else:
        dn, dr, dv = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
        rank = config["kv_lora_rank"]
        shapes.update({
            "wq": (d, h * (dn + dr)), "wkva": (d, rank + dr), "kv_norm": (rank,),
            "wkvb": (rank, h * (dn + dv)), "wo": (h * dv, d)})
    if ffn == "dense":
        f = config["intermediate_size"]
        shapes.update({"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)})
    else:
        f, held = config["moe_intermediate_size"], len(config["cut"]["experts_held"])
        outputs = config["cut"]["router_outputs"]
        shapes.update({
            "router": (d, outputs), "router_bias": (outputs,),
            "shared_gate": (d, f), "shared_up": (d, f), "shared_down": (f, d),
            "e_gate": (held, d, f), "e_up": (held, d, f), "e_down": (held, f, d)})
    return shapes


def init_leaf(key, name, shape, init_scale):
    """Matrices and convolutions ``init_scale / sqrt(fan_in) * N(0, 1)``
    drawn from the key folded with the CRC-32 of the leaf's name; the
    embedding ``init_scale * N(0, 1)`` (fan-in one); norm weights one; the
    balancing bias zero; ``A_log`` the log of 1..16 over the heads;
    ``dt_bias`` the inverse softplus of 0.001..0.1 (geometric) over the
    channels."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf.startswith("norm") or leaf in ("kv_norm", "o_norm"):
        return jnp.ones(shape, jnp.float32)
    if leaf == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    if leaf == "A_log":
        return jnp.log(jnp.linspace(1.0, 16.0, shape[0], dtype=jnp.float32))
    if leaf == "dt_bias":
        dt = jnp.exp(jnp.linspace(np.log(0.001), np.log(0.1), shape[0], dtype=jnp.float32))
        return dt + jnp.log(-jnp.expm1(-dt))
    draw = jax.random.normal(
        jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF), shape, jnp.float32)
    fan_in = 1 if leaf == "embed" else shape[-2]
    return init_scale * fan_in ** -0.5 * draw


def init_params(config, key, init_scale, dtype=jnp.float32):
    d, rows = config["hidden_size"], config["vocab_size"]
    shapes = {"embed": (rows, d), "norm_f": (d,), "head": (d, rows)}
    params = {n: init_leaf(key, n, s, init_scale) for n, s in shapes.items()}
    for i, (mixer, ffn) in enumerate(layer_kinds(config)):
        params["l%d" % i] = {
            n: init_leaf(key, "l%d/%s" % (i, n), s, init_scale)
            for n, s in layer_shapes(config, mixer, ffn).items()}
    return jax.tree.map(lambda x: x.astype(dtype), params)


def dataset(config):
    """Zipf ids over the slice by inverse CDF, the second half of each
    sequence repeating its first: ``(train, val)`` of i32[n, T + 1]."""
    rows, t = config["vocab_size"], config["train"]["seq_len"]
    cdf = np.cumsum(1.0 / np.arange(1, rows + 1, dtype=np.float64))
    cdf = jnp.asarray((cdf / cdf[-1]).astype(np.float32))
    half = t // 2 + 1

    def draw(k, n):
        ids = jnp.searchsorted(cdf, jax.random.uniform(k, (n, half)))
        ids = jnp.minimum(ids, rows - 1).astype(jnp.int32)
        return jnp.concatenate([ids, ids[:, :t + 1 - half]], axis=1)

    kt, kv = jax.random.split(jax.random.key(config["data_seed"]))
    return draw(kt, config["train"]["n_train"]), draw(kv, config["train"]["n_val"])


# -------------------------------------------------------------------- layers
def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def conv(x, w):
    """Causal depthwise: ``y_t = sum_i w[i] x[t - K + 1 + i]``."""
    kernel, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((kernel - 1, x.shape[1]), x.dtype), x])
    return sum(w[i] * padded[i:i + t] for i in range(kernel))


def delta_rule(q, k, v, a, beta):
    """The recurrence, one token at a time: ``q, k, a`` [T, H, d_k], ``v``
    [T, H, d_v], ``beta`` [T, H] -> ``o`` [T, H, d_v]."""
    t, h, dk = q.shape

    def token(state, x):
        qt, kt, vt, at, bt = x
        state = at[:, :, None] * state
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", state, kt))
        state = state + kt[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = -t % RECURRENCE_BLOCK  # steps that leave the state alone
    xs = [jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
          for x, fill in ((q, 0), (k, 0), (v, 0), (a, 1), (beta, 0))]
    xs = tuple(x.reshape((-1, RECURRENCE_BLOCK) + x.shape[1:]) for x in xs)
    state = jnp.zeros((h, dk, v.shape[-1]), q.dtype)
    _, out = jax.lax.scan(block, state, xs)
    return out.reshape((t + pad, h, -1))[:t]


def kda(x, p, config):
    linear = config["linear_attn_config"]
    h, dk = linear["num_heads"], linear["head_dim"]
    heads = lambda y: y.reshape(x.shape[0], h, dk)
    q = l2norm(heads(jax.nn.silu(conv(x @ p["wq"], p["conv_q"]))))
    k = l2norm(heads(jax.nn.silu(conv(x @ p["wk"], p["conv_k"]))))
    v = heads(jax.nn.silu(conv(x @ p["wv"], p["conv_v"])))
    a = jnp.exp(-jnp.exp(p["A_log"])[None, :, None] * heads(
        jax.nn.softplus(x @ p["wa1"] @ p["wa2"] + p["dt_bias"])))
    beta = jax.nn.sigmoid(x @ p["wb"])
    o = delta_rule(q, k, v, a, beta) / dk ** 0.5
    gate = jax.nn.sigmoid(heads(x @ p["wg1"] @ p["wg2"]))
    o = rmsnorm(o, p["o_norm"], config["rms_norm_eps"]) * gate
    return o.reshape(x.shape[0], h * dk) @ p["wo"]


def mla(x, p, config):
    t, h = x.shape[0], config["num_attention_heads"]
    dn, dr, dv = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    rank = config["kv_lora_rank"]
    q = (x @ p["wq"]).reshape(t, h, dn + dr)
    kva = x @ p["wkva"]
    c = rmsnorm(kva[:, :rank], p["kv_norm"], config["rms_norm_eps"])
    k_pe = kva[:, rank:]
    kvb = (c @ p["wkvb"]).reshape(t, h, dn + dv)
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qh, kvh):
        kh = jnp.concatenate([kvh[:, :dn], k_pe], axis=1)
        scores = qh @ kh.T / (dn + dr) ** 0.5
        return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1) @ kvh[:, dn:]

    out = jax.lax.map(lambda a: head(*a), (q.swapaxes(0, 1), kvb.swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(t, h * dv) @ p["wo"]


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def experts(x, p, config, held=None):
    """The share of the expert layer that holds ``held`` (global expert
    ids, in the order of the leaves' leading axis); default the
    configuration's. The held experts one after the other, each over every
    token with its weight or zero."""
    held = config["cut"]["experts_held"] if held is None else held
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + p["router_bias"], config["num_experts_per_token"])
    s_chosen = jnp.take_along_axis(s, chosen, axis=1)
    weight = s_chosen / s_chosen.sum(-1, keepdims=True) * config["routed_scaling_factor"]
    ids = jnp.asarray(held, chosen.dtype)[:, None, None]
    w = jnp.where(chosen[None] == ids, weight[None], 0.0).sum(-1).astype(x.dtype)  # [held, T]

    def add_expert(y, e):
        w_e, gate, up, down = e
        return y + w_e[:, None] * swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(
        add_expert, swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"]),
        (w, p["e_gate"], p["e_up"], p["e_down"]))
    return y


def layer(h, p, mixer, ffn, config):
    eps = config["rms_norm_eps"]
    h = h + (kda if mixer == "kda" else mla)(rmsnorm(h, p["norm1"], eps), p, config)
    x = rmsnorm(h, p["norm2"], eps)
    if ffn == "dense":
        return h + swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
    return h + experts(x, p, config)


def head_loss(h, norm_f, head, tokens, config):
    """Final norm, head, mean cross-entropy of ``tokens[1:]``."""
    logits = (rmsnorm(h, norm_f, config["rms_norm_eps"]) @ head).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


def loss_fn(params, tokens, config):
    """Mean next-token cross-entropy of ``tokens`` i32[T + 1]."""
    h = params["embed"][tokens[:-1]]
    for i, (mixer, ffn) in enumerate(layer_kinds(config)):
        h = jax.checkpoint(functools.partial(layer, mixer=mixer, ffn=ffn, config=config))(
            h, params["l%d" % i])
    return head_loss(h, params["norm_f"], params["head"], tokens, config)


# ------------------------------------------------------------------ training
_LANE_FUNCTIONS = {}
#: the check compiles beside the program it checks: quickly, not for speed
_COMPILE = {"exec_time_optimization_effort": -1.0}


def lane_functions(config, dtype):
    """``(init, step, held_out)`` of a lane, made once per configuration
    and precision: ``init(init_scale) -> (p, v)``, ``step(p, v, t, lr,
    momentum, wd) -> (p, v)``, ``held_out(p) -> loss``.

    The gradient is ``loss_fn``'s by the chain rule, a layer at a time
    (``jax.vjp`` of the same ``layer`` and ``head_loss``, each layer's
    input kept and its inside recomputed), and a layer's parameters and
    momentum are updated as soon as its gradient is known: layers of one
    kind share one compiled function, and the lane's old state makes room
    for the new a layer at a time."""
    key = (json.dumps(config, sort_keys=True), jnp.dtype(dtype).name)
    if key in _LANE_FUNCTIONS:
        return _LANE_FUNCTIONS[key]
    train, val = dataset(config)
    n_train = config["train"]["n_train"]
    kinds = layer_kinds(config)
    jit = functools.partial(jax.jit, compiler_options=_COMPILE)

    def back(h, p, dh, mixer, ffn):
        _, pull = jax.vjp(functools.partial(layer, mixer=mixer, ffn=ffn, config=config), h, p)
        return pull(dh)

    forward = {k: jit(functools.partial(layer, mixer=k[0], ffn=k[1], config=config))
               for k in set(kinds)}
    backward = {k: jit(functools.partial(back, mixer=k[0], ffn=k[1])) for k in set(kinds)}
    head = jit(functools.partial(head_loss, config=config))
    head_grad = jit(jax.grad(functools.partial(head_loss, config=config), argnums=(0, 1, 2)))
    embed_grad = jit(lambda like, ids, dh: jnp.zeros_like(like).at[ids].add(dh))

    # on the chip a leaf's old value and momentum make room for the new;
    # the CPU cannot donate and would warn
    @functools.partial(
        jit, donate_argnums=(0, 1) if jax.default_backend() != "cpu" else ())
    def update(p, v, g, lr, momentum, wd):
        v = jax.tree.map(lambda vi, gi, pi: (momentum * vi + gi + wd * pi).astype(dtype),
                         v, g, p)
        return jax.tree.map(lambda pi, vi: (pi - lr * vi).astype(dtype), p, v), v

    @jit
    def init(init_scale):
        p = init_params(config, jax.random.key(config["data_seed"] + 1), init_scale, dtype)
        return p, jax.tree.map(jnp.zeros_like, p)

    def hidden(p, tokens):
        """The input of every layer, and the last one's output."""
        hs = [p["embed"][tokens[:-1]]]
        for i, kind in enumerate(kinds):
            hs.append(forward[kind](hs[-1], p["l%d" % i]))
        return hs

    def step(p, v, t, lr, momentum, wd):
        tokens = train[t % n_train]
        hs = hidden(p, tokens)
        dh, g_norm, g_head = head_grad(hs[-1], p["norm_f"], p["head"], tokens)
        new_p, new_v = {}, {}

        def move(name, g):
            new_p[name], new_v[name] = update(p[name], v[name], g, lr, momentum, wd)

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
        for k in set(kinds):
            p = {n: sds(shape) for n, shape in layer_shapes(config, *k).items()}
            forward[k].lower(h, p).compile()
            backward[k].lower(h, p, h).compile()
            update.lower(p, p, p, scalar, scalar, scalar).compile()
        for leaf in leaves.values():
            update.lower(leaf, leaf, leaf, scalar, scalar, scalar).compile()
        head.lower(h, leaves["norm_f"], leaves["head"], tokens).compile()
        head_grad.lower(h, leaves["norm_f"], leaves["head"], tokens).compile()
        embed_grad.lower(leaves["embed"], sds((t,), jnp.int32), h).compile()

    step.compile_ahead = compile_ahead
    _LANE_FUNCTIONS[key] = init, step, held_out
    return init, step, held_out


def compile_ahead(config):
    """Compile the lane's functions without running them. Where the process
    keeps a compile cache on disk, the comparison that comes after the
    window finds them there: the benchmark's builder calls this beside the
    program's own, much longer compilation (``configs/kimi-linear-sgd.py``)."""
    with jax.default_matmul_precision("highest"):
        lane_functions(config, jnp.float32)[1].compile_ahead()


def reference_losses(config, hparams, marks, dtype=jnp.float32):
    """``f[len(marks)]``: the held-out loss after each mark of cumulative
    steps of the lane trained from ``hparams = (lr, momentum, weight_decay,
    init_scale)``. ``v <- m v + g + wd p; p <- p - lr v``; step ``t``
    trains on sequence ``t mod n_train``. A loss that is no number (the
    training diverged) is infinity."""
    lr, momentum, wd, init_scale = (jnp.asarray(x, jnp.float32) for x in hparams)
    with jax.default_matmul_precision("highest"):
        init, step, held_out = lane_functions(config, dtype)
        (p, v), done, out = init(init_scale), 0, []
        for mark in marks:
            for t in range(done, mark):
                p, v = step(p, v, t, lr, momentum, wd)
            done = mark
            loss = float(held_out(p))
            # a lane whose training diverged reports the worst loss
            out.append(np.inf if np.isnan(loss) else loss)
    return np.asarray(out, np.float64)


def sample_lanes(records, seed):
    """From a seeded sweep of the window: the lane that reached the top
    rung, with its loss at every rung, and a seeded one of those that ran
    the first rung only. ``[(hyperparameters, {steps: reported loss})]``."""
    rng = np.random.default_rng(seed)
    rec = records[rng.integers(len(records))]
    lane_of = list(zip(rec["bracket"].tolist(), rec["lane"].tolist()))
    rungs = {}
    for row, lane in enumerate(lane_of):
        rungs.setdefault(lane, {})[int(round(rec["budget"][row]))] = rec["loss"][row]
    deepest = max(len(r) for r in rungs.values())
    top = next(lane for lane, r in rungs.items() if len(r) == deepest)
    once = [lane for lane, r in rungs.items() if len(r) == 1 and lane != top]
    picked = [top] + ([once[rng.integers(len(once))]] if once else [])
    return [([rec["config"][n][lane_of.index(lane)] for n in HPARAMS], rungs[lane])
            for lane in picked]


def gap(got, ref):
    """|got - ref| / (1 + |ref|); a crash or an overflow is sound only
    where the reference has the same."""
    if np.isfinite(got) and np.isfinite(ref):
        return abs(got - ref) / (1 + abs(ref))
    same = (np.isnan(got) and np.isnan(ref)) or got == ref
    return 0.0 if same else np.inf


def compare(config, traffic, records, seed, control=False):
    """``[(name, value, limit)]``. With ``control`` the reference computed
    with bfloat16 parameters and momentum stands in the program's place."""
    t0 = time.perf_counter()
    gaps, early = [], []
    for lane, (hparams, reported) in enumerate(sample_lanes(records, seed)):
        marks = sorted(reported)[:TOP_LANE_RUNGS]
        want = reference_losses(config, hparams, marks)
        got = (reference_losses(config, hparams, marks, dtype=jnp.bfloat16)
               if control else [reported[m] for m in marks])
        for rung, (mark, g, w) in enumerate(zip(marks, got, want)):
            gaps.append(gap(g, w))
            if lane == 0 and rung < EARLY_RUNGS:
                early.append(gaps[-1])
            print("kimi-linear-sgd %s: lr %.3g momentum %.3g wd %.3g init %.3g, %d steps: "
                  "%.6f against the reference's %.6f, gap %.3g" % (
                      ("control" if control else "reported",) + tuple(hparams)
                      + (mark, g, w, gaps[-1])))
    print("kimi-linear-sgd reference: %.1f s" % (time.perf_counter() - t0))
    return [
        ("loss_gap_early", float(np.max(early)), LOSS_GAP_EARLY_LIMIT),
        ("loss_gap_max", float(np.max(gaps)), LOSS_GAP_MAX_LIMIT),
    ]
