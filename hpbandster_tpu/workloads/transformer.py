"""Transformer hyperparameter-search workload — attention on the MXU.

A decoder-only transformer (pre-LN, causal MHA + MLP blocks) trained on a
synthetic COPY task: each sequence is ``[prefix, SEP, prefix]`` with the
prefix drawn uniformly from ``V^P`` — the second half is predictable only
by attending back across the separator (the classic induction behavior),
never by position-local statistics, and the prefix space is astronomically
larger than any training set so memorization cannot substitute for the
attention circuit. Validation prefixes are disjoint draws: accuracy on the
copied half is a genuine generalization axis.

TPU-first choices (same regime as ``workloads/cnn.py``):

* every matmul — QKV/out projections, attention scores and mixing, the MLP,
  the vocabulary head — runs in **bfloat16** operands with float32
  accumulation on the MXU; parameters, layernorms, softmax and the
  optimizer state stay float32.
* head and model dims are lane-friendly (``d_model`` 64/128, ``d_ff = 4x``).
* budget = SGD steps through the shared ``momentum_sgd_train``
  ``lax.while_loop`` (traced bound: one compilation serves a whole
  successive-halving budget ladder).

Reference analog: the reference has no transformer workload — its model
families are the MNIST MLP/Keras/PyTorch example workers (SURVEY.md §2
"examples"); this rung extends the same ``eval_fn`` contract to the
attention family the MXU is built for.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from hpbandster_tpu.space import ConfigurationSpace, UniformFloatHyperparameter
from hpbandster_tpu.workloads.train import momentum_sgd_train

__all__ = [
    "TransformerConfig",
    "TRANSFORMER_TARGET_VAL_ACCURACY",
    "transformer_space",
    "decode_transformer_hparams",
    "init_transformer_params",
    "transformer_forward",
    "transformer_forward_seq_parallel",
    "make_copy_dataset",
    "make_transformer_eval_fn",
    "make_transformer_error_fn",
    "make_transformer_accuracy_fn",
]

#: documented generalization target for the default config (data_seed 0,
#: budget = 81 SGD steps): chance on the copied half is 1/32 ~= 0.031.
#: Calibrated the same way CNN_TARGET_VAL_ACCURACY was — measured over 12
#: random hyperparameter draws at budget 81 on the documented config (CPU
#: backend, round 5): sorted val accuracies [0.032 .. 0.132, 0.395] —
#: most draws stall at chance; the best starts learning the attention
#: copy circuit (81 steps is deliberately tight for this config: the
#: budget axis stays informative instead of saturating, the same design
#: choice as the CNN rung's noise ceiling). Target = just under the
#: measured best-of-12 (the CNN convention), ~11x chance.
TRANSFORMER_TARGET_VAL_ACCURACY = 0.35


class TransformerConfig(NamedTuple):
    vocab: int = 32          # payload tokens; id ``vocab`` is the separator
    prefix_len: int = 31     # sequence = prefix + SEP + prefix (len 2P+1)
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256          # 4 * d_model
    n_train: int = 512
    n_val: int = 256
    batch_size: int = 128

    @property
    def seq_len(self) -> int:
        return 2 * self.prefix_len + 1


def transformer_space(seed=None) -> ConfigurationSpace:
    """lr (log), momentum, weight decay (log), init scale (log) — the same
    4-knob space as the MLP/CNN rungs, so sweeps compare across families."""
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


def decode_transformer_hparams(vec: jax.Array):
    """Unit-cube vector -> (lr, momentum, weight_decay, init_scale);
    mirrors ``transformer_space()``'s codec."""
    lr = 10.0 ** (-4.0 + 4.0 * vec[0])
    momentum = 0.99 * vec[1]
    wd = 10.0 ** (-7.0 + 5.0 * vec[2])
    init_scale = 10.0 ** (-1.0 + 2.0 * vec[3])
    return lr, momentum, wd, init_scale


def _dense_init(key, d_in, d_out, scale):
    w = scale * (2.0 / d_in) ** 0.5 * jax.random.normal(key, (d_in, d_out))
    return w.astype(jnp.float32)


def init_transformer_params(key: jax.Array, cfg: TransformerConfig,
                            init_scale) -> dict:
    n_tok = cfg.vocab + 1  # + separator
    keys = jax.random.split(key, 3 + 6 * cfg.n_layers)
    params = {
        "tok_emb": (0.02 * init_scale * jax.random.normal(
            keys[0], (n_tok, cfg.d_model))).astype(jnp.float32),
        "pos_emb": (0.02 * init_scale * jax.random.normal(
            keys[1], (cfg.seq_len - 1, cfg.d_model))).astype(jnp.float32),
        "head": _dense_init(keys[2], cfg.d_model, n_tok, init_scale),
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        "ln_f_b": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    for i in range(cfg.n_layers):
        k = keys[3 + 6 * i: 3 + 6 * (i + 1)]
        params[f"l{i}"] = {
            "wq": _dense_init(k[0], cfg.d_model, cfg.d_model, init_scale),
            "wk": _dense_init(k[1], cfg.d_model, cfg.d_model, init_scale),
            "wv": _dense_init(k[2], cfg.d_model, cfg.d_model, init_scale),
            "wo": _dense_init(k[3], cfg.d_model, cfg.d_model, init_scale),
            "w1": _dense_init(k[4], cfg.d_model, cfg.d_ff, init_scale),
            "w2": _dense_init(k[5], cfg.d_ff, cfg.d_model, init_scale),
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "ln1_b": jnp.zeros((cfg.d_model,), jnp.float32),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32),
            "ln2_b": jnp.zeros((cfg.d_model,), jnp.float32),
        }
    return params


def _ln(x, g, b):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return g * (x - m) * jax.lax.rsqrt(v + 1e-6) + b


def _mm(a, b):
    """bf16 operands, f32 accumulation — the MXU-native regime (XLA's TPU
    lowering accumulates bf16 GEMMs in f32 on the systolic array)."""
    return jnp.matmul(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )


def _dense_attention(q, k, v, scale):
    """Causal attention on one device: ``[T, H, dh]`` blocks, bf16 score/
    mixing GEMMs with f32 accumulation, softmax in f32. Same tile math
    and mask constant as the ring path, so the two attention backends are
    drop-in twins behind :func:`_layer`."""
    t = q.shape[0]
    s = jnp.einsum(
        "qhd,khd->hqk", q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -1e30)
    att = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "hqk,khd->qhd", att.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )


def _layer(x, p, n_heads, attn_fn):
    """One pre-LN block: attention (via ``attn_fn(q, k, v) -> [T, H, dh]``,
    dense or ring) + MLP. The ONE definition both the local and the
    sequence-parallel forwards share — any change here changes both."""
    t, d = x.shape
    dh = d // n_heads
    h = _ln(x, p["ln1"], p["ln1_b"])
    q = _mm(h, p["wq"]).reshape(t, n_heads, dh)
    k = _mm(h, p["wk"]).reshape(t, n_heads, dh)
    v = _mm(h, p["wv"]).reshape(t, n_heads, dh)
    x = x + _mm(attn_fn(q, k, v).reshape(t, d), p["wo"])
    h = _ln(x, p["ln2"], p["ln2_b"])
    return x + _mm(jax.nn.relu(_mm(h, p["w1"])), p["w2"])


def _forward_impl(params, x, cfg: TransformerConfig, attn_fn):
    for i in range(cfg.n_layers):
        x = _layer(x, params[f"l{i}"], cfg.n_heads, attn_fn)
    x = _ln(x, params["ln_f"], params["ln_f_b"])
    return _mm(x, params["head"])


def transformer_forward(params: dict, tokens: jax.Array,
                        cfg: TransformerConfig) -> jax.Array:
    """tokens: i32[T] (T = seq_len - 1 teacher-forced inputs) ->
    logits f32[T, vocab+1]. Batched via vmap by the callers."""
    dh = cfg.d_model // cfg.n_heads
    x = params["tok_emb"][tokens] + params["pos_emb"]
    return _forward_impl(
        params, x, cfg,
        lambda q, k, v: _dense_attention(q, k, v, dh ** -0.5),
    )


def transformer_forward_seq_parallel(
    params: dict, tokens: jax.Array, cfg: TransformerConfig, axis_name: str
) -> jax.Array:
    """Long-context twin of :func:`transformer_forward` — call inside a
    ``shard_map`` whose ``axis_name`` shards the SEQUENCE axis.

    ``tokens``: this shard's slice, i32[T_blk]. Everything per-position
    (embeddings, layernorms, MLP, head) runs locally on the shard; only
    attention is global, and it runs as exact ring attention
    (:func:`~hpbandster_tpu.ops.ring_attention.ring_attention_block`):
    K/V blocks rotate around the mesh ring while queries stay resident,
    so a sequence P× longer than one device's memory trains with the
    identical math (parity pinned in tests/test_transformer_workload.py).
    """
    from hpbandster_tpu.ops.ring_attention import ring_attention_block

    i = jax.lax.axis_index(axis_name)
    t_blk = tokens.shape[0]
    dh = cfg.d_model // cfg.n_heads
    pos = i * t_blk + jnp.arange(t_blk)
    x = params["tok_emb"][tokens] + params["pos_emb"][pos]
    return _forward_impl(
        params, x, cfg,
        lambda q, k, v: ring_attention_block(
            q, k, v, axis_name, causal=True, scale=dh ** -0.5
        ),
    )


def make_copy_dataset(key: jax.Array, cfg: TransformerConfig):
    """``[prefix, SEP, prefix]`` sequences; train/val prefixes are disjoint
    draws from a space of ``vocab^prefix_len`` (memorization-proof).

    Returns ``((x_tr, y_tr), (x_val, y_val), loss_mask)`` where ``x`` is the
    teacher-forced input ``seq[:-1]``, ``y`` is ``seq[1:]``, and
    ``loss_mask`` (f32[T]) selects the COPIED half — the only positions
    whose prediction measures the attention circuit rather than unigram
    noise."""
    kt, kv = jax.random.split(key)

    def draw(k, n):
        prefix = jax.random.randint(k, (n, cfg.prefix_len), 0, cfg.vocab)
        sep = jnp.full((n, 1), cfg.vocab, prefix.dtype)
        seq = jnp.concatenate([prefix, sep, prefix], axis=1)
        return seq[:, :-1], seq[:, 1:]

    train = draw(kt, cfg.n_train)
    val = draw(kv, cfg.n_val)
    t = cfg.seq_len - 1
    # positions >= prefix_len predict [SEP-successor ... last copy token]:
    # exactly the copied half (the SEP position itself predicts the first
    # copied token, which IS attention-predictable)
    loss_mask = (jnp.arange(t) >= cfg.prefix_len).astype(jnp.float32)
    return train, val, loss_mask


def _masked_xent(params, xb, yb, cfg, mask):
    logits = jax.vmap(lambda s: transformer_forward(params, s, cfg))(xb)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, yb[..., None], axis=-1)[..., 0]
    return (nll * mask).sum() / (mask.sum() * xb.shape[0])


def _train_transformer(vec, budget, train, cfg, init_key, mask):
    hp = decode_transformer_hparams(vec)
    params = init_transformer_params(init_key, cfg, hp[3])

    def loss_fn(p, xb, yb):
        return _masked_xent(p, xb, yb, cfg, mask)

    return momentum_sgd_train(
        params, hp[0], hp[1], hp[2], train,
        jnp.asarray(budget, jnp.float32), loss_fn,
        cfg.batch_size, cfg.n_train,
    )


def make_transformer_eval_fn(cfg: TransformerConfig = TransformerConfig(),
                             data_seed: int = 0):
    """``eval_fn(config_vec, budget) -> masked val cross-entropy`` —
    jittable, VmapBackend/FusedBOHB-compatible; budget = SGD steps."""
    train, val, mask = make_copy_dataset(jax.random.key(data_seed), cfg)
    init_key = jax.random.key(data_seed + 1)

    def eval_fn(vec: jax.Array, budget) -> jax.Array:
        params = _train_transformer(vec, budget, train, cfg, init_key, mask)
        return _masked_xent(params, val[0], val[1], cfg, mask)

    return eval_fn


def _masked_accuracy(params, x, y, cfg, mask):
    logits = jax.vmap(lambda s: transformer_forward(params, s, cfg))(x)
    hit = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
    return (hit * mask).sum() / (mask.sum() * x.shape[0])


def make_transformer_error_fn(cfg: TransformerConfig = TransformerConfig(),
                              data_seed: int = 0):
    """``eval_fn(config_vec, budget) -> 1 - copied-half val accuracy`` —
    the generalization twin (teacher/CNN convention: HPO loss reads as
    accuracy progress against ``TRANSFORMER_TARGET_VAL_ACCURACY``)."""
    train, val, mask = make_copy_dataset(jax.random.key(data_seed), cfg)
    init_key = jax.random.key(data_seed + 1)

    def eval_fn(vec: jax.Array, budget) -> jax.Array:
        params = _train_transformer(vec, budget, train, cfg, init_key, mask)
        return 1.0 - _masked_accuracy(params, val[0], val[1], cfg, mask)

    return eval_fn


def make_transformer_accuracy_fn(
        cfg: TransformerConfig = TransformerConfig(), data_seed: int = 0):
    """``acc_fn(config_vec, budget) -> (train_acc, val_acc)`` on the copied
    half — analysis twin for tests/calibration."""
    train, val, mask = make_copy_dataset(jax.random.key(data_seed), cfg)
    init_key = jax.random.key(data_seed + 1)

    def acc_fn(vec: jax.Array, budget):
        params = _train_transformer(vec, budget, train, cfg, init_key, mask)
        return (
            _masked_accuracy(params, train[0], train[1], cfg, mask),
            _masked_accuracy(params, val[0], val[1], cfg, mask),
        )

    return acc_fn
