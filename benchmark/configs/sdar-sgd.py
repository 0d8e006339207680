"""Builder of the ``sdar-sgd`` configuration: one chip's share of
SDAR-30B-A3B-Chat (grouped-query attention under a per-head norm, 128
softmax-routed experts, trained by masked diffusion over blocks: every
sequence through the layers as a clean and a masked copy under a rule of
sight that is not causal, a weighted cross-entropy on the masked rows) as a
stateless ``eval_fn``, its tokens, its masks and noise levels and its
initial-weight key made from the configuration's data seed, once. The record
of a sweep carries the program's trainer (``lane_change``) for the
comparison, as ``lfm2-sgd.py``'s does, and the program's forward pass as far
as the masked rows' last states (``masked_states``), which the comparison
reads the rule of sight off: what is the same for both cells (the thread
that compiles ahead, the lane's vector from its hyperparameters) is loaded
from beside this file."""

import importlib.util
import os

import program


def _beside(*parts):
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_beside_" + parts[-1].split("-")[0], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_shared = _beside("configs", "ouro-sgd.py")


def lane_config(config):
    """The program's ``SdarConfig`` from the configuration's file: the
    published widths under their published keys, the cut under ``cut``, the
    data and the noise under ``train``."""
    from hpbandster_tpu.workloads.sdar import SdarConfig

    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise ValueError("sdar-sgd: every layer has experts")
    if (config["rope_scaling"] is not None or config["use_sliding_window"]
            or config["tie_word_embeddings"] or config["attention_bias"]):
        raise ValueError("sdar-sgd: plain RoPE, no window, an untied head, no bias")
    if not config["norm_topk_prob"]:
        raise ValueError("sdar-sgd: the top k renormalised")
    if len(config["cut"]["experts_held"]) != config["num_experts"]:
        raise ValueError("sdar-sgd: num_experts counts the experts held here")
    return SdarConfig(
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["cut"]["router_outputs"],
        num_experts_per_token=config["num_experts_per_tok"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        block_length=config["train"]["block_length"],
        noise_floor=config["train"]["noise_floor"],
        num_layers=config["num_hidden_layers"],
        experts_held=tuple(config["cut"]["experts_held"]),
        vocab_rows=config["vocab_size"],
        seq_len=config["train"]["seq_len"],
        n_train=config["train"]["n_train"],
        n_val=config["train"]["n_val"],
    )


def build(config, traffic, seed, devices):
    from hpbandster_tpu.workloads.sdar import make_sdar_eval_fn, sdar_space

    eval_fn = make_sdar_eval_fn(lane_config(config), data_seed=config["data_seed"])
    ahead = [_shared._ahead(_compile_the_reference, config),
             _shared._ahead(_compile_the_change, eval_fn),
             _shared._ahead(_compile_the_states, config)]
    one_sweep = program.make_sweep(
        sdar_space, {"eval_fn": eval_fn}, config, traffic, devices)

    def lane_change(hparams, steps):
        return _shared._lane_change(
            ahead[1]() or _compile_the_change(eval_fn), hparams, steps)

    def masked_states(hparams, tokens, mask):
        import numpy as np

        states = ahead[2]() or _compile_the_states(config)
        return states(np.float32(hparams[3]), tokens, mask)

    def sweep(seed):
        raw = one_sweep(seed)
        for compiled in ahead:   # a wait in the first warm-up sweep alone
            compiled()
        extract = raw["extract"]
        raw["extract"] = lambda: dict(
            extract(), lane_change=lane_change, masked_states=masked_states)
        return raw

    return sweep


def _compile_the_reference(config):
    """The plain reference's functions: it takes nothing from the program
    and gives it nothing."""
    _beside("reference", "sdar-sgd.py").compile_ahead(config)


def _compile_the_change(eval_fn):
    """``(vec f32[4], steps f32[]) -> what the steps changed``, the layers'
    stacked leaves taken apart into ``l<i>``, the names the reference has;
    compiled at the compiler's quickest effort: it runs once a comparison."""
    import jax
    import jax.numpy as jnp

    def change(vec, steps):
        tree = dict(eval_fn.change(vec, steps))
        stacked = tree.pop("layers")
        for i in range(jax.tree.leaves(stacked)[0].shape[0]):
            tree["l%d" % i] = jax.tree.map(lambda x: x[i], stacked)
        return tree

    return jax.jit(change, compiler_options={"exec_time_optimization_effort": -1.0}).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32), jax.ShapeDtypeStruct((), jnp.float32)).compile()


def _compile_the_states(config):
    """``(init scale f32[], tokens i32[S], mask bool[S]) -> f32[S, D]``: the
    last layer's output for the masked rows, by the program's forward pass
    (``sdar_forward``: the trainer's own) at the lane's initial weights;
    compiled at the compiler's quickest effort: a comparison runs it four
    times."""
    import jax
    import jax.numpy as jnp

    from hpbandster_tpu.workloads.sdar import init_sdar_params, sdar_forward

    cfg = lane_config(config)
    s = cfg.seq_len

    def states(init_scale, tokens, mask):
        params = init_sdar_params(jax.random.key(config["data_seed"] + 1), cfg, init_scale)
        seq = {"tokens": tokens, "mask": mask, "weight": mask.astype(jnp.float32)}
        return sdar_forward(params, seq, cfg)[2][-1][s:]

    return jax.jit(states, compiler_options={"exec_time_optimization_effort": -1.0}).lower(
        jax.ShapeDtypeStruct((), jnp.float32), jax.ShapeDtypeStruct((s,), jnp.int32),
        jax.ShapeDtypeStruct((s,), jnp.bool_)).compile()
