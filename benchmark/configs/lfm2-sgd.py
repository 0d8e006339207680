"""Builder of the ``lfm2-sgd`` configuration: one chip's share of LFM2-8B-A1B
(gated short convolutions to attention 3 : 1 after a leading dense layer,
sigmoid-routed experts chosen with a bias, the head tied to the embedding) as
a stateless ``eval_fn``, its tokens and its initial-weight key made from the
configuration's data seed, once. The record of a sweep carries the program's
trainer (``lane_change``) for the comparison, as ``ouro-sgd.py``'s does: what
is the same for both (the thread that compiles ahead, the lane's vector from
its hyperparameters) is loaded from that file."""

import importlib.util
import os

import program

KINDS = {"conv": "conv", "full_attention": "attention"}


def _beside(*parts):
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_beside_" + parts[-1].split("-")[0], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_shared = _beside("configs", "ouro-sgd.py")


def lane_config(config):
    """The program's ``Lfm2Config`` from the configuration's file: the
    published widths under their published keys, the cut under ``cut`` and
    the held layers' kinds, the data under ``train``."""
    from hpbandster_tpu.workloads.lfm2 import Lfm2Config

    if config["conv_bias"] or not config["norm_topk_prob"] or not config["use_expert_bias"]:
        raise ValueError("lfm2-sgd: no convolution bias; the top k renormalised; "
                         "a bias that chooses the experts")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("lfm2-sgd: one layer_types entry a layer held")
    if len(config["cut"]["experts_held"]) != config["num_experts"]:
        raise ValueError("lfm2-sgd: num_experts counts the experts held here")
    kinds = tuple((KINDS[kind], "dense" if i < config["num_dense_layers"] else "moe")
                  for i, kind in enumerate(config["layer_types"]))
    return Lfm2Config(
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        conv_kernel=config["conv_L_cache"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["cut"]["router_outputs"],
        num_experts_per_token=config["num_experts_per_tok"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        router_epsilon=config["router_epsilon"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["norm_eps"],
        layer_kinds=kinds,
        experts_held=tuple(config["cut"]["experts_held"]),
        vocab_rows=config["vocab_size"],
        seq_len=config["train"]["seq_len"],
        n_train=config["train"]["n_train"],
        n_val=config["train"]["n_val"],
    )


def build(config, traffic, seed, devices):
    from hpbandster_tpu.workloads.lfm2 import lfm2_space, make_lfm2_eval_fn

    eval_fn = make_lfm2_eval_fn(lane_config(config), data_seed=config["data_seed"])
    ahead = [_shared._ahead(_compile_the_reference, config),
             _shared._ahead(_compile_the_change, eval_fn)]
    one_sweep = program.make_sweep(
        lfm2_space, {"eval_fn": eval_fn}, config, traffic, devices)

    def lane_change(hparams, steps):
        return _shared._lane_change(
            ahead[1]() or _compile_the_change(eval_fn), hparams, steps)

    def sweep(seed):
        raw = one_sweep(seed)
        for compiled in ahead:   # a wait in the first warm-up sweep alone
            compiled()
        extract = raw["extract"]
        raw["extract"] = lambda: dict(extract(), lane_change=lane_change)
        return raw

    return sweep


def _compile_the_reference(config):
    """The plain reference's functions: it takes nothing from the program
    and gives it nothing."""
    _beside("reference", "lfm2-sgd.py").compile_ahead(config)


def _compile_the_change(eval_fn):
    """``(vec f32[4], steps f32[]) -> what the steps changed``, leaf by leaf
    under the names the reference has too; compiled at the compiler's
    quickest effort: it runs once a comparison."""
    import jax
    import jax.numpy as jnp

    return jax.jit(eval_fn.change, compiler_options={"exec_time_optimization_effort": -1.0}
                   ).lower(jax.ShapeDtypeStruct((4,), jnp.float32),
                           jax.ShapeDtypeStruct((), jnp.float32)).compile()
