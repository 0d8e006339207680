"""Builder of the ``olmo-hybrid-sgd`` configuration: one chip's share of
Olmo-Hybrid-7B (one period: three Gated-DeltaNet layers, whose delta rule is
gated once a head, to one full-attention layer without positions; the norm
after the sub-layer) as a stateless ``eval_fn``, its tokens and its
initial-weight key made from the configuration's data seed, once. The record
of a sweep carries the program's trainer (``lane_change``) for the comparison,
as ``ouro-sgd.py``'s does: what is the same for both (the thread that compiles
ahead, the lane's vector from its hyperparameters) is loaded from that file."""

import importlib.util
import os

import program

KINDS = {"linear_attention": "gdn", "full_attention": "gqa"}


def _beside(*parts):
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_beside_" + parts[-1].split("-")[0], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_shared = _beside("configs", "ouro-sgd.py")


def lane_config(config):
    """The program's ``OlmoHybridConfig`` from the configuration's file: the
    published widths under their published keys, the held layers' kinds, the
    vocabulary's slice, the data under ``train``."""
    from hpbandster_tpu.workloads.olmo_hybrid import OlmoHybridConfig

    if config["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("olmo-hybrid-sgd: the full layers carry no positions")
    if config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("olmo-hybrid-sgd: no bias in attention, an untied head")
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("olmo-hybrid-sgd: as many key heads as value heads")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("olmo-hybrid-sgd: one layer_types entry a layer held")
    return OlmoHybridConfig(
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        linear_num_heads=config["linear_num_key_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=config["linear_allow_neg_eigval"],
        intermediate_size=config["intermediate_size"],
        rms_norm_eps=config["rms_norm_eps"],
        layer_kinds=tuple(KINDS[kind] for kind in config["layer_types"]),
        vocab_rows=config["vocab_size"],
        seq_len=config["train"]["seq_len"],
        n_train=config["train"]["n_train"],
        n_val=config["train"]["n_val"],
    )


def build(config, traffic, seed, devices):
    from hpbandster_tpu.workloads.olmo_hybrid import (
        make_olmo_hybrid_eval_fn,
        olmo_hybrid_space,
    )

    eval_fn = make_olmo_hybrid_eval_fn(lane_config(config), data_seed=config["data_seed"])
    ahead = [_shared._ahead(_compile_the_reference, config),
             _shared._ahead(_compile_the_change, eval_fn)]
    one_sweep = program.make_sweep(
        olmo_hybrid_space, {"eval_fn": eval_fn}, config, traffic, devices)

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
    _beside("reference", "olmo-hybrid-sgd.py").compile_ahead(config)


def _compile_the_change(eval_fn):
    """``(vec f32[4], steps f32[]) -> what the steps changed``, leaf by leaf
    under the names the reference has too; compiled at the compiler's
    quickest effort: it runs once a comparison."""
    import jax
    import jax.numpy as jnp

    return jax.jit(eval_fn.change, compiler_options={"exec_time_optimization_effort": -1.0}
                   ).lower(jax.ShapeDtypeStruct((4,), jnp.float32),
                           jax.ShapeDtypeStruct((), jnp.float32)).compile()
