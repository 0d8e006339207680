"""Builder of the ``kimi-linear-sgd`` configuration: one chip's share of a
Kimi-Linear block as a stateless ``eval_fn``, its tokens and its
initial-weight key made from the configuration's data seed, once."""

import program


def lane_config(config):
    """The program's ``KimiLinearConfig`` from the configuration's file:
    the published widths under their published keys, the cut under
    ``cut`` and the data under ``train``."""
    from hpbandster_tpu.workloads.kimi_linear import KimiLinearConfig

    linear = config["linear_attn_config"]
    assert linear["num_heads"] == config["num_attention_heads"]
    kinds = tuple(
        ("kda" if n in linear["kda_layers"] else "mla",
         "dense" if n <= config["first_k_dense_replace"] else "moe")
        for n in config["cut"]["layers"])
    return KimiLinearConfig(
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        kda_head_dim=linear["head_dim"],
        short_conv_kernel_size=linear["short_conv_kernel_size"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["cut"]["router_outputs"],
        num_experts_per_token=config["num_experts_per_token"],
        routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["rms_norm_eps"],
        layer_kinds=kinds,
        experts_held=tuple(config["cut"]["experts_held"]),
        vocab_rows=config["vocab_size"],
        seq_len=config["train"]["seq_len"],
        n_train=config["train"]["n_train"],
        n_val=config["train"]["n_val"],
    )


def build(config, traffic, seed, devices):
    from hpbandster_tpu.workloads.kimi_linear import (
        kimi_linear_space,
        make_kimi_linear_eval_fn,
    )

    _compile_the_check_ahead(config)
    eval_fn = make_kimi_linear_eval_fn(lane_config(config), data_seed=config["data_seed"])
    return program.make_sweep(
        kimi_linear_space, {"eval_fn": eval_fn}, config, traffic, devices)


def _compile_the_check_ahead(config):
    """A cold run compiles the program for two minutes on a few of the
    host's cores; the plain reference (twenty seconds of compilation, after
    the window) is compiled meanwhile on another thread into the same
    compile cache on disk. It takes nothing from the program and gives it
    nothing; a failure here is the comparison's to report when it compiles
    for itself."""
    import importlib.util
    import os
    import threading

    def work():
        try:
            path = os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "reference", "kimi-linear-sgd.py")
            spec = importlib.util.spec_from_file_location("bench_reference_ahead", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            module.compile_ahead(config)
        except Exception:  # noqa: BLE001 - see the docstring
            pass

    threading.Thread(target=work, daemon=True).start()
