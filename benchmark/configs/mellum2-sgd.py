"""Builder of the ``mellum2-sgd`` configuration: one chip's share of a
Mellum2 block as a stateless ``eval_fn``, its tokens and its initial-weight
key made from the configuration's data seed, once."""

import program

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def lane_config(config):
    """The program's ``Mellum2Config`` from the configuration's file: the
    published widths under their published keys, the cut under ``cut`` and
    the data under ``train``."""
    from hpbandster_tpu.workloads.mellum2 import Mellum2Config

    rope = config["rope_parameters"]
    window, full = rope["sliding_attention"], rope["full_attention"]
    if (window["rope_type"], full["rope_type"]) != ("default", "yarn"):
        raise ValueError("mellum2-sgd: window layers take plain RoPE and full "
                         "layers YaRN; rope_parameters has %r" % rope)
    if window["rope_theta"] != full["rope_theta"]:
        raise ValueError("mellum2-sgd: one rope_theta for both kinds of layer")
    if set(config["mlp_layer_types"]) != {"sparse"} or not config["norm_topk_prob"]:
        raise ValueError("mellum2-sgd: every layer is sparse and the top k is renormalised")
    return Mellum2Config(
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts_per_token=config["num_experts_per_tok"],
        sliding_window=config["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original_max_position=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=full["attention_factor"],
        rms_norm_eps=config["rms_norm_eps"],
        layer_kinds=tuple(KINDS[kind] for kind in config["layer_types"]),
        experts_held=tuple(config["cut"]["experts_held"]),
        router_outputs=config["cut"]["router_outputs"],
        vocab_rows=config["vocab_size"],
        seq_len=config["train"]["seq_len"],
        n_train=config["train"]["n_train"],
        n_val=config["train"]["n_val"],
    )


def build(config, traffic, seed, devices):
    from hpbandster_tpu.workloads.mellum2 import make_mellum2_eval_fn, mellum2_space

    _compile_the_check_ahead(config)
    eval_fn = make_mellum2_eval_fn(lane_config(config), data_seed=config["data_seed"])
    return program.make_sweep(
        mellum2_space, {"eval_fn": eval_fn}, config, traffic, devices)


def _compile_the_check_ahead(config):
    """A cold run compiles the program for a minute or more on a few of the
    host's cores; the plain reference is compiled meanwhile on another
    thread into the same compile cache on disk (``kimi-linear-sgd.py``'s
    way). It takes nothing from the program and gives it nothing; a failure
    here is the comparison's to report when it compiles for itself."""
    import importlib.util
    import os
    import threading

    def work():
        try:
            path = os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "reference", "mellum2-sgd.py")
            spec = importlib.util.spec_from_file_location("bench_reference_ahead", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            module.compile_ahead(config)
        except Exception:  # noqa: BLE001 - see the docstring
            pass

    threading.Thread(target=work, daemon=True).start()
