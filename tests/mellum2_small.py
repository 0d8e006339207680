"""The ``mellum2-sgd`` configuration at a size the CPU tests can run: hidden
64, 8 query heads on 2 key/value heads of 16, a window of 8 at 64 tokens
(query blocks of 16 in the tests), 16 experts top-4 with 4 held, the four
layers of the same kinds. The benchmark owns the reference and the builder;
the tests load both by path (``kimi_small.load``), as ``benchmark/run.py``
does."""

import copy

from kimi_small import (  # noqa: F401
    BENCHMARK, check_the_moe_backward_rule_is_named, load, scatters_and_sorts)

SMALL = {
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention",
                    "full_attention"],
    "mlp_layer_types": ["sparse"] * 4,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_experts": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 4, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 100, "factor": 16,
            "original_max_position_embeddings": 64, "beta_fast": 4, "beta_slow": 1,
            "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 100}},
    "sliding_window": 8, "vocab_size": 96,
    "cut": {"layers": [0, 1, 2, 3], "experts_held": [3, 7, 8, 12],
            "router_outputs": 16},
    "train": {"seq_len": 64, "n_train": 4, "n_val": 1},
    "eta": 3, "min_budget": 1, "max_budget": 9, "data_seed": 0,
}


def small(**changes):
    config = copy.deepcopy(SMALL)
    for key, value in changes.items():
        if isinstance(value, dict):
            config[key].update(value)
        else:
            config[key] = value
    return config
