"""The ``lfm2-sgd`` configuration at a size the CPU tests can run: hidden 64,
4 query heads on 2 key/value heads of 16 with a per-head norm, a dense SwiGLU
of 96, 8 experts of 32 top-2 with 4 held, 256 ids, three layers (convolution
and dense, attention, convolution, the last two with experts), 32-token
sequences (query blocks of 16 in the tests). The benchmark owns the reference
and the builder; the tests load both by path (``kimi_small.load``), as
``benchmark/run.py`` does."""

import copy

from kimi_small import (  # noqa: F401
    BENCHMARK, check_the_moe_backward_rule_is_named, load, scatters_and_sorts)

SMALL = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 64, "intermediate_size": 96,
    "layer_types": ["conv", "full_attention", "conv"],
    "moe_intermediate_size": 32, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_dense_layers": 1, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 3, "num_key_value_heads": 2,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 256, "router_epsilon": 1e-6,
    "cut": {"layers": [0, 2, 3], "experts_held": [1, 3, 4, 6], "router_outputs": 8},
    "train": {"seq_len": 32, "n_train": 4, "n_val": 1},
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
