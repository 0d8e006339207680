"""The ``laguna-xs2-sgd`` configuration at a size the CPU tests can run:
hidden 64, 2 key/value heads of 16 with 6 query heads in a full layer (3 a
key/value head: no power of two, as the published 6) and 8 in a window layer,
a window of 8 at 64 tokens (query blocks of 16 in the tests), half of a full
layer's head rotated under a YaRN ramp, a gate a head, a dense SwiGLU of 128
first, then 16 sigmoid experts top-4 with 4 held beside a shared one; the
five layers of the same kinds. The benchmark owns the reference and the
builder; the tests load both by path (``kimi_small.load``), as
``benchmark/run.py`` does."""

import copy

from kimi_small import (  # noqa: F401
    BENCHMARK, check_the_moe_backward_rule_is_named, load, scatters_and_sorts)

SMALL = {
    "attention_bias": False, "gating": True, "head_dim": 16, "hidden_size": 64,
    "intermediate_size": 128,
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "moe_apply_router_weight_on_input": False,
    "moe_intermediate_size": 32, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads": 6, "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "num_experts": 4, "num_experts_per_tok": 4, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 100, "factor": 64,
            "original_max_position_embeddings": 32, "beta_fast": 4, "beta_slow": 1,
            "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 100,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 32},
    "shared_expert_intermediate_size": 32, "sliding_window": 8,
    "tie_word_embeddings": False, "vocab_size": 96,
    "cut": {"layers": [0, 1, 2, 3, 4], "experts_held": [3, 7, 8, 12],
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
