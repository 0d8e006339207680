"""The ``sdar-sgd`` configuration at a size the CPU tests can run: hidden 64,
4 query heads on 2 key/value heads of 16 under a per-head norm, 8 softmax
experts of 32 top-2 with 4 held, 256 ids (the last one ``MASK``), two layers,
32-token sequences in blocks of 4 (64 rows a pass; query blocks of 16 in the
tests). The benchmark owns the reference and the builder; the tests load
both by path (``kimi_small.load``), as ``benchmark/run.py`` does."""

import copy

from kimi_small import (  # noqa: F401
    BENCHMARK, check_the_moe_backward_rule_is_named, load, scatters_and_sorts)

SMALL = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16, "hidden_size": 64,
    "mlp_only_layers": [], "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 256,
    "cut": {"layers": [0, 1], "experts_held": [1, 3, 4, 6], "router_outputs": 8},
    "train": {"seq_len": 32, "n_train": 4, "n_val": 1, "block_length": 4,
              "noise_floor": 1e-3},
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
