"""The ``olmo-hybrid-sgd`` configuration at a size the CPU tests can run:
hidden 64, full attention of 4 heads of 16 under a norm over the whole width,
linear layers of 4 heads of ``d_k`` 8 beside ``d_v`` 16 (unequal, as the
published 96 and 192), a SwiGLU of 96, 256 ids, one period (linear, linear,
linear, full), 32-token sequences (query blocks of 16 and the scan in chunks
of 16 in the tests). The benchmark owns the reference and the builder; the
tests load both by path (``kimi_small.load``), as ``benchmark/run.py`` does."""

import copy

from kimi_small import BENCHMARK, load  # noqa: F401

SMALL = {
    "attention_bias": False, "hidden_size": 64, "intermediate_size": 96,
    "layer_types": ["linear_attention", "linear_attention", "linear_attention",
                    "full_attention"],
    "linear_allow_neg_eigval": True, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 8, "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_value_head_dim": 16, "num_attention_heads": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
    "rope_parameters": {"rope_theta": None}, "tie_word_embeddings": False,
    "vocab_size": 256,
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
