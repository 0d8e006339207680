"""The ``ouro-sgd`` configuration at a size the CPU tests can run: hidden
64, 2 heads of 32, a SwiGLU of 96, 256 ids, 2 layers run 3 times over (6
layer visits, 3 exits), 32-token sequences (query blocks of 16 in the
tests). The benchmark owns the reference and the builder; the tests load
both by path (``kimi_small.load``), as ``benchmark/run.py`` does."""

import copy

from kimi_small import BENCHMARK, load  # noqa: F401

SMALL = {
    "head_dim": 32, "hidden_size": 64, "intermediate_size": 96,
    "layer_types": ["full_attention", "full_attention"],
    "num_attention_heads": 2, "num_hidden_layers": 2, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "total_ut_steps": 3, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 256, "exit_entropy_beta": 0.1,
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
