"""The ``kimi-linear-sgd`` configuration at a size the CPU tests can run:
hidden 64, 4 heads of 16, 16 experts top-4 with 4 held, the five layers of
the same kinds, 64-token sequences. The benchmark owns the reference and the
builder; the tests load both by path, as ``benchmark/run.py`` does."""

import copy
import importlib.util
import os
import re

BENCHMARK = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")

SMALL = {
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 32,
    "linear_attn_config": {
        "full_attn_layers": [4, 8], "head_dim": 16,
        "kda_layers": [1, 2, 3, 5, 6, 7], "num_heads": 4,
        "short_conv_kernel_size": 4},
    "moe_intermediate_size": 32, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_per_token": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-5, "routed_scaling_factor": 2.446, "v_head_dim": 16,
    "vocab_size": 96,
    "cut": {"layers": [1, 2, 3, 4, 5], "experts_held": [3, 7, 8, 12],
            "router_outputs": 16},
    "train": {"seq_len": 64, "n_train": 4, "n_val": 2},
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


def load(*parts):
    path = os.path.join(BENCHMARK, *parts)
    name = "kimi_" + "_".join(parts).replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def lower_forward_and_backward(layer, x, p):
    """``layer(x, p) -> y`` and its pull-back of a cotangent shaped as
    ``x``, lowered as one program."""
    import jax

    def both(x, p, dy):
        y, pull = jax.vjp(layer, x, p)
        return y, pull(dy)

    return jax.jit(both).lower(x, p, x)


def scatters_and_sorts(layer, x, p):
    """``[(element type, "scatter" | "sort")]`` of the lowered forward and
    backward pass of ``layer(x, p) -> (y, ...)``, one instruction a line in
    the HLO dialect (``top_k`` is its own instruction there, no sort)."""
    text = lower_forward_and_backward(
        lambda x, p: layer(x, p)[0], x, p).as_text(dialect="hlo")
    return re.findall(r"= (\w+)\[[^\]]*\]\S* (scatter|sort)\(", text)


def check_the_moe_backward_rule_is_named(compiled_text, parts):
    """The expert layer's backward rule is written by hand
    (``lane._routed``): in a compiled sweep its instructions are charged to
    ``lane.moe`` as the layer's others are (``parts``: instruction ->
    lane part), and no gather of hidden-sized rows (64 wide at the small
    sizes: the embedding's, the layer's five) is in no part."""
    backward, gathers = [], []
    for line in compiled_text.splitlines():
        name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        if name and "transpose(jvp(lane.moe))" in line:
            backward.append(name.group(1))
        if name and re.search(r" = (f32|bf16)\[[\d,]+,64\]\S* gather\(", line):
            gathers.append(name.group(1))
    assert len(backward) > 20 and {parts.get(name) for name in backward} == {"lane.moe"}
    assert len([g for g in gathers if parts.get(g) == "lane.moe"]) >= 5
    assert all(g in parts for g in gathers)
