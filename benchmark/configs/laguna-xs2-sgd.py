"""Builder of the ``laguna-xs2-sgd`` configuration: one chip's share of
Laguna-XS.2 (the leading dense layer and one whole period: full attention of
48 heads over half-rotated heads, three 512-token window layers of 64, a gate
a head, 32 of 256 sigmoid-routed experts beside a shared one) as a stateless
``eval_fn``, its tokens and its initial-weight key made from the
configuration's data seed, once. The record of a sweep carries the program's
trainer (``lane_change``) for the comparison, as ``olmo-hybrid-sgd.py``'s
does: what is the same for both (the thread that compiles ahead, the lane's
vector from its hyperparameters, the trainer compiled at the quickest effort)
is loaded from beside this file."""

import importlib.util
import os

import program

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}
ROPE_TYPES = {"sliding": "default", "full": "yarn"}


def _beside(*parts):
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_beside_" + parts[-1].split("-")[0], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_shared = _beside("configs", "ouro-sgd.py")
_olmo = _beside("configs", "olmo-hybrid-sgd.py")


def _by_kind(config, what):
    """``((kind, what(layers of the kind)), ...)``, every layer of a kind
    agreeing."""
    found = {}
    for kind, value in zip(config["layer_types"], what):
        if found.setdefault(KINDS[kind], value) != value:
            raise ValueError("laguna-xs2-sgd: layers of kind %s differ: %r" % (kind, what))
    return tuple(sorted(found.items()))


def lane_config(config):
    """The program's ``LagunaConfig`` from the configuration's file: the
    published widths under their published keys, the cut under ``cut`` and
    the data under ``train``. A file whose kinds of layer, head counts or
    rope types the lane does not implement is refused."""
    from hpbandster_tpu.workloads.laguna import LagunaConfig

    unknown = set(config["layer_types"]) - set(KINDS)
    if unknown or set(config["mlp_layer_types"]) - {"dense", "sparse"}:
        raise ValueError("laguna-xs2-sgd: layers are full_attention or sliding_attention "
                         "and dense or sparse; the file has %r, %r" % (
                             config["layer_types"], config["mlp_layer_types"]))
    held = config["num_hidden_layers"]
    per_layer = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")
    if any(len(config[key]) != held for key in per_layer):
        raise ValueError("laguna-xs2-sgd: one entry a layer held in %s" % (per_layer,))
    heads = _by_kind(config, config["num_attention_heads_per_layer"])
    if any(n % config["num_key_value_heads"] for _, n in heads):
        raise ValueError("laguna-xs2-sgd: a layer's query heads are whole groups of "
                         "its key/value heads; the file has %r" % (heads,))
    rope = {kind: config["rope_parameters"][name] for name, kind in KINDS.items()}
    if any(rope[kind]["rope_type"] != ROPE_TYPES[kind] for kind in rope):
        raise ValueError("laguna-xs2-sgd: window layers take plain RoPE and full layers "
                         "YaRN; rope_parameters has %r" % config["rope_parameters"])
    if config["gating"] is not True or config["attention_bias"] or config[
            "tie_word_embeddings"] or config["moe_apply_router_weight_on_input"]:
        raise ValueError("laguna-xs2-sgd: a gate a head, no bias in attention, an untied "
                         "head, the router's weight on an expert's output")
    full = rope["full"]
    return LagunaConfig(
        hidden_size=config["hidden_size"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        heads_by_kind=heads,
        rotary_by_kind=tuple(sorted(
            (kind, float(rope[kind]["partial_rotary_factor"])) for kind in rope)),
        theta_by_kind=tuple(sorted((kind, float(rope[kind]["rope_theta"])) for kind in rope)),
        sliding_window=config["sliding_window"],
        yarn_factor=float(full["factor"]),
        yarn_original_max_position=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=full["attention_factor"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config["shared_expert_intermediate_size"],
        num_experts_per_token=config["num_experts_per_tok"],
        routed_scaling_factor=config["moe_routed_scaling_factor"],
        rms_norm_eps=config["rms_norm_eps"],
        layer_kinds=tuple(KINDS[kind] for kind in config["layer_types"]),
        mlp_kinds=tuple(config["mlp_layer_types"]),
        experts_held=tuple(config["cut"]["experts_held"]),
        router_outputs=config["cut"]["router_outputs"],
        vocab_rows=config["vocab_size"],
        seq_len=config["train"]["seq_len"],
        n_train=config["train"]["n_train"],
        n_val=config["train"]["n_val"],
    )


def build(config, traffic, seed, devices):
    from hpbandster_tpu.workloads.laguna import laguna_space, make_laguna_eval_fn

    eval_fn = make_laguna_eval_fn(lane_config(config), data_seed=config["data_seed"])
    ahead = [_shared._ahead(_compile_the_reference, config),
             _shared._ahead(_olmo._compile_the_change, eval_fn)]
    one_sweep = program.make_sweep(
        laguna_space, {"eval_fn": eval_fn}, config, traffic, devices)

    def lane_change(hparams, steps):
        return _shared._lane_change(
            ahead[1]() or _olmo._compile_the_change(eval_fn), hparams, steps)

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
    _beside("reference", "laguna-xs2-sgd.py").compile_ahead(config)
