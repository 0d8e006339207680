"""What one lane of ``mellum2-sgd`` needs, counted from the shapes in its
configuration's file, by the rules of ``lane_counts.py`` (2 operations a
multiply-add of the products that the layer equations need; a training step
three forward passes, a validation pass one; no recomputation; the even load
of the held experts; bytes as float32 parameters read twice and their
gradient written, a pass's input and output rows, 20 a parameter for the
optimizer). Attention is charged its pairs exactly: a full layer the causal
half-square, ``S (S + 1) / 2`` a head, a window layer its band, ``W S - W (W
- 1) / 2`` a head, so that an implementation that computes whole blocks
reads under 100 %. The trace's seconds in each part and the schedule's
passes are ``lane_counts.py``'s.
"""

import span_reduce
from lane_counts import device_share, lane_spans, schedule_passes  # noqa: F401

PARTS = ("swa", "gqa", "moe", "head", "update")
MIXER = {"sliding_attention": "swa", "full_attention": "gqa"}


def part_params(config):
    """Parameters of one layer of each part, and of embedding plus head."""
    d, dh = config["hidden_size"], config["head_dim"]
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    attention = 2 * d * hq * dh + 2 * d * hk * dh
    held = len(config["cut"]["experts_held"])
    return {
        "swa": attention, "gqa": attention,
        "moe": (d * config["cut"]["router_outputs"]
                + held * 3 * d * config["moe_intermediate_size"]),
        "head": 2 * d * config["vocab_size"],
    }


def attended_pairs(config, part):
    """Pairs (query, key) one head scores over a sequence, in a layer of ``part``."""
    t = config["train"]["seq_len"]
    if part == "gqa":
        return t * (t + 1) // 2
    w = min(config["sliding_window"], t)
    return w * t - w * (w - 1) // 2


def part_forward_flops(config):
    """Operations of one forward pass of one layer of each part, a token."""
    d, dh, hq = config["hidden_size"], config["head_dim"], config["num_attention_heads"]
    t = config["train"]["seq_len"]
    params = part_params(config)
    outputs, held = config["cut"]["router_outputs"], len(config["cut"]["experts_held"])
    routed = config["num_experts_per_tok"] * held / outputs
    # scores and weighted values: 2 products of dh a pair and head
    attention = lambda part: 2 * params[part] + 4 * hq * dh * attended_pairs(config, part) / t
    return {
        "swa": attention("swa"), "gqa": attention("gqa"),
        "moe": 2 * d * outputs + routed * 6 * d * config["moe_intermediate_size"],
        "head": 2 * d * config["vocab_size"],
    }


def layers_of(config):
    """How many layers of each part a lane has (the head once)."""
    mixers = [MIXER[kind] for kind in config["layer_types"]]
    return {"swa": mixers.count("swa"), "gqa": mixers.count("gqa"),
            "moe": len(mixers), "head": 1, "update": 0}


def lane_params(config):
    """Parameters of the lane: the parts' and the norms' (two a layer, one last)."""
    params, layers = part_params(config), layers_of(config)
    return (sum(params[p] * layers[p] for p in params)
            + (2 * len(config["layer_types"]) + 1) * config["hidden_size"])


def part_work(config, plans, part):
    """``(operations, bytes)`` one sweep needs in ``part``."""
    steps, validations = schedule_passes(plans)
    t, n_val = config["train"]["seq_len"], config["train"]["n_val"]
    params = part_params(config)
    if part == "update":
        n = sum(params[p] * k for p, k in layers_of(config).items() if p != "update")
        return 5.0 * n * steps, 20.0 * n * steps
    layers = layers_of(config)[part]
    rows = 4 * 2 * t * config["hidden_size"]    # a pass's input and output, float32
    flops = part_forward_flops(config)[part] * t * layers * (3 * steps + n_val * validations)
    moved = layers * ((12 * params[part] + 3 * rows) * steps
                      + (4 * params[part] + rows) * n_val * validations)
    return flops, moved


def sweep_flops(config, plans):
    return sum(part_work(config, plans, part)[0] for part in PARTS)


def roofline_share(ctx, part):
    """The least seconds the chip could take for the traced sweeps' work in
    ``part`` (the larger of operations over peak FLOP/s and bytes over peak
    bytes/s), over its busy seconds there, in percent."""
    spans = lane_spans(ctx)
    if spans is None or not spans["phase_s"]:
        return None
    busy_s = spans["phase_s"].get("lane." + part, 0.0)
    if not busy_s:
        return None
    flops, moved = part_work(ctx["config"], ctx["plans"], part)
    least_s = max(flops / ctx["peaks"]["flops_per_s"],
                  moved / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s * spans["sweeps"] / busy_s / ctx["chips"]
