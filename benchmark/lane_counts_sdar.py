"""What one lane of ``sdar-sgd`` needs, counted from the shapes in its
configuration's file (layers, widths, experts held, ``seq_len``,
``block_length``), by the rules of ``lane_counts.py``: 2 operations a
multiply-add of the products that the layer equations need; a training step
three forward passes, a held-out pass one; no recomputation; the even load
of the held experts; bytes as float32 parameters read twice and their
gradient written (12 a parameter a step, 4 a held-out pass), a pass's input
and output rows, 20 a parameter for the optimizer.

What training by diffusion over blocks changes in the count: **every product
of a layer runs on 2 S rows** (the clean copy and the masked one of the S
data tokens), **the head on S** (the masked rows alone), and **attention is
charged the pairs its rule of sight holds, exactly**: a clean query its own
block and the earlier ones, a masked query the clean earlier blocks and the
masked copy of its own, ``S^2 + L S`` pairs a head and layer with blocks of
``L`` (of the ``4 S^2`` of the square: a quarter), whatever blocks an
implementation computes them in. So a form that computes whole blocks of 512
reads under 100 %, and a later kernel is read by the same count. The trace's
seconds in each part and the schedule's passes are ``lane_counts.py``'s.
"""

from lane_counts import device_share, lane_spans, schedule_passes  # noqa: F401

PARTS = ("bda", "moe", "head", "update")


def part_params(config):
    """Parameters of one layer's part (the matrices that its products read),
    and of embedding plus head."""
    d, dh = config["hidden_size"], config["head_dim"]
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    held = len(config["cut"]["experts_held"])
    return {
        "bda": 2 * d * hq * dh + 2 * d * hk * dh,
        "moe": (d * config["cut"]["router_outputs"]
                + held * 3 * d * config["moe_intermediate_size"]),
        "head": 2 * d * config["vocab_size"],
    }


def layers_of(config):
    """How many layers of each part a lane has (the head once)."""
    n = config["num_hidden_layers"]
    return {"bda": n, "moe": n, "head": 1, "update": 0}


def lane_params(config):
    """Parameters of the lane: the parts', the per-head norms' and the
    norms' (two a layer, one last)."""
    params, layers = part_params(config), layers_of(config)
    return (sum(params[p] * layers[p] for p in params)
            + layers["bda"] * 2 * config["head_dim"]
            + (2 * config["num_hidden_layers"] + 1) * config["hidden_size"])


def rows_of(config, part):
    """Rows a pass takes through ``part``: both copies through a layer, the
    masked one through the head."""
    s = config["train"]["seq_len"]
    return s if part == "head" else 2 * s


def attended_pairs(config):
    """Pairs (query row, key row) one head scores over a sequence's ``2 S``
    rows: ``sum_i (B(i) + 1) L`` for the clean queries, ``sum_i B(i) L + L``
    for the masked ones: ``S^2 + L S``."""
    s, length = config["train"]["seq_len"], config["train"]["block_length"]
    return s * s + length * s


def part_forward_flops(config):
    """Operations of one forward pass of one layer's part, over the pass's
    rows (not a token: the parts take different rows)."""
    d, hq = config["hidden_size"], config["num_attention_heads"]
    params = part_params(config)
    outputs, held = config["cut"]["router_outputs"], len(config["cut"]["experts_held"])
    routed = config["num_experts_per_tok"] * held / outputs
    return {
        # four projections a row; scores and weighted values: 2 products of dh a pair and head
        "bda": (2 * params["bda"] * rows_of(config, "bda")
                + 4 * hq * config["head_dim"] * attended_pairs(config)),
        "moe": ((2 * d * outputs + routed * 6 * d * config["moe_intermediate_size"])
                * rows_of(config, "moe")),
        # the lookup is no product: the head's alone
        "head": 2 * d * config["vocab_size"] * rows_of(config, "head"),
    }


def part_work(config, plans, part):
    """``(operations, bytes)`` one sweep needs in ``part``."""
    steps, validations = schedule_passes(plans)
    held_out = config["train"]["n_val"] * validations
    if part == "update":
        n = lane_params(config)
        return 5.0 * n * steps, 20.0 * n * steps
    params, layers = part_params(config)[part], layers_of(config)[part]
    moved = 4 * 2 * rows_of(config, part) * config["hidden_size"]
    flops = part_forward_flops(config)[part] * layers * (3 * steps + held_out)
    return flops, layers * ((12 * params + 3 * moved) * steps + (4 * params + moved) * held_out)


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
