"""What one lane of ``ouro-sgd`` needs, counted from the shapes in its
configuration's file (passes, layers, widths, ``seq_len``), by the rules of
``lane_counts.py``: 2 operations a multiply-add of the products that the
equations need; a training step three forward passes, a held-out pass one;
no recomputation; attention its causal half-square exactly, ``S (S + 1) / 2``
pairs a head. A layer is visited ``total_ut_steps`` times a pass: its
products are charged once a visit, and so are its weights' bytes (a visit
reads them in the forward and in the backward pass and writes their
gradient: 12 a parameter a training visit, 4 a held-out one), beside a
visit's input and output rows in float32; the optimizer moves 20 bytes a
parameter a step, once whatever the visits. A training step differentiates
all ``total_ut_steps`` exits' heads, a held-out pass reads the last one. The
trace's seconds in each part and the schedule's passes are
``lane_counts.py``'s.
"""

from lane_counts import device_share, lane_spans, schedule_passes  # noqa: F401

PARTS = ("gqa", "dense_ffn", "head", "update")


def part_params(config):
    """Parameters of one layer's part, and of embedding plus head."""
    d, dh = config["hidden_size"], config["head_dim"]
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return {
        "gqa": 2 * d * hq * dh + 2 * d * hk * dh,
        "dense_ffn": 3 * d * config["intermediate_size"],
        "head": 2 * d * config["vocab_size"],
    }


def lane_params(config):
    """Parameters of the lane: the layers' products and their four norms,
    embedding and head, the final norm, the gate and its bias."""
    params, d = part_params(config), config["hidden_size"]
    return (config["num_hidden_layers"] * (params["gqa"] + params["dense_ffn"] + 4 * d)
            + params["head"] + d + d + 1)


def attended_pairs(config):
    """Pairs (query, key) one head scores over a sequence: the causal half-square."""
    t = config["train"]["seq_len"]
    return t * (t + 1) // 2


def visit_forward_flops(config):
    """Operations of one forward visit of one layer's part, a token; for
    ``head`` of one exit's head."""
    d, dh, hq = config["hidden_size"], config["head_dim"], config["num_attention_heads"]
    t = config["train"]["seq_len"]
    params = part_params(config)
    return {
        # scores and weighted values: 2 products of dh a pair and head
        "gqa": 2 * params["gqa"] + 4 * hq * dh * attended_pairs(config) / t,
        "dense_ffn": 2 * params["dense_ffn"],
        "head": 2 * d * config["vocab_size"],
    }


def part_work(config, plans, part):
    """``(operations, bytes)`` one sweep needs in ``part``."""
    steps, validations = schedule_passes(plans)
    t, n_val = config["train"]["seq_len"], config["train"]["n_val"]
    passes, layers = config["total_ut_steps"], config["num_hidden_layers"]
    params = part_params(config)
    held_out = n_val * validations
    if part == "update":
        n = lane_params(config)
        return 5.0 * n * steps, 20.0 * n * steps
    rows = 4 * 2 * t * config["hidden_size"]    # a visit's input and output, float32
    flops = visit_forward_flops(config)[part] * t
    if part == "head":
        # every exit's head in a training step, the last exit's in a held-out pass
        return (flops * (3 * passes * steps + held_out),
                (12 * params[part] + 3 * rows) * steps + (4 * params[part] + rows) * held_out)
    visits = layers * passes
    return (flops * visits * (3 * steps + held_out),
            visits * ((12 * params[part] + 3 * rows) * steps
                      + (4 * params[part] + rows) * held_out))


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
