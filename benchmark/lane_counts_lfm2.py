"""What one lane of ``lfm2-sgd`` needs, counted from the shapes in its
configuration's file (layers by kind, widths, experts held, ``seq_len``), by
the rules of ``lane_counts.py``: 2 operations a multiply-add of the products
that the layer equations need; a training step three forward passes, a
held-out pass one; no recomputation; the even load of the held experts;
attention its causal half-square exactly, ``S (S + 1) / 2`` pairs a head;
bytes as float32 parameters read twice and their gradient written (12 a
parameter a step, 4 a held-out pass), a pass's input and output rows, 20 a
parameter for the optimizer. A gated short convolution is charged its two
products (``D -> 3D``, ``D -> D``) and, beside its weights and rows, the
four arrays between them (``u`` f32[S, 3D]; ``z``, ``c``, ``y`` f32[S, D]),
each written and read once a pass in float32; the gates and the taps are
charged no operations (three multiplies and two adds a channel: a thousandth
of the products'). The head is the embedding: one matrix, one product a
pass. The trace's seconds in each part and the schedule's passes are
``lane_counts.py``'s.
"""

from lane_counts import device_share, lane_spans, schedule_passes  # noqa: F401

PARTS = ("conv", "gqa", "moe", "dense_ffn", "head", "update")
MIXER = {"conv": "conv", "full_attention": "gqa"}


def layer_parts(config):
    """``[(mixer's part, feed-forward's part)]`` of the layers held."""
    return [(MIXER[kind], "dense_ffn" if i < config["num_dense_layers"] else "moe")
            for i, kind in enumerate(config["layer_types"])]


def head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def part_params(config):
    """Parameters of one layer's part (the matrices that its products read),
    and of the tied embedding."""
    d, dh = config["hidden_size"], head_dim(config)
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    held = len(config["cut"]["experts_held"])
    return {
        "conv": 3 * d * d + d * d,
        "gqa": 2 * d * hq * dh + 2 * d * hk * dh,
        "moe": (d * config["cut"]["router_outputs"]
                + held * 3 * d * config["moe_intermediate_size"]),
        "dense_ffn": 3 * d * config["intermediate_size"],
        "head": d * config["vocab_size"],
    }


def layers_of(config):
    """How many layers of each part a lane has (the head once)."""
    parts = [part for layer in layer_parts(config) for part in layer]
    return {part: 1 if part == "head" else parts.count(part) for part in PARTS}


def lane_params(config):
    """Parameters of the lane: the parts', the convolutions' taps, the
    routers' biases, the per-head norms' and the norms' (two a layer, one
    last)."""
    params, layers = part_params(config), layers_of(config)
    d = config["hidden_size"]
    return (sum(params[p] * layers[p] for p in params)
            + layers["conv"] * config["conv_L_cache"] * d
            + layers["moe"] * config["cut"]["router_outputs"]
            + layers["gqa"] * 2 * head_dim(config)
            + (2 * len(config["layer_types"]) + 1) * d)


def attended_pairs(config):
    """Pairs (query, key) one head scores over a sequence: the causal half-square."""
    t = config["train"]["seq_len"]
    return t * (t + 1) // 2


def part_forward_flops(config):
    """Operations of one forward pass of one layer's part, a token."""
    d, hq = config["hidden_size"], config["num_attention_heads"]
    t = config["train"]["seq_len"]
    params = part_params(config)
    outputs, held = config["cut"]["router_outputs"], len(config["cut"]["experts_held"])
    routed = config["num_experts_per_tok"] * held / outputs
    return {
        "conv": 2 * params["conv"],
        # scores and weighted values: 2 products of dh a pair and head
        "gqa": 2 * params["gqa"] + 4 * hq * head_dim(config) * attended_pairs(config) / t,
        "moe": 2 * d * outputs + routed * 6 * d * config["moe_intermediate_size"],
        "dense_ffn": 2 * params["dense_ffn"],
        "head": 2 * params["head"],
    }


def part_pass_bytes(config, part):
    """Bytes of activations one forward pass of one layer's part moves: its
    input and output rows in float32 and, in a convolution mixer, ``u``,
    ``z``, ``c`` and ``y`` written and read once."""
    t, d = config["train"]["seq_len"], config["hidden_size"]
    rows = 4 * 2 * t * d
    return rows + (4 * 2 * t * (3 * d + 3 * d) if part == "conv" else 0)


def part_work(config, plans, part):
    """``(operations, bytes)`` one sweep needs in ``part``."""
    steps, validations = schedule_passes(plans)
    t, n_val = config["train"]["seq_len"], config["train"]["n_val"]
    held_out = n_val * validations
    if part == "update":
        n = lane_params(config)
        return 5.0 * n * steps, 20.0 * n * steps
    params, layers = part_params(config)[part], layers_of(config)[part]
    moved = part_pass_bytes(config, part)
    flops = part_forward_flops(config)[part] * t * layers * (3 * steps + held_out)
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
