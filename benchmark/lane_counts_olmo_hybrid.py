"""What one lane of ``olmo-hybrid-sgd`` needs, counted from the shapes in its
configuration's file (layers by kind, widths, ``seq_len``), by the rules of
``lane_counts.py``: 2 operations a multiply-add of the products that the layer
equations need; a training step three forward passes, a held-out pass one; no
recomputation; attention its causal half-square exactly, ``S (S + 1) / 2``
pairs a head; bytes as float32 parameters read twice and their gradient
written (12 a parameter a step, 4 a held-out pass), a pass's input and output
rows, 20 a parameter for the optimizer. A linear layer's mixer is charged its
seven products (``W_q``, ``W_k``, ``W_v``, the output gate's ``W_g``, the
gate's ``W_a`` and ``W_b``, ``W_o``) and **the delta rule's recurrence**, as
``lane_counts.py`` charges KDA's: the decay of the state, its two products
(with ``k`` and with ``q``) and the rank-one update, ``7 d_k d_v`` a token and
head with ``d_k`` 96 beside ``d_v`` 192; not what a chunked form adds (a
chunk's ``K K^T`` and ``Q K^T``, its solve), and the same whatever implements
the scan. The taps, the gates and the norms are charged no operations (a
thousandth of the products'). The trace's seconds in each part and the
schedule's passes are ``lane_counts.py``'s.
"""

from lane_counts import device_share, lane_spans, schedule_passes  # noqa: F401

PARTS = ("gdn", "gqa", "dense_ffn", "head", "update")
MIXER = {"linear_attention": "gdn", "full_attention": "gqa"}


def head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def linear_widths(config):
    """``(heads, all heads' keys, all heads' values)`` of a linear layer."""
    h = config["linear_num_key_heads"]
    return h, h * config["linear_key_head_dim"], h * config["linear_value_head_dim"]


def part_params(config):
    """Parameters of one layer's part (the matrices that its products read),
    and of embedding plus head."""
    d, dh = config["hidden_size"], head_dim(config)
    hq, hk = config["num_attention_heads"], config["num_key_value_heads"]
    h, wk, wv = linear_widths(config)
    return {
        "gdn": 2 * d * wk + 3 * d * wv + 2 * d * h,
        "gqa": 2 * d * hq * dh + 2 * d * hk * dh,
        "dense_ffn": 3 * d * config["intermediate_size"],
        "head": 2 * d * config["vocab_size"],
    }


def layers_of(config):
    """How many layers of each part a lane has (the head and the update once)."""
    mixers = [MIXER[kind] for kind in config["layer_types"]]
    return {"gdn": mixers.count("gdn"), "gqa": mixers.count("gqa"),
            "dense_ffn": len(mixers), "head": 1, "update": 1}


def lane_params(config):
    """Parameters of the lane: the parts', the linear layers' taps, gate
    leaves and head norm, the full layers' q/k norms, and the norms' (two a
    layer, one last)."""
    params, layers = part_params(config), layers_of(config)
    d = config["hidden_size"]
    h, wk, wv = linear_widths(config)
    return (sum(params[p] * layers[p] for p in params)
            + layers["gdn"] * (config["linear_conv_kernel_dim"] * (2 * wk + wv) + 2 * h
                               + config["linear_value_head_dim"])
            + layers["gqa"] * head_dim(config) * (
                config["num_attention_heads"] + config["num_key_value_heads"])
            + (2 * len(config["layer_types"]) + 1) * d)


def attended_pairs(config):
    """Pairs (query, key) one head scores over a sequence: the causal half-square."""
    t = config["train"]["seq_len"]
    return t * (t + 1) // 2


def part_forward_flops(config):
    """Operations of one forward pass of one layer's part, a token."""
    hq, t = config["num_attention_heads"], config["train"]["seq_len"]
    params = part_params(config)
    h = config["linear_num_key_heads"]
    return {
        # the seven products, and the recurrence's 7 d_k d_v a head
        "gdn": (2 * params["gdn"]
                + 7 * h * config["linear_key_head_dim"] * config["linear_value_head_dim"]),
        # scores and weighted values: 2 products of dh a pair and head
        "gqa": 2 * params["gqa"] + 4 * hq * head_dim(config) * attended_pairs(config) / t,
        "dense_ffn": 2 * params["dense_ffn"],
        "head": 2 * config["hidden_size"] * config["vocab_size"],
    }


def part_work(config, plans, part):
    """``(operations, bytes)`` one sweep needs in ``part``."""
    steps, validations = schedule_passes(plans)
    t, n_val = config["train"]["seq_len"], config["train"]["n_val"]
    held_out = n_val * validations
    if part == "update":
        n = lane_params(config)
        return 5.0 * n * steps, 20.0 * n * steps
    params, layers = part_params(config)[part], layers_of(config)[part]
    rows = 4 * 2 * t * config["hidden_size"]    # a pass's input and output, float32
    flops = part_forward_flops(config)[part] * t * layers * (3 * steps + held_out)
    return flops, layers * ((12 * params + 3 * rows) * steps + (4 * params + rows) * held_out)


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
