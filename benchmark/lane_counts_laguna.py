"""What one lane of ``laguna-xs2-sgd`` needs, counted from the shapes in its
configuration's file, by the rules of ``lane_counts.py`` (2 operations a
multiply-add of the products that the layer equations need; a training step
three forward passes, a held-out pass one; no recomputation; the even load of
the held experts; bytes as float32 parameters read twice and their gradient
written, a pass's input and output rows, 20 a parameter for the optimizer).

**A layer is counted by its own kind and its own head count**
(``layer_types``, ``num_attention_heads_per_layer``): four projections at the
layer's width and the gate's column a head; the pairs a head scores exactly (a
full layer the causal half-square, ``S (S + 1) / 2``; a window layer its band,
``W S - W (W - 1) / 2``: the band and not the square, and not the tiles an
implementation walks, so that one that computes whole tiles reads under
100 %); the rotation of the channels the kind turns (``partial_rotary_factor``
of a head: half in a full layer), four multiplies and two additions a pair of
channels; the gate's multiply a channel. The expert layers: the router, the
shared expert on every token, the held experts at the even load (``top k x
held / outputs`` of a token); layer 0 the dense SwiGLU; the head over the
vocabulary's slice. The trace's seconds in each part and the schedule's
passes are ``lane_counts.py``'s.
"""

from lane_counts import device_share, lane_spans, schedule_passes  # noqa: F401

PARTS = ("swa", "gqa", "moe", "dense_ffn", "head", "update")
MIXER = {"sliding_attention": "swa", "full_attention": "gqa"}
FFN = {"dense": "dense_ffn", "sparse": "moe"}
ATTENTION = ("swa", "gqa")


def layers(config):
    """``[(mixer part, feed-forward part, query heads)]`` of the layers held."""
    return [(MIXER[kind], FFN[mlp], heads) for kind, mlp, heads in zip(
        config["layer_types"], config["mlp_layer_types"],
        config["num_attention_heads_per_layer"])]


def attention_params(config, heads):
    """Parameters of the mixer of a layer of ``heads`` query heads: the four
    projections and the gate."""
    d, dh, hk = config["hidden_size"], config["head_dim"], config["num_key_value_heads"]
    return 2 * d * heads * dh + 2 * d * hk * dh + d * heads


def ffn_params(config, part):
    d = config["hidden_size"]
    if part == "dense_ffn":
        return 3 * d * config["intermediate_size"]
    held = len(config["cut"]["experts_held"])
    return (d * config["cut"]["router_outputs"]
            + 3 * d * config["shared_expert_intermediate_size"]
            + held * 3 * d * config["moe_intermediate_size"])


def attended_pairs(config, part):
    """Pairs (query, key) one head scores over a sequence, in a layer of ``part``."""
    t = config["train"]["seq_len"]
    if part == "gqa":
        return t * (t + 1) // 2
    w = min(config["sliding_window"], t)
    return w * t - w * (w - 1) // 2


def attention_forward_flops(config, part, heads):
    """Operations of one forward pass of one layer's mixer, a token."""
    dh, hk, t = config["head_dim"], config["num_key_value_heads"], config["train"]["seq_len"]
    rope = config["rope_parameters"][
        "sliding_attention" if part == "swa" else "full_attention"]
    turned = rope["partial_rotary_factor"] * dh
    return (2 * attention_params(config, heads)
            # scores and weighted values: 2 products of dh a pair and head
            + 4 * heads * dh * attended_pairs(config, part) / t
            # queries and keys turned: 6 operations a pair of channels; the gate
            + 3 * turned * (heads + hk) + heads * dh)


def ffn_forward_flops(config, part):
    """Operations of one forward pass of one layer's feed-forward, a token."""
    d = config["hidden_size"]
    if part == "dense_ffn":
        return 6 * d * config["intermediate_size"]
    outputs, held = config["cut"]["router_outputs"], len(config["cut"]["experts_held"])
    routed = config["num_experts_per_tok"] * held / outputs
    return (2 * d * outputs + 6 * d * config["shared_expert_intermediate_size"]
            + routed * 6 * d * config["moe_intermediate_size"])


def part_layers(config, part):
    """``[(parameters, forward operations a token)]`` of the layers' halves
    that are of ``part``; the head once."""
    if part == "head":
        n = 2 * config["hidden_size"] * config["vocab_size"]
        return [(n, n)]     # the lookup is no product: the head's alone
    found = []
    for mixer, ffn, heads in layers(config):
        if part == mixer:
            found.append((attention_params(config, heads),
                          attention_forward_flops(config, part, heads)))
        if part == ffn:
            found.append((ffn_params(config, part), ffn_forward_flops(config, part)))
    return found


def lane_params(config):
    """Parameters of the lane: the parts' and the norms' (two a layer, one last)."""
    return (sum(n for part in PARTS for n, _ in part_layers(config, part))
            + (2 * len(config["layer_types"]) + 1) * config["hidden_size"])


def part_work(config, plans, part):
    """``(operations, bytes)`` one sweep needs in ``part``."""
    steps, validations = schedule_passes(plans)
    t, held_out = config["train"]["seq_len"], config["train"]["n_val"] * validations
    if part == "update":
        n = lane_params(config)
        return 5.0 * n * steps, 20.0 * n * steps
    rows = 4 * 2 * t * config["hidden_size"]    # a pass's input and output, float32
    flops = moved = 0.0
    for params, forward in part_layers(config, part):
        flops += forward * t * (3 * steps + held_out)
        moved += (12 * params + 3 * rows) * steps + (4 * params + rows) * held_out
    return flops, moved


def sweep_flops(config, plans):
    return sum(part_work(config, plans, part)[0] for part in PARTS)


def roofline_share(ctx, parts):
    """The least seconds the chip could take for the traced sweeps' work in
    ``parts`` (of each the larger of operations over peak FLOP/s and bytes
    over peak bytes/s), over its busy seconds there, in percent."""
    spans = lane_spans(ctx)
    if spans is None or not spans["phase_s"]:
        return None
    busy_s = sum(spans["phase_s"].get("lane." + part, 0.0) for part in parts)
    if not busy_s:
        return None
    least_s = 0.0
    for part in parts:
        flops, moved = part_work(ctx["config"], ctx["plans"], part)
        least_s += max(flops / ctx["peaks"]["flops_per_s"],
                       moved / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s * spans["sweeps"] / busy_s / ctx["chips"]
