"""What one lane of ``kimi-linear-sgd`` needs, counted from the shapes in
its configuration's file, and the device's seconds in each of its parts.

Operations are 2 a multiply-add of the matrix products that the layer
equations need; a training step is charged three forward passes (forward,
input gradient, weight gradient), a validation pass one; recomputation is
not counted at all, causal attention is half a square, and KDA is charged
its recurrence (decay, two products with the state, the rank-one update:
7 d_k d_v a token and head), not what a chunked form adds. Routed experts
are charged the even load, ``held / outputs`` of a token's choices. Bytes
are what has to cross the memory's pins once: a part's float32 parameters
read in the forward and in the backward pass and their gradient written
(12 a parameter a step, 4 a validation pass) and its input and output rows
in float32 in each pass; the optimizer reads parameter, momentum and
gradient and writes parameter and momentum (20 a parameter).
"""


import span_reduce
from reference import halving

PARTS = ("kda", "mla", "moe", "dense_ffn", "head", "update")


def layer_kinds(config):
    linear = config["linear_attn_config"]
    return [("kda" if n in linear["kda_layers"] else "mla",
             "dense_ffn" if n <= config["first_k_dense_replace"] else "moe")
            for n in config["cut"]["layers"]]


def part_params(config):
    """Parameters of one layer of each part, and of embedding plus head."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    linear = config["linear_attn_config"]
    dk, kernel = linear["head_dim"], linear["short_conv_kernel_size"]
    dn, dr, dv = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    rank, f = config["kv_lora_rank"], config["moe_intermediate_size"]
    held = len(config["cut"]["experts_held"])
    return {
        "kda": (4 * d * h * dk + 3 * kernel * h * dk + 2 * (d * dk + dk * h * dk)
                + d * h + h + h * dk + dk),
        "mla": d * h * (dn + dr) + d * (rank + dr) + rank + rank * h * (dn + dv) + h * dv * d,
        "moe": (d + 1) * config["cut"]["router_outputs"] + (1 + held) * 3 * d * f,
        "dense_ffn": 3 * d * config["intermediate_size"],
        "head": 2 * d * config["vocab_size"],
    }


def part_forward_flops(config):
    """Operations of one forward pass of one layer of each part, a token."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    linear = config["linear_attn_config"]
    dk = linear["head_dim"]
    dn, dr, dv = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    rank, f = config["kv_lora_rank"], config["moe_intermediate_size"]
    t = config["train"]["seq_len"]
    outputs, held = config["cut"]["router_outputs"], len(config["cut"]["experts_held"])
    routed = config["num_experts_per_token"] * held / outputs
    return {
        "kda": 2 * (4 * d * h * dk + 2 * (d * dk + dk * h * dk) + d * h) + 7 * h * dk * dk,
        "mla": (2 * (d * h * (dn + dr) + d * (rank + dr) + rank * h * (dn + dv) + h * dv * d)
                + 2 * h * (dn + dr + dv) * t / 2),
        "moe": 2 * d * outputs + (1 + routed) * 6 * d * f,
        "dense_ffn": 6 * d * config["intermediate_size"],
        "head": 2 * d * config["vocab_size"],
    }


def layers_of(config):
    """How many layers of each part a lane has (the head once)."""
    kinds = layer_kinds(config)
    count = {part: sum(part in kind for kind in kinds) for part in PARTS}
    count["head"] = 1
    return count


def schedule_passes(plans):
    """``(training steps, validation passes)`` of one sweep of stateless
    lanes: an evaluation trains its whole budget and validates once."""
    steps = sum(n * int(round(b)) for counts, budgets in plans
                for n, b in zip(counts, budgets))
    return steps, halving.schedule_evaluations(plans)


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


def lane_spans(ctx):
    """``{"phase_s": {lane part: busy seconds}, "busy_s", "sweeps"}`` of the
    traced run, made once and kept in ``ctx``: the busy (self) seconds that
    the harness's own reduction (``ctx["trace"]["op_s"]``) holds for every
    instruction name, added up by the lane's parts (the program's second
    map). A trace of these sweeps holds over a million operations and the
    cold traced run has 360 s: the trace is not read a second time.
    ``None`` where the run was not traced or the program offers no map."""
    if "lane_spans" not in ctx:
        ctx["lane_spans"] = None
        if ctx.get("trace") is not None:
            import program_lane_parts

            maps = program_lane_parts.lane_maps()
            if maps:
                # a name gives its part if every program that has the name
                # agrees (``span_reduce.instruction_phases``' rule for an
                # operation that no program's event encloses)
                part_of = {}
                for parts in maps.values():
                    for name, part in parts.items():
                        if part_of.setdefault(name, part) != part:
                            part_of[name] = None
                phase_s = {}
                for name, seconds in ctx["trace"]["op_s"].items():
                    part = part_of.get(name) or span_reduce.UNNAMED
                    phase_s[part] = phase_s.get(part, 0.0) + seconds
                ctx["lane_spans"] = {
                    "phase_s": phase_s, "busy_s": sum(phase_s.values()),
                    # the harness writes two spans a sweep, its construction and its run
                    "sweeps": ctx["trace"]["spans"] // 2}
                print("lane parts, busy seconds: %s" % phase_s)
    return ctx["lane_spans"]


def device_share(ctx, part):
    """Percent of the device's busy seconds in ``lane.<part>``."""
    return span_reduce.phase_share(lane_spans(ctx), "lane." + part)


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
