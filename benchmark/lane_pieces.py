"""The device's seconds in a lane by pass and, inside the expert layer, by
piece: the harness's own reduction of the trace (``ctx["trace"]["op_s"]``,
self seconds per instruction name; the trace is not read again) joined with
the program's maps from instruction to lane part, to pass and to piece
(``program_lane_parts.py``, ``program_lane_pieces.py``), exactly as ``lane_counts.lane_spans`` joins it
with the parts alone.

A part's seconds split three ways: the forward trace that training and
held-out passes share, what a training pass computes again for its gradient
(a visit's inside under the trainer's ``jax.vjp``, a tile of the expert
layer inside its backward rule, a block of scores under ``jax.checkpoint``),
and the pull-backs. The optimizer's step and the sums of a shared leaf's
gradients carry no pass; nor do the draw of the initial weights and the
sweep's own phases. A Pallas kernel is one instruction: what the backward
kernel computes again of the scores is the backward pass's.
"""

import json

NO_PASS = "no pass"
NO_PART = "no part"
NO_PIECE = "no piece"
MOE = "lane.moe"
PASSES = ("pass.forward", "pass.recompute", "pass.backward")
#: the parts that carry no pass by construction: each has a share of its own
PASSLESS = ("lane.update", "lane.accumulate")
#: what the chip's compiler calls the kernels it makes of ``ragged_dot``
#: (``ragged-dot-none.3``): they carry no name of the program's and inherit
#: their loop's, so inside the expert layer's backward rule the products
#: that are computed again read ``pass.backward`` with the gradient's
GROUPED_KERNELS = "ragged-dot"


def _by_name(maps):
    """``{instruction name: name}``: a name gives what every program that
    has it agrees on (``lane_counts.lane_spans``' rule); ``None`` where the
    program offers no such map."""
    if not maps:
        return None
    found = {}
    for names in maps.values():
        for name, value in names.items():
            if found.setdefault(name, value) != value:
                found[name] = None
    return found


def split(op_s, part_of, pass_of, piece_of):
    """``op_s`` ``{instruction name: busy seconds}`` ->
    ``{"busy_s", "part_pass_s": {part: {pass: seconds}}, "piece_s": {piece:
    seconds inside lane.moe}, "stray_piece_s": seconds that have a piece and
    lie outside lane.moe, "grouped_kernel_s": {pass: seconds of the grouped
    products' kernels}}``; no piece at all where ``piece_of`` is ``None``
    (a lane without experts)."""
    part_pass_s, piece_s, kernel_s, stray = {}, {}, {}, 0.0
    for name, seconds in op_s.items():
        part = part_of.get(name) or NO_PART
        passes = part_pass_s.setdefault(part, {})
        which = pass_of.get(name) or NO_PASS
        passes[which] = passes.get(which, 0.0) + seconds
        if name.startswith(GROUPED_KERNELS):
            kernel_s[which] = kernel_s.get(which, 0.0) + seconds
        if piece_of is None:
            continue
        piece = piece_of.get(name)
        if part == MOE:
            piece_s[piece or NO_PIECE] = piece_s.get(piece or NO_PIECE, 0.0) + seconds
        elif piece:
            stray += seconds
    return {"busy_s": sum(op_s.values()), "part_pass_s": part_pass_s,
            "piece_s": piece_s, "stray_piece_s": stray, "grouped_kernel_s": kernel_s}


def of(ctx):
    """:func:`split` of the traced run, made once and kept in ``ctx``;
    ``None`` where the run was not traced or the program names no pass."""
    if "lane_pieces" not in ctx:
        ctx["lane_pieces"] = _read(ctx)
    return ctx["lane_pieces"]


def _read(ctx):
    if ctx.get("trace") is None:
        return None
    import program_lane_parts
    import program_lane_pieces

    passes = program_lane_pieces.family_maps("passes")
    if not passes:
        return None
    found = split(ctx["trace"]["op_s"], _by_name(program_lane_parts.lane_maps()) or {},
                  _by_name(passes), _by_name(program_lane_pieces.family_maps("pieces")))
    part_s = lambda part: sum(found["part_pass_s"].get(part, {}).values())  # noqa: E731
    print("lane passes, busy seconds by part: %s" % json.dumps(found["part_pass_s"]))
    print("lane shares of busy, %%: %s" % json.dumps(
        {which: _share(found, _pass_s(found, which)) for which in PASSES}
        | {part: _share(found, part_s(part)) for part in PASSLESS}
        | {NO_PASS: _share(found, no_pass_s(found))}))
    if found["piece_s"]:
        print("expert layer, busy seconds by piece: %s; outside %s: %s; "
              "the grouped products' kernels by pass: %s"
              % (json.dumps(found["piece_s"]), MOE, found["stray_piece_s"],
                 json.dumps(found["grouped_kernel_s"])))
    return found


def _share(found, seconds):
    return 100.0 * seconds / found["busy_s"] if found["busy_s"] else None


def no_pass_s(found):
    """Busy seconds under no pass, outside the parts that have none by
    construction (their own shares hold them)."""
    return sum(passes.get(NO_PASS, 0.0) for part, passes in found["part_pass_s"].items()
               if part not in PASSLESS)


def _pass_s(found, which):
    return sum(passes.get(which, 0.0) for passes in found["part_pass_s"].values())


def pass_share(ctx, which):
    """Percent of the device's busy seconds in the pass ``which``."""
    found = of(ctx)
    return None if found is None else _share(found, _pass_s(found, which))


def piece_share(ctx, piece):
    """Percent of the device's busy seconds in ``piece`` of the expert
    layer; ``None`` where the lane has none."""
    found = of(ctx)
    if found is None or not found["piece_s"]:
        return None
    return _share(found, found["piece_s"].get(piece, 0.0))
