"""The device's seconds in a lane by kind of operation, and what the seconds
in no part are: the harness's own reduction of the trace
(``ctx["trace"]["op_s"]``, self seconds per instruction name; the trace is
not read again) joined with the program's map from instruction to lane part
(``program_lane_parts.py``) and with what it says of every instruction
(``program_lane_kinds.py``), by ``lane_counts.lane_spans``' rule for a name.

A part's seconds split by kind: its Pallas kernels, the compiler's copies,
casts and slices, fills, and everything else. The seconds in no part
(``lane.no_part_device_share``) split five ways: work that has an
``op_name`` and stands outside every lane scope (a scope can name it), and
what the compiler made itself and left without a name, by kind: copies,
casts and slices, fills, and the rest. Of the nameless seconds the program
gives some the part of what reads them (``adopted``); the rest are orphans.
An operation that no sweep executable's text holds has no fact: it is
nameless, of no kind ("other") and an orphan.
"""

import time

import lane_pieces

NO_PART = lane_pieces.NO_PART
KINDS = ("kernel", "copy", "cast_slice", "fill", "compute")
#: the five shares that add up to ``lane.no_part_device_share``
NO_PART_SPLIT = ("named", "copy", "cast_slice", "fill", "other")
UNKNOWN = "not in the text"
ORPHANS = 20
NAMED = 10


def split(op_s, part_of, fact_of):
    """``op_s`` ``{instruction name: busy seconds}`` -> ``{"busy_s",
    "part_kind_s": {part: {kind: seconds}}, "no_part_s": {one of
    NO_PART_SPLIT: seconds}, "adopted_s": {part: nameless no-part seconds it
    adopts}, "named_adopted_s": the same of the named ones, "orphan_s":
    nameless no-part seconds no part adopts, "kernel_s", "orphans" and
    "named": [(seconds, name, fact)] of the nameless no-part operations no
    part adopts and of the named no-part ones, heaviest first}``."""
    part_kind_s, no_part_s = {}, dict.fromkeys(NO_PART_SPLIT, 0.0)
    adopted_s, named_adopted_s, orphans, named = {}, {}, [], []
    for name, seconds in op_s.items():
        fact = fact_of.get(name) or {}
        kind = fact.get("kind", UNKNOWN)
        part = part_of.get(name) or NO_PART
        kinds = part_kind_s.setdefault(part, {})
        kinds[kind] = kinds.get(kind, 0.0) + seconds
        if part != NO_PART:
            continue
        has_name, adopter = fact.get("named", False), fact.get("adopted")
        no_part_s["named" if has_name else kind if kind in no_part_s else "other"] += seconds
        if has_name:
            named.append((seconds, name, fact))
        elif not adopter:
            orphans.append((seconds, name, fact))
        if adopter:
            into = named_adopted_s if has_name else adopted_s
            into[adopter] = into.get(adopter, 0.0) + seconds
    heaviest = lambda rows: sorted(rows, key=lambda row: -row[0])  # noqa: E731
    return {
        "busy_s": sum(op_s.values()), "part_kind_s": part_kind_s,
        "no_part_s": no_part_s, "adopted_s": adopted_s,
        "named_adopted_s": named_adopted_s,
        "orphan_s": sum(row[0] for row in orphans),
        "kernel_s": sum(kinds.get("kernel", 0.0) for kinds in part_kind_s.values()),
        "orphans": heaviest(orphans), "named": heaviest(named)}


def of(ctx):
    """:func:`split` of the traced run, made once and kept in ``ctx``;
    ``None`` where the run was not traced or the program says nothing of its
    instructions."""
    if "lane_kinds" not in ctx:
        ctx["lane_kinds"] = _read(ctx)
    return ctx["lane_kinds"]


def _read(ctx):
    if ctx.get("trace") is None:
        return None
    import program_lane_kinds
    import program_lane_parts

    t0 = time.perf_counter()
    facts = program_lane_kinds.instruction_facts()
    if not facts:
        return None
    print("instruction facts of %d instruction(s) in %.3f s" % (
        sum(map(len, facts.values())), time.perf_counter() - t0))
    found = split(ctx["trace"]["op_s"],
                  lane_pieces._by_name(program_lane_parts.lane_maps()) or {},
                  lane_pieces._by_name(facts))
    # the harness writes two spans a sweep, its construction and its run
    print(table(found, ctx["trace"]["spans"] // 2))
    return found


def table(found, sweeps):
    """What the by-hand scripts made: busy seconds a sweep by part x kind,
    the adopted seconds by the part that adopts them, the heaviest orphans
    and the heaviest named operations outside every part."""
    a_sweep = lambda seconds: "%9.4f" % (seconds / sweeps)  # noqa: E731
    columns = KINDS + tuple(
        sorted({k for kinds in found["part_kind_s"].values() for k in kinds} - set(KINDS)))
    lines = ["lane kinds, busy seconds a sweep by part x kind (%d traced sweeps):" % sweeps,
             "  %-16s" % "part" + "".join("%16s" % c for c in columns) + "%10s" % "all"]
    for part, kinds in sorted(found["part_kind_s"].items(), key=lambda kv: -sum(kv[1].values())):
        lines.append("  %-16s" % part + "".join(
            "%16s" % a_sweep(kinds.get(c, 0.0)) for c in columns) + " " + a_sweep(sum(kinds.values())))
    no_part = found["no_part_s"]
    lines.append("in %s, seconds a sweep: %s" % (NO_PART, ", ".join(
        "%s %s" % (k, a_sweep(no_part[k]).strip()) for k in NO_PART_SPLIT)))
    nameless = sum(no_part[k] for k in NO_PART_SPLIT if k != "named")
    lines.append(
        "nameless in %s: adopted %s + orphaned %s = %s s a sweep; adopted by: %s; "
        "named and read by one part alone: %s"
        % (NO_PART, a_sweep(sum(found["adopted_s"].values())).strip(),
           a_sweep(found["orphan_s"]).strip(), a_sweep(nameless).strip(),
           _by_part(found["adopted_s"], sweeps), _by_part(found["named_adopted_s"], sweeps)))
    for title, rows in (
            ("the %d heaviest orphans, nameless and read by no one part" % ORPHANS,
             found["orphans"][:ORPHANS]),
            ("named in %s, the %d heaviest (a scope can name them)" % (NO_PART, NAMED),
             found["named"][:NAMED])):
        lines.append("%s (seconds a sweep, name, opcode, kind, adopted by, shape, op_name):" % title)
        for seconds, name, fact in rows:
            lines.append("  %s %-36s %-14s %-10s %-14s %-40s %s" % (
                a_sweep(seconds), name, fact.get("opcode", UNKNOWN), fact.get("kind", ""),
                fact.get("adopted") or "-", fact.get("shape", ""),
                (fact.get("op_name") or "")[-80:]))
    return "\n".join(lines)


def _by_part(seconds_by_part, sweeps):
    return ", ".join("%s %.4f" % (part, s / sweeps) for part, s in sorted(
        seconds_by_part.items(), key=lambda kv: -kv[1])) or "none"


def no_part_share(ctx, which):
    """Percent of the device's busy seconds in no lane part and in
    ``which``, one of :data:`NO_PART_SPLIT`."""
    found = of(ctx)
    return None if found is None else lane_pieces._share(found, found["no_part_s"][which])


def adopted_share(ctx):
    """Percent of the device's busy seconds that have no name, lie in no
    part, and are read by one part alone."""
    found = of(ctx)
    return None if found is None else lane_pieces._share(found, sum(found["adopted_s"].values()))


def kernel_share(ctx):
    """Percent of the device's busy seconds in kernels, in any part."""
    found = of(ctx)
    return None if found is None else lane_pieces._share(found, found["kernel_s"])
