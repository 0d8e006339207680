"""What the lane tests share about the names a lane leaves in its compiled
program: the passes (``obs.timeline.PASS_SCOPES``), the expert layer's
pieces (``MOE_SCOPES``), and ``device_phase_map`` as it stood before either
(PR 37's, kept here as the reference that the part and phase readers are
held to, entry for entry); and where a jaxpr draws its random bits, inside
a loop or outside every one."""

import contextlib
import re

import jax

from hpbandster_tpu.obs.profile import device_phase_map, parse_program_text
from hpbandster_tpu.obs.timeline import DEVICE_SCOPES, LANE_SCOPES, MOE_SCOPES, PASS_SCOPES

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEES = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_SCOPE_NAME = re.compile(r"[a-z]+\.[a-z_]+")


@contextlib.contextmanager
def compiled_here():
    """Compile, never load: an executable out of the persistent cache carries
    the names of the commit that compiled it first, which may have had no
    pass and no piece (the cache's key leaves metadata out)."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


def random_bits(jaxpr, in_loop=False, found=None):
    """``[outside every loop, inside one]``: the equations of a jaxpr that
    draw random bits, wherever they are nested (a ``fori_loop`` is a
    ``scan`` where its trip count is concrete, else a ``while``)."""
    found = [0, 0] if found is None else found
    for eqn in jaxpr.eqns:
        found[in_loop] += eqn.primitive.name == "random_bits"
        inside = in_loop or eqn.primitive.name in ("while", "scan")
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    random_bits(sub, inside, found)
    return found


def reference_phase_map(text, scopes=DEVICE_SCOPES):
    """PR 37's ``device_phase_map``: one parse a family, the last name of
    the family found in an ``op_name`` wins."""
    computations, entry, current = {}, None, None
    for line in text.splitlines():
        header = _COMPUTATION.match(line)
        if header is not None:
            current = computations.setdefault(header.group(2), [])
            if header.group(1):
                entry = header.group(2)
            continue
        instruction = _INSTRUCTION.match(line)
        if instruction is None or current is None:
            continue
        op_name = _OP_NAME.search(line)
        found = [s for s in _SCOPE_NAME.findall(op_name.group(1)) if s in scopes
                 ] if op_name else []
        callees = _CALLEES.findall(line)
        for group in _BRANCHES.findall(line):
            callees += [c.strip().lstrip("%") for c in group.split(",")]
        current.append((instruction.group(1), found[-1] if found else None, callees))
    phases, inherited, queue = {}, {entry: None}, [entry]
    while queue:
        name = queue.pop()
        for instruction, own, callees in computations.get(name, ()):
            phase = own or inherited[name]
            if phase is not None:
                phases[instruction] = phase
            for callee in callees:
                if callee not in inherited:
                    inherited[callee] = phase
                    queue.append(callee)
    return phases


def parsed_before_the_operands(text):
    """``(module, entry, computations)`` as PR 51's ``parse_program_text``
    returned them, line for line its loop: what ``device_phase_map`` reads of
    a text, before the parse kept opcodes and operands beside it (PR 52)."""
    computations, names, entry, current = {}, {}, None, None
    for line in text.splitlines():
        header = _COMPUTATION.match(line)
        if header is not None:
            current = computations.setdefault(header.group(2), [])
            if header.group(1):
                entry = header.group(2)
            continue
        instruction = _INSTRUCTION.match(line)
        if instruction is None or current is None:
            continue
        op_name = _OP_NAME.search(line)
        callees = _CALLEES.findall(line)
        for group in _BRANCHES.findall(line):
            callees += [c.strip().lstrip("%") for c in group.split(",")]
        current.append((
            instruction.group(1),
            names.setdefault(op_name.group(1), op_name.group(1)) if op_name else None,
            callees))
    return re.match(r"^HloModule ([^\s,]+)", text).group(1), entry, computations


def check_the_maps_are_what_they_were(text):
    """``device_phase_map`` under all four lists returns what PR 51's
    returned: the fields it reads of the parse are PR 51's, entry for
    entry, and without the new ones it returns the same."""
    program = parse_program_text(text)
    assert tuple(program[:3]) == parsed_before_the_operands(text)
    bare = program._replace(instructions={}, roots={})
    for scopes in (DEVICE_SCOPES, LANE_SCOPES, PASS_SCOPES, MOE_SCOPES):
        assert device_phase_map(program, scopes) == device_phase_map(bare, scopes)
        assert device_phase_map(text, scopes) == device_phase_map(bare, scopes)
    for scopes in (DEVICE_SCOPES, LANE_SCOPES, MOE_SCOPES):
        assert device_phase_map(program, scopes) == reference_phase_map(text, scopes)
    return program


def check_the_older_readers_read_what_they_read(text):
    """The parts' and the phases' maps, from one parse, are PR 37's."""
    program = parse_program_text(text)
    for scopes in (DEVICE_SCOPES, LANE_SCOPES):
        want = reference_phase_map(text, scopes)
        assert want and device_phase_map(program, scopes) == want
        assert device_phase_map(text, scopes) == want


def check_the_trainer_names_its_passes(text, parts):
    """Every instruction in a part of the lane other than the update and
    the sums has a pass, all three passes occur, and the update and the sums
    carry none (but for what the compiler fused into them: an instruction
    inside a fusion is not one a trace prints)."""
    program = parse_program_text(text)
    passes = device_phase_map(program, PASS_SCOPES)
    assert set(passes.values()) == set(PASS_SCOPES)
    passless = {"lane.update", "lane.accumulate"}
    without = [n for n, part in parts.items() if part not in passless and n not in passes]
    assert not without, without[:5]
    inside = [name for name in program.computations
              if name.startswith("fused_computation") or ".clone" in name]
    fused = set()
    while inside:       # and what a fused instruction calls (a reduction's region)
        for n, _, callees in program.computations.get(inside.pop(), ()):
            fused.add(n)
            inside += [c for c in callees if c in program.computations]
    stepped = [n for n, part in parts.items()
               if part in passless and n in passes and n not in fused]
    assert not stepped, stepped[:5]
    # every layer's part is computed forward, again, and backward (the
    # exits' gradient is one ``jax.grad``, the backward pass's whole)
    for part in set(parts.values()) - passless - {"lane.exit", "lane.head"}:
        seen = {passes[n] for n, p in parts.items() if p == part}
        assert seen == set(PASS_SCOPES), (part, seen)
    return passes


def check_the_expert_layer_names_its_pieces(text, parts, shared):
    """Every instruction in ``lane.moe`` has a piece or is counted as none
    (a few: the layer's counters, a reshape), every piece occurs (the
    shared expert's where the layer has one), and the rules written by hand
    name theirs in both passes."""
    pieces = device_phase_map(text, MOE_SCOPES)
    passes = device_phase_map(text, PASS_SCOPES)
    in_moe = [n for n, part in parts.items() if part == "lane.moe"]
    none = [n for n in in_moe if n not in pieces]
    assert len(in_moe) > 1000 and len(none) < 0.02 * len(in_moe), (
        len(in_moe), len(none), none[:5])
    want = set(MOE_SCOPES) - (set() if shared else {"moe.shared"})
    assert {pieces[n] for n in in_moe if n in pieces} == want
    for piece in ("moe.dispatch", "moe.experts", "moe.combine"):
        seen = {passes.get(n) for n in in_moe if pieces.get(n) == piece}
        assert {"pass.forward", "pass.backward"} <= seen, (piece, seen)
    # the tiles' recomputation inside the backward rule is the experts'
    assert "pass.recompute" in {passes.get(n) for n in in_moe if pieces.get(n) == "moe.experts"}
    return pieces
