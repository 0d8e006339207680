"""What the program says of one instruction beside its names (ISSUE 52): its
opcode and what it reads, kept by the one parse of a program's text; its
kind (``obs.timeline.OP_KINDS``), decided by opcode alone; the part that an
instruction without one by name takes from what reads it
(``obs.profile.adopted_phase_map``); the call that offers all of it over the
sweep executables (``optimizers.sweep_instruction_facts``); and the
benchmark's join of it with a trace's seconds (``benchmark/lane_kinds.py``).
The lines are the TPU compiler's own, as ``compiled.as_text()`` prints them;
the chipless compile that holds the rules to its real text is in
``tests/test_tpu_aot.py``, which owns the topology's description."""

import jax
import jax.numpy as jnp
import pytest

from hpbandster_tpu.obs import profile
from hpbandster_tpu.obs.profile import (
    adopted_phase_map, device_kind_map, device_phase_map, parse_program_text)
from hpbandster_tpu.obs.timeline import (
    DEVICE_SCOPES, LANE_SCOPES, MOE_SCOPES, OP_KINDS, PASS_SCOPES)
from hpbandster_tpu.optimizers import (
    FusedBOHB, fused_bohb, sweep_instruction_facts, sweep_phase_maps)
from hpbandster_tpu.space import ConfigurationSpace, UniformFloatHyperparameter

import lane_names
from kimi_small import load

GQA = 'metadata={op_name="jit(f)/hpb.train/while/body/pass.forward/lane.gqa/dot_general"}'
FFN = 'metadata={op_name="jit(f)/hpb.train/while/body/pass.forward/lane.dense_ffn/dot_general"}'
UPDATE = 'metadata={op_name="jit(f)/hpb.train/lane.update/sub"}'
TRAIN = 'metadata={op_name="jit(f)/hpb.train/mul"}'


def module(entry, *computations):
    """A module's text of an ENTRY's instruction lines and whole
    computations before it."""
    return ("HloModule jit_f, is_scheduled=true\n\n" + "".join(c + "\n" for c in computations)
            + "ENTRY %main.1 (x.1: f32[4,8]) -> f32[4,8] {\n"
            + "  %x.1 = f32[4,8]{1,0:T(4,128)} parameter(0)\n"
            + "  %zero.1 = f32[]{:T(128)} constant(0)\n" + entry + "}\n")


def fused(name, *lines):
    return ("%%%s (p.%s: f32[4,8]) -> f32[4,8] {\n  %%p.%s = f32[4,8]{1,0:T(4,128)} parameter(0)\n"
            % (name, name, name) + "".join("  " + line + "\n" for line in lines) + "}\n")


# ---------------------------------------------------------------- the parse
def test_the_parse_keeps_opcode_operands_and_detail_of_the_compilers_lines():
    """Lines as the TPU compiler prints them: a tuple's shape with spaces and
    comments in it, operands by ``%``, attributes that name computations
    and predecessors (no operands), a parameter's number, an element's
    index, a custom call's target, the ROOT."""
    text = module(
        '  %copy-start.3 = (f32[4,8]{1,0:T(4,128)S(1)}, f32[4,8]{1,0:T(4,128)}, u32[]{:S(2)}) '
        'copy-start(%x.1), control-predecessors={%zero.1}\n'
        '  %copy-done.3 = f32[4,8]{1,0:T(4,128)S(1)} copy-done(%copy-start.3)\n'
        '  %call.7 = f32[4,8]{1,0:T(4,128)} custom-call(%copy-done.3, %x.1), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4,8]{1,0}, f32[4,8]{1,0}}\n'
        '  %tuple.2 = (s32[]{:T(128)}, f32[4,8]{1,0:T(4,128)}, /*index=2*/f32[4,8]{1,0:T(4,128)}) '
        'tuple(%zero.1, %call.7, %x.1)\n'
        '  %while.5 = (s32[]{:T(128)}, f32[4,8]{1,0:T(4,128)}, /*index=2*/f32[4,8]{1,0:T(4,128)}) '
        'while(%tuple.2), condition=%cond.1, body=%body.1\n'
        '  ROOT %out.6 = f32[4,8]{1,0:T(4,128)} get-tuple-element(%while.5), index=1\n')
    program = parse_program_text(text)
    got = {name: fact[:3] for name, fact in program.instructions.items()}
    assert got == {
        "x.1": ("parameter", (), 0),
        "zero.1": ("constant", (), None),
        "copy-start.3": ("copy-start", ("x.1",), None),
        "copy-done.3": ("copy-done", ("copy-start.3",), None),
        "call.7": ("custom-call", ("copy-done.3", "x.1"), "tpu_custom_call"),
        "tuple.2": ("tuple", ("zero.1", "call.7", "x.1"), None),
        "while.5": ("while", ("tuple.2",), None),
        "out.6": ("get-tuple-element", ("while.5",), 1),
    }
    assert program.roots == {"main.1": "out.6"}
    assert program.instructions["copy-done.3"][3] == "f32[4,8]{1,0:T(4,128)S(1)}"
    # the callees are where they were: the call graph's, not operands
    assert [callees for name, _, callees in program.computations["main.1"]
            if name == "while.5"] == [["cond.1", "body.1"]]


def test_an_operand_printed_with_its_shape_is_still_one_operand():
    text = module('  ROOT %add.2 = f32[4,8]{1,0:T(4,128)} add(f32[4,8]{1,0:T(4,128)} %x.1, '
                  'f32[4,8]{1,0:T(4,128)(2,1)S(1)} %x.1), ' + GQA + '\n')
    assert parse_program_text(text).instructions["add.2"][:2] == ("add", ("x.1", "x.1"))


# ---------------------------------------------------------------- the kinds
def one(line, *computations):
    return device_kind_map(module("  " + line + "\n", *computations))


@pytest.mark.parametrize("line, kind", [
    ('%k.2 = f32[4,8]{1,0} custom-call(%x.1), custom_call_target="tpu_custom_call"', "kernel"),
    # what the chip's compiler makes of ragged_dot (the LFM2 lane's text)
    ('%k.2 = f32[8192,3584]{1,0:T(8,128)} custom-call(%x.1, %x.1, /*index=2*/%x.1), '
     'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[1]{0}, s32[10]{0}}', "kernel"),
    # the compiler's bookkeeping: no kernels, no time
    ('%k.2 = f32[4,8]{1,0:T(4,128)S(1)} custom-call(), custom_call_target="AllocateBuffer"', "compute"),
    ('%k.2 = f32[8,8]{1,0} custom-call(%x.1, %x.1), custom_call_target="ConcatBitcast"', "copy"),
    ('%k.2 = f32[4,8]{0,1} copy(%x.1)', "copy"),
    ('%k.2 = (f32[4,8]{1,0:S(1)}, f32[4,8]{1,0}, u32[]{:S(2)}) copy-start(%x.1)', "copy"),
    ('%k.2 = f32[4,8]{1,0:S(1)} copy-done(%x.1)', "copy"),
    ('%k.2 = f32[8,4]{1,0} transpose(%x.1), dimensions={1,0}', "copy"),
    ('%k.2 = bf16[4,8]{1,0} convert(%x.1)', "cast_slice"),
    ('%k.2 = f32[1,8]{1,0} slice(%x.1), slice={[0:1], [0:8]}', "cast_slice"),
    ('%k.2 = f32[1,8]{1,0} dynamic-slice(%x.1, %zero.1, %zero.1), dynamic_slice_sizes={1,8}', "cast_slice"),
    ('%k.2 = ((f32[4,8]{1,0}), f32[1,8]{1,0:S(1)}, s32[]{:S(2)}) slice-start(%x.1), slice={[0:1], [0:8]}', "cast_slice"),
    ('%k.2 = f32[1,8]{1,0:S(1)} slice-done(%x.1)', "cast_slice"),
    ('%k.2 = f32[4,8]{1,0} dynamic-update-slice(%x.1, %x.1, %zero.1, %zero.1)', "cast_slice"),
    ('%k.2 = f32[8,8]{1,0} concatenate(%x.1, %x.1), dimensions={0}', "cast_slice"),
    ('%k.2 = f32[8,8]{1,0} pad(%x.1, %zero.1), padding=0_4x0_0', "cast_slice"),
    ('%k.2 = f32[4,8]{1,0} broadcast(%zero.1), dimensions={}', "fill"),
    ('%k.2 = s32[4,8]{1,0} iota(), iota_dimension=0', "fill"),
    # a broadcast of what was computed moves it: not a fill
    ('%k.2 = f32[2,4,8]{2,1,0} broadcast(%x.1), dimensions={1,2}', "compute"),
    ('%k.2 = f32[4,8]{1,0} add(%x.1, %x.1)', "compute"),
    ('%k.2 = f32[4,4]{1,0} convolution(%x.1, %x.1), dim_labels=bf_oi->bf', "compute"),
    ('%k.2 = f32[4,8]{1,0} bitcast(%x.1)', "compute"),
])
def test_a_kind_is_decided_by_the_opcode_alone(line, kind):
    kinds = one(line)
    assert kinds["k.2"] == kind
    assert kinds["x.1"] == kinds["zero.1"] == "compute"
    assert set(kinds.values()) <= set(OP_KINDS)


FUSION = '%%f.9 = f32[4,8]{1,0} fusion(%s), kind=kLoop, calls=%%inside'


@pytest.mark.parametrize("operands, lines, kind", [
    # a change of layout behind a bitcast: nothing but the copy counts
    ("%x.1", ["%b.1 = f32[8,4]{1,0} bitcast(%p.inside)",
              "ROOT %c.1 = f32[8,4]{0,1} copy(%b.1)"], "copy"),
    ("%x.1", ["ROOT %b.1 = f32[8,4]{1,0} bitcast(%p.inside)"], "copy"),
    # the cast of a slice of a stacked leaf, with a copy beside it
    ("%x.1, %zero.1", ["%s.1 = f32[1,8]{1,0} dynamic-slice(%p.inside, %zero.2, %zero.2)",
                       "%c.1 = f32[1,8]{0,1} copy(%s.1)",
                       "ROOT %v.1 = bf16[1,8]{0,1} convert(%c.1)"], "cast_slice"),
    # zeros: no operand but a constant, whatever is inside
    ("%zero.1", ["ROOT %b.1 = f32[4,8]{1,0} broadcast(%p.inside), dimensions={}"], "fill"),
    ("", ["%i.1 = s32[4,8]{1,0} iota(), iota_dimension=0",
          "ROOT %v.1 = f32[4,8]{1,0} convert(%i.1)"], "fill"),
    ("%x.1", ["%v.1 = bf16[4,8]{1,0} convert(%p.inside)",
              "ROOT %m.1 = bf16[4,8]{1,0} multiply(%v.1, %v.1)"], "compute"),
    # a broadcast beside a cast reads what was computed: no fill, no cast
    ("%x.1", ["%b.1 = f32[2,4,8]{2,1,0} broadcast(%p.inside), dimensions={1,2}",
              "ROOT %v.1 = bf16[2,4,8]{2,1,0} convert(%b.1)"], "compute"),
])
def test_a_fusion_is_judged_by_what_it_fuses(operands, lines, kind):
    kinds = one(FUSION % operands, fused("inside", *lines))
    assert kinds["f.9"] == kind
    assert set(kinds.values()) <= set(OP_KINDS)


def test_what_the_chip_runs_beside_others_is_of_the_kind_of_what_it_wraps():
    """The chip's own text of a lane's sweep: a slice into the fast memory
    is an ``async-start`` around a computation of one ``slice``, and the
    ``async-done`` that finishes it names no computation."""
    kinds = device_kind_map(module(
        "  %slice-start.3 = ((f32[4,8]{1,0:T(4,128)}), f32[1,8]{1,0:T(4,128)S(1)}, s32[]{:S(2)}) "
        "async-start(%x.1), calls=%inside\n"
        "  %slice-done.3 = f32[1,8]{1,0:T(4,128)S(1)} async-done(%slice-start.3)\n"
        "  ROOT %a.1 = f32[1,8]{1,0} add(%slice-done.3, %slice-done.3), " + GQA + "\n",
        fused("inside", "ROOT %s.1 = f32[1,8]{1,0:T(4,128)S(1)} slice(%p.inside), slice={[0:1], [0:8]}")))
    assert kinds["slice-start.3"] == kinds["slice-done.3"] == "cast_slice"
    # ... and so the walk passes through both
    adopted = adopted_phase_map(module(
        "  %v.1 = bf16[4,8]{1,0} convert(%x.1)\n"
        "  %slice-start.3 = ((bf16[4,8]{1,0}), bf16[1,8]{1,0:S(1)}, s32[]{:S(2)}) "
        "async-start(%v.1), calls=%inside\n"
        "  %slice-done.3 = bf16[1,8]{1,0:S(1)} async-done(%slice-start.3)\n"
        "  ROOT %a.1 = bf16[1,8]{1,0} add(%slice-done.3, %slice-done.3), " + GQA + "\n",
        fused("inside", "ROOT %s.1 = bf16[1,8]{1,0:S(1)} slice(%p.inside), slice={[0:1], [0:8]}")),
        LANE_SCOPES)
    assert adopted["v.1"] == adopted["slice-start.3"] == adopted["slice-done.3"] == "lane.gqa"


# ------------------------------------------------------------- the adoption
#: a loop over layers: the stacked weights' cast is lifted out of it (no
#: name), its slice inside is read by a product under ``lane.gqa``
LOOP = (
    "%body.1 (s.1: (s32[], f32[4,8], bf16[2,8,8])) -> (s32[], f32[4,8], bf16[2,8,8]) {\n"
    "  %s.1 = (s32[]{:T(128)}, f32[4,8]{1,0}, bf16[2,8,8]{2,1,0}) parameter(0)\n"
    "  %i.1 = s32[]{:T(128)} get-tuple-element(%s.1), index=0\n"
    "  %h.1 = f32[4,8]{1,0} get-tuple-element(%s.1), index=1\n"
    "  %w.1 = bf16[2,8,8]{2,1,0} get-tuple-element(%s.1), index=2\n"
    "  %w.2 = bf16[1,8,8]{2,1,0} dynamic-slice(%w.1, %i.1, %i.1, %i.1), dynamic_slice_sizes={1,8,8}\n"
    "  %w.3 = bf16[8,8]{1,0} bitcast(%w.2)\n"
    "  %dot.1 = f32[4,8]{1,0} convolution(%h.1, %w.3), dim_labels=bf_io->bf, " + GQA + "\n"
    "  %next.1 = s32[]{:T(128)} add(%i.1, %i.1)\n"
    "  ROOT %t.1 = (s32[]{:T(128)}, f32[4,8]{1,0}, bf16[2,8,8]{2,1,0}) tuple(%next.1, %dot.1, %w.1)\n"
    "}\n")
COND = ("%cond.1 (c.1: (s32[], f32[4,8], bf16[2,8,8])) -> pred[] {\n"
        "  %c.1 = (s32[]{:T(128)}, f32[4,8]{1,0}, bf16[2,8,8]{2,1,0}) parameter(0)\n"
        "  %c.2 = s32[]{:T(128)} get-tuple-element(%c.1), index=0\n"
        "  ROOT %c.3 = pred[]{:T(512)} compare(%c.2, %c.2), direction=LT\n}\n")
ENTRY = (
    "  %ws.1 = f32[2,8,8]{2,1,0} parameter(1)\n"
    "  %lifted.1 = bf16[2,8,8]{2,1,0} convert(%ws.1)\n"
    "  %moved.1 = bf16[2,8,8]{2,1,0:S(1)} copy(%lifted.1)\n"
    "  %i.0 = s32[]{:T(128)} constant(0)\n"
    "  %state.1 = (s32[]{:T(128)}, f32[4,8]{1,0}, bf16[2,8,8]{2,1,0:S(1)}) tuple(%i.0, %x.1, %moved.1)\n"
    "  %loop.1 = (s32[]{:T(128)}, f32[4,8]{1,0}, bf16[2,8,8]{2,1,0:S(1)}) while(%state.1), "
    "condition=%cond.1, body=%body.1\n"
    "  %h.9 = f32[4,8]{1,0} get-tuple-element(%loop.1), index=1\n")


def test_a_cast_lifted_out_of_a_loop_is_the_part_that_reads_it_inside():
    text = module(ENTRY + "  ROOT %out.1 = f32[4,8]{1,0} copy(%h.9)\n", LOOP, COND)
    program = parse_program_text(text)
    by_name = device_phase_map(program, LANE_SCOPES)
    assert by_name == {"dot.1": "lane.gqa"}
    adopted = adopted_phase_map(program, LANE_SCOPES)
    # through the copy, the tuple, the loop's boundary (element 2 stays
    # element 2), the slice and the bitcast
    for name in ("lifted.1", "moved.1", "w.1", "w.2", "w.3"):
        assert adopted[name] == "lane.gqa", name
    # the counter is read by the slice as its index, from the body's ROOT
    # to the next turn's parameter; nothing reads the condition's compare
    assert adopted["next.1"] == adopted["i.0"] == "lane.gqa" and "c.3" not in adopted
    # what reads the loop's result is found the other way, from what the
    # last turn left
    assert adopted["out.1"] == adopted["h.9"] == "lane.gqa"
    # a name is never overridden, and the named map is not touched
    assert "dot.1" not in adopted
    assert device_phase_map(program, LANE_SCOPES) == by_name
    # no lane part in the text under another list: nothing to adopt from
    assert adopted_phase_map(program, MOE_SCOPES) == {}


def test_a_copy_read_by_two_parts_is_an_orphan_and_one_read_by_one_is_its():
    text = module(
        "  %both.1 = f32[4,8]{0,1} copy(%x.1)\n"
        "  %a.1 = f32[4,8]{1,0} add(%both.1, %both.1), " + GQA + "\n"
        "  %mine.1 = f32[4,8]{0,1} copy(%a.1)\n"
        "  %b.1 = f32[4,8]{1,0} add(%both.1, %mine.1), " + FFN + "\n"
        "  %zeros.1 = f32[4,8]{1,0} broadcast(%zero.1), dimensions={}\n"
        "  %scaled.1 = f32[4,8]{1,0} multiply(%x.1, %x.1), " + TRAIN + "\n"
        "  ROOT %m.1 = f32[4,8]{1,0} subtract(%zeros.1, %scaled.1), " + UPDATE + "\n")
    program = parse_program_text(text)
    adopted = adopted_phase_map(program, LANE_SCOPES)
    assert "both.1" not in adopted           # two parts read it: no vote
    assert adopted["mine.1"] == "lane.dense_ffn"   # between two parts: the reader's
    assert adopted["zeros.1"] == "lane.update"     # the fill of the momentum's zeros
    # named work outside every lane scope is offered a part too; its name
    # says which seconds a scope could name
    assert adopted["scaled.1"] == "lane.update"
    assert device_phase_map(program, LANE_SCOPES).keys() == {"a.1", "b.1", "m.1"}


def test_the_walk_stops_at_its_depth(monkeypatch):
    chain = "".join(
        "  %%c.%d = f32[4,8]{1,0} copy(%%c.%d)\n" % (i + 1, i) for i in range(1, 12))
    text = module("  %c.1 = f32[4,8]{0,1} copy(%x.1)\n" + chain
                  + "  ROOT %a.1 = f32[4,8]{1,0} add(%c.12, %c.12), " + GQA + "\n")
    program = parse_program_text(text)
    depth = profile._ADOPTION_DEPTH
    assert 4 <= depth <= 12
    adopted = adopted_phase_map(program, LANE_SCOPES)
    # c.12 is one instruction from the product; c.(13 - depth) is the last within reach
    assert {n for n in adopted if n.startswith("c.")} == {
        "c.%d" % i for i in range(13 - depth, 13)}
    monkeypatch.setattr(profile, "_ADOPTION_DEPTH", 2)
    assert set(adopted_phase_map(program, LANE_SCOPES)) == {"c.11", "c.12"}


def test_a_reader_that_computes_ends_its_branch_with_nothing():
    text = module(
        "  %v.1 = bf16[4,8]{1,0} convert(%x.1)\n"
        "  %n.1 = bf16[4,8]{1,0} negate(%v.1)\n"
        "  ROOT %a.1 = bf16[4,8]{1,0} add(%n.1, %n.1), " + GQA + "\n")
    adopted = adopted_phase_map(text, LANE_SCOPES)
    # the nameless negation is its reader's; the cast before it is read by
    # an operation without a part, which is no move: the walk ends there
    assert adopted == {"n.1": "lane.gqa"}


def test_a_conditional_and_a_call_are_entered_by_operand_position():
    branch = ("%%%s (q.%s: f32[4,8]) -> f32[4,8] {\n  %%q.%s = f32[4,8]{1,0} parameter(0)\n"
              "  ROOT %%r.%s = f32[4,8]{1,0} negate(%%q.%s)%s\n}\n")
    called = ("%called.1 (u.1: f32[4,8], u.2: f32[4,8]) -> f32[4,8] {\n"
              "  %u.1 = f32[4,8]{1,0} parameter(0)\n  %u.2 = f32[4,8]{1,0} parameter(1)\n"
              "  %u.3 = f32[4,8]{1,0} negate(%u.1)\n"
              "  ROOT %u.4 = f32[4,8]{1,0} add(%u.2, %u.2), " + FFN + "\n}\n")
    text = module(
        "  %p.1 = pred[]{:T(512)} constant(true)\n"
        "  %first.1 = f32[4,8]{0,1} copy(%x.1)\n"
        "  %second.1 = f32[4,8]{0,1} copy(%x.1)\n"
        "  %cond.2 = f32[4,8]{1,0} conditional(%p.1, %first.1, %second.1), "
        "true_computation=%yes, false_computation=%no\n"
        "  %third.1 = f32[4,8]{0,1} copy(%x.1)\n"
        "  %fourth.1 = f32[4,8]{0,1} copy(%x.1)\n"
        "  ROOT %call.2 = f32[4,8]{1,0} call(%third.1, %fourth.1), to_apply=%called.1\n",
        branch % (("yes",) * 5 + (", " + GQA,)), branch % (("no",) * 5 + ("",)), called)
    adopted = adopted_phase_map(text, LANE_SCOPES)
    assert adopted["first.1"] == "lane.gqa" and adopted["q.yes"] == "lane.gqa"
    assert "second.1" not in adopted    # its branch names nothing
    assert adopted["fourth.1"] == "lane.dense_ffn" and "third.1" not in adopted


# ------------------------------------------------- the call over the sweeps
def _toy_lane(vec, budget):
    """A lane in little: a stacked leaf cast and read in a loop under one
    part, a second part, a step under ``lane.update``, and a scaling that
    stands outside every part."""
    w = jnp.ones((3, 8, 8), jnp.float32) * vec[0]
    x = jnp.ones((4, 8), jnp.float32)

    def layer(h, w_l):
        with jax.named_scope("lane.gqa"):
            h = jnp.tanh(h @ w_l.astype(jnp.bfloat16).astype(jnp.float32))
        with jax.named_scope("lane.dense_ffn"):
            return h + jax.nn.silu(h), None

    def loss(w):
        h, _ = jax.lax.scan(layer, x, w)
        with jax.named_scope("lane.head"):
            return jnp.mean(h * h)

    def step(_, w):
        g = jax.grad(loss)(w)
        with jax.named_scope("lane.update"):
            return w - vec[1] * g

    return loss(jax.lax.fori_loop(0, int(budget), step, w))


@pytest.fixture(scope="module")
def toy_sweep():
    space = ConfigurationSpace(seed=3)
    space.add_hyperparameters([UniformFloatHyperparameter("scale", 0.5, 1.5),
                               UniformFloatHyperparameter("lr", 0.01, 0.1)])
    fused_bohb._SWEEP_EXE_CACHE.clear()
    opt = FusedBOHB(configspace=space, eval_fn=_toy_lane, run_id="facts",
                    min_budget=1, max_budget=9, eta=3, seed=3)
    with lane_names.compiled_here():
        opt.run(n_iterations=1)
    yield opt
    fused_bohb._SWEEP_EXE_CACHE.clear()


def test_every_instruction_of_a_sweep_has_its_facts_and_the_maps_are_the_parents(toy_sweep):
    text = toy_sweep.last_executable.as_text()
    (facts,) = sweep_instruction_facts(LANE_SCOPES).values()
    (parts,) = sweep_phase_maps(LANE_SCOPES).values()
    program = parse_program_text(text)
    assert facts.keys() == program.instructions.keys()
    assert {"opcode", "kind", "named", "adopted"} <= set(next(iter(facts.values())))
    assert {fact["kind"] for fact in facts.values()} <= set(OP_KINDS)
    # adopted and named by a part never meet, and a part adopts only a name of the list
    adopted = {name: fact["adopted"] for name, fact in facts.items() if fact["adopted"]}
    assert adopted and not set(adopted) & set(parts)
    assert set(adopted.values()) <= set(parts.values()) <= set(LANE_SCOPES)
    assert all(fact["named"] == (fact["op_name"] is not None) for fact in facts.values())
    # what the named maps return is what they returned before the operands
    # were kept: PR 51's parse entry for entry, PR 37's reader, all four lists
    lane_names.check_the_maps_are_what_they_were(text)
    for scopes in (DEVICE_SCOPES, LANE_SCOPES, PASS_SCOPES, MOE_SCOPES):
        assert sweep_phase_maps(scopes) == (
            {program.module: device_phase_map(text, scopes)}
            if device_phase_map(text, scopes) else {})
    # an executable that names nothing of a list is left out whole
    assert sweep_instruction_facts(MOE_SCOPES) == {}


def test_the_five_seconds_by_kind_add_up_to_the_seconds_in_no_part(toy_sweep, monkeypatch):
    """``benchmark/lane_kinds.py`` on a made-up ``op_s`` over the sweep's own
    instruction names, and on a name that no text holds."""
    import sys

    for name in ("lane_pieces", "program_lane_parts", "program_lane_kinds", "lane_kinds"):
        monkeypatch.setitem(sys.modules, name, load(name + ".py"))
    lane_kinds = sys.modules["lane_kinds"]
    (facts,) = sweep_instruction_facts(LANE_SCOPES).values()
    (parts,) = sweep_phase_maps(LANE_SCOPES).values()
    op_s = {name: 1.0 + i % 7 for i, name in enumerate(sorted(facts))}
    op_s["a_program_of_its_own.1"] = 25.0
    ctx = {"trace": {"op_s": op_s, "spans": 2}}
    found = lane_kinds.of(ctx)
    assert lane_kinds.of(ctx) is found      # made once, kept in ctx
    no_part = sum(s for name, s in op_s.items() if name not in parts)
    assert sum(found["no_part_s"].values()) == pytest.approx(no_part)
    assert found["no_part_s"]["named"] == pytest.approx(sum(
        s for name, s in op_s.items() if name in facts and name not in parts
        and facts[name]["named"]))
    nameless = no_part - found["no_part_s"]["named"]
    assert sum(found["adopted_s"].values()) + found["orphan_s"] == pytest.approx(nameless)
    assert found["adopted_s"] and found["orphan_s"] >= 25.0
    assert sum(sum(k.values()) for k in found["part_kind_s"].values()) == pytest.approx(
        found["busy_s"]) == pytest.approx(sum(op_s.values()))
    shares = [load("layer_metrics", "lane.no_part_%s_device_share.py" % k).read(ctx)
              for k in lane_kinds.NO_PART_SPLIT]
    assert sum(shares) == pytest.approx(100.0 * no_part / found["busy_s"])
    assert load("layer_metrics", "lane.adopted_device_share.py").read(ctx) == pytest.approx(
        100.0 * sum(found["adopted_s"].values()) / found["busy_s"])
    assert load("layer_metrics", "lane.kernel_device_share.py").read(ctx) == 0.0
    table = lane_kinds.table(found, 1)
    assert "a_program_of_its_own.1" in table and "adopted" in table


def test_a_program_without_the_call_gives_nothing(monkeypatch):
    import sys

    from hpbandster_tpu import optimizers

    for name in ("lane_pieces", "program_lane_parts", "program_lane_kinds", "lane_kinds"):
        monkeypatch.setitem(sys.modules, name, load(name + ".py"))
    monkeypatch.delattr(optimizers, "sweep_instruction_facts")
    assert sys.modules["program_lane_kinds"].instruction_facts() is None
    ctx = {"trace": {"op_s": {"fusion.1": 1.0}, "spans": 2}}
    for name in ("no_part_named", "no_part_copy", "no_part_cast_slice", "no_part_fill",
                 "no_part_other", "adopted", "kernel"):
        assert load("layer_metrics", "lane.%s_device_share.py" % name).read(ctx) is None
    assert load("layer_metrics", "lane.kernel_device_share.py").read({"trace": None}) is None
