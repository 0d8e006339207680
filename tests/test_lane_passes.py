"""The names a lane leaves beside its parts (ISSUE 38): the rule that reads
a pass off an ``op_name`` as the compiler writes it, one parse of a
program's text for every family of names, and the names as metadata only
(each lane's lowered program is the one that no scope at all lowers to)."""

import contextlib
import sys

import jax
import jax.numpy as jnp
import pytest

from hpbandster_tpu.obs.profile import (
    ProgramText, device_phase_map, hlo_module_name, parse_program_text)
from hpbandster_tpu.obs.timeline import (
    DEVICE_SCOPES, LANE_SCOPES, MOE_SCOPES, PASS_SCOPES)
from hpbandster_tpu.optimizers import fused_bohb, sweep_phase_maps

import kimi_small
import lane_names
import mellum2_small
import ouro_small

#: ``op_name`` as jax 0.9.0 writes it -> (pass, part, piece); the first
#: three are ISSUE 38's, the others read off the small lanes' compiled text
OP_NAMES = {
    "jit(step)/pass.recompute/jvp(lane.gqa)/cos":
        ("pass.recompute", "lane.gqa", None),
    "jit(step)/pass.backward/transpose(jvp(lane.gqa))/mul":
        ("pass.backward", "lane.gqa", None),
    # a custom_vjp's backward rule: the scope that was ambient where the
    # primal was traced comes back wrapped, and does not count
    "jit(step)/pass.backward/transpose(pass.recompute)/jvp(lane.moe)/lane.moe/dot_general":
        ("pass.backward", "lane.moe", None),
    # ... and so it does in a loop's backward pass, under the loop's vmap
    "jit(f)/while/body/cond/branch_1_fun/pass.backward/transpose(jvp(lane.swa))"
    "/vmap(pass.recompute)/jvp(lane.swa)/vmap()/checkpoint/mul":
        ("pass.backward", "lane.swa", None),
    # what jax.checkpoint computes again in a backward pass
    "jit(f)/while/body/cond/branch_1_fun/pass.backward/transpose(jvp(lane.swa))"
    "/vmap(pass.recompute)/jvp(lane.swa)/vmap()/checkpoint/rematted_computation/exp":
        ("pass.recompute", "lane.swa", None),
    "jit(f)/pass.backward/transpose(jvp(pass.backward))/jvp()/checkpoint"
    "/rematted_computation/lane.head/jit(log_softmax)/sub":
        ("pass.recompute", "lane.head", None),
    # a recomputation inside a backward rule stands outside and wins
    "jit(f)/pass.backward/transpose(pass.recompute)/jvp(lane.moe)/lane.moe/moe.experts"
    "/while/body/moe.experts/pass.recompute/jvp()/ragged_dot_general":
        ("pass.recompute", "lane.moe", "moe.experts"),
    "jit(f)/pass.backward/transpose(pass.recompute)/jvp(lane.moe)/lane.moe/moe.experts"
    "/while/body/moe.experts/transpose(jvp())/ragged_dot_general":
        ("pass.backward", "lane.moe", "moe.experts"),
    "jit(f)/pass.backward/transpose(pass.recompute)/jvp(lane.moe)/lane.moe/moe.experts"
    "/while/body/moe.dispatch/jit(floor_divide)/div":
        ("pass.backward", "lane.moe", "moe.dispatch"),
    # the autodiff of what the layer does not write by hand
    "jit(f)/pass.backward/transpose(jvp(lane.moe))/moe.router/dot_general":
        ("pass.backward", "lane.moe", "moe.router"),
    "jit(f)/while/body/pass.forward/lane.moe/lane.moe/moe.combine/reduce_sum":
        ("pass.forward", "lane.moe", "moe.combine"),
    "jit(f)/while/body/pass.forward/lane.moe/moe.shared/dot_general":
        ("pass.forward", "lane.moe", "moe.shared"),
    # vmap over a rung's lanes wraps the phase, never a pass
    "jit(hpb_sweep)/vmap(hpb.train)/while/body/pass.forward/lane.kda/exp":
        ("pass.forward", "lane.kda", None),
    # the update and the sums carry no pass
    "jit(f)/while/body/cond/branch_1_fun/lane.update/sub": (None, "lane.update", None),
    "jit(f)/while/body/cond/branch_1_fun/while/body/lane.accumulate/add":
        (None, "lane.accumulate", None),
    # a pass only ever wrapped is no pass (a pull-back called under none)
    "jit(f)/transpose(pass.recompute)/jvp(lane.gqa)/mul": (None, "lane.gqa", None),
    # two operations the compiler merged: their names side by side
    "jit(f)/pass.backward/transpose(jvp(lane.exit))/mul;jit(f)/pass.backward"
    "/transpose(jvp(lane.exit))/add": ("pass.backward", "lane.exit", None),
    "jit(f)/jit(_normal)/jit(_uniform)/threefry2x32": (None, None, None),
}


def one_instruction(op_name):
    return """HloModule jit_f, is_scheduled=true

ENTRY %%main.2 (x: f32[4]) -> f32[4] {
  %%x = f32[4]{0} parameter(0)
  ROOT %%op.1 = f32[4]{0} negate(%%x), metadata={op_name="%s"}
}
""" % op_name


@pytest.mark.parametrize("op_name", sorted(OP_NAMES))
def test_a_pass_is_read_outside_every_wrapper(op_name):
    which, part, piece = OP_NAMES[op_name]
    program = parse_program_text(one_instruction(op_name))
    for scopes, want in ((PASS_SCOPES, which), (LANE_SCOPES, part), (MOE_SCOPES, piece)):
        assert device_phase_map(program, scopes).get("op.1") == want
    # the parts keep the rule they had
    for scopes in (LANE_SCOPES, DEVICE_SCOPES):
        assert device_phase_map(program, scopes) == lane_names.reference_phase_map(
            one_instruction(op_name), scopes)


def test_a_pass_is_inherited_as_a_part_is():
    text = """HloModule jit_f, is_scheduled=true

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %copy.3 = f32[4]{0} copy(%p)
  ROOT %add.1 = f32[4]{0} add(%copy.3, %p), metadata={op_name="jit(f)/pass.backward/transpose(pass.recompute)/jvp(lane.moe)/lane.moe/while/body/pass.recompute/jvp()/add"}
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %while.2 = f32[4]{0} while(%x), condition=%cond, body=%body, metadata={op_name="jit(f)/pass.backward/transpose(pass.recompute)/jvp(lane.moe)/lane.moe/while"}
}
"""
    program = parse_program_text(text)
    assert isinstance(program, ProgramText) and program.module == "jit_f"
    assert hlo_module_name(text) == "jit_f"
    assert device_phase_map(program, PASS_SCOPES) == {
        "while.2": "pass.backward", "p": "pass.backward", "copy.3": "pass.backward",
        "add.1": "pass.recompute"}
    assert set(device_phase_map(program, LANE_SCOPES).values()) == {"lane.moe"}


class Executable:
    """An executable for what ``sweep_phase_maps`` asks of one."""

    def __init__(self, op_name):
        self.text, self.fetched = one_instruction(op_name), 0

    def as_text(self):
        self.fetched += 1
        return self.text


def test_sweep_phase_maps_fetches_a_text_once_whatever_the_families(monkeypatch):
    from hpbandster_tpu.utils.lru import LRUCache

    named = Executable("jit(f)/hpb.train/pass.forward/lane.moe/moe.router/dot_general")
    older = Executable("jit(f)/hpb.train/lane.moe/dot_general")  # a cache's, from before
    cache = LRUCache(maxsize=4)
    cache["named"], cache["older"] = named, older
    monkeypatch.setattr(fused_bohb, "_SWEEP_EXE_CACHE", cache)
    for _ in range(2):
        assert sweep_phase_maps() == {"jit_f": {"op.1": "hpb.train"}}
        assert sweep_phase_maps(LANE_SCOPES) == {"jit_f": {"op.1": "lane.moe"}}
        # the executable that names none of a family is left out of its map:
        # the two would clash otherwise, and the metric read a wrong number
        assert sweep_phase_maps(PASS_SCOPES) == {"jit_f": {"op.1": "pass.forward"}}
        assert sweep_phase_maps(MOE_SCOPES) == {"jit_f": {"op.1": "moe.router"}}
        # ... and the facts of every instruction are read off the same parse
        # (the older executable names a part too: the two agree on ``x``
        # and differ in ``op.1``'s name, which is left out)
        facts = fused_bohb.sweep_instruction_facts(LANE_SCOPES)
        assert facts == {"jit_f": {"x": {
            "opcode": "parameter", "kind": "compute", "named": False, "adopted": "lane.moe",
            "shape": "f32[4]{0}", "op_name": None}}}
        assert fused_bohb.sweep_instruction_facts(MOE_SCOPES)["jit_f"]["op.1"]["opcode"] == "negate"
    assert (named.fetched, older.fetched) == (1, 1)
    # kept while the executable lives, and no longer
    assert len(fused_bohb._PROGRAM_TEXTS) >= 2
    kept = len(fused_bohb._PROGRAM_TEXTS)
    cache.clear()
    del named, older
    assert len(fused_bohb._PROGRAM_TEXTS) == kept - 2


def small_lane(model):
    load = kimi_small.load
    sys.modules.setdefault("program", load("program.py"))
    if model == "kimi":
        from hpbandster_tpu.workloads import kimi_linear
        return kimi_linear.make_kimi_linear_eval_fn(
            load("configs", "kimi-linear-sgd.py").lane_config(kimi_small.SMALL), data_seed=0)
    if model == "mellum2":
        from hpbandster_tpu.workloads import mellum2
        return mellum2.make_mellum2_eval_fn(
            load("configs", "mellum2-sgd.py").lane_config(mellum2_small.SMALL)._replace(
                attn_query_block=16), data_seed=0)
    from hpbandster_tpu.workloads import ouro
    return ouro.make_ouro_eval_fn(
        load("configs", "ouro-sgd.py").lane_config(ouro_small.SMALL)._replace(
            attn_query_block=16), data_seed=0)


@pytest.fixture
def traces_not_left_behind():
    """What a test traces leaves this worker with it: ``jax.checkpoint``
    keeps a module-level function's trace by its identity and shapes
    (``ouro._exit_cross_entropy``), so a later file that builds the same
    small lane with float32 operands, or reads its scopes, would be handed
    this one's (``test_ouro.py`` and ``test_ouro_sweep.py`` failed after
    this file in one worker)."""
    yield
    jax.clear_caches()


@pytest.mark.parametrize("model", ["kimi", "mellum2", "ouro"])
def test_a_lane_lowers_to_what_no_scope_at_all_lowers_to(
        monkeypatch, traces_not_left_behind, model):
    """The lowered program (its text prints no locations) is byte for byte
    the one without a single ``jax.named_scope``: the passes, the pieces
    and the parts are metadata, and what the compiler and the compile
    cache's key read is the parent's."""
    eval_fn = small_lane(model)
    # a function of a new identity a lowering: nothing traced before is reused
    lowered = lambda: jax.jit(lambda v, b: eval_fn(v, b)).lower(  # noqa: E731
        jnp.full((4,), 0.5), 3.0).as_text()
    with_scopes = lowered()
    entered = set()

    def no_scope(name):
        entered.add(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax, "named_scope", no_scope)
    assert lowered() == with_scopes
    assert set(PASS_SCOPES) <= entered
    assert (set(MOE_SCOPES) - {"moe.shared"} <= entered) == (model != "ouro")
    assert ("moe.shared" in entered) == (model == "kimi")
    assert entered <= set(PASS_SCOPES) | set(MOE_SCOPES) | set(LANE_SCOPES)
    assert "pass." not in with_scopes and "moe." not in with_scopes
