"""A bracket of the small Ouro lane (``ouro_small.py``: 2 layers run 3 times
over, an exit after every pass) through ``FusedBOHB``, its lanes taken in
turn, every reported loss held to the benchmark's plain reference. In a file
of its own: the sweep's compilation is the suite's cost here, and the
workers share out files."""

import collections
import re
import sys

import jax.numpy as jnp
import pytest

from hpbandster_tpu import obs
from hpbandster_tpu.obs.timeline import (
    DEVICE_SCOPES, LANE_SCOPES, MOE_SCOPES, PASS_SCOPES)
from hpbandster_tpu.ops import fused
from hpbandster_tpu.optimizers import FusedBOHB, sweep_phase_maps
from hpbandster_tpu.optimizers.fused_bohb import _SWEEP_EXE_CACHE
from hpbandster_tpu.workloads import lane
from hpbandster_tpu.workloads import ouro as O

import lane_names
from ouro_small import SMALL, load


@pytest.fixture(scope="module")
def swept():
    """One bracket of 9, 3, 1 lanes at 1, 3, 9 steps, float32 operands so
    that the reference can hold every loss tightly, one lane at a time."""
    sys.modules.setdefault("program", load("program.py"))
    cfg = load("configs", "ouro-sgd.py").lane_config(SMALL)._replace(attn_query_block=16)
    patch = pytest.MonkeyPatch()
    patch.setattr(lane, "_OPERAND", jnp.float32)
    eval_fn = O.make_ouro_eval_fn(cfg, data_seed=SMALL["data_seed"])
    patch.setattr(fused, "_device_memory_bytes", lambda: eval_fn.lane_facts.bytes + 1)
    # the phase maps below are over every sweep executable the process
    # holds: this worker's earlier files have left theirs
    _SWEEP_EXE_CACHE.clear()
    try:
        opt = FusedBOHB(configspace=O.ouro_space(seed=11), eval_fn=eval_fn,
                        run_id="ouro", min_budget=1, max_budget=9, eta=3, seed=11)
        with lane_names.compiled_here():
            result = opt.run(n_iterations=1)
        yield opt, result
    finally:
        patch.undo()


def test_every_reported_loss_is_the_references(swept):
    _, result = swept
    reference = load("reference", "ouro-sgd.py")
    by_lane = collections.defaultdict(dict)
    for run in result.get_all_runs():
        by_lane[run.config_id][int(run.budget)] = run.loss
    id2config = result.get_id2config_mapping()
    assert sorted(len(v) for v in by_lane.values()) == [1] * 6 + [2, 2, 3]
    for config_id, reported in by_lane.items():
        hp = id2config[config_id]["config"]
        marks = sorted(reported)
        want = reference.reference_losses(
            SMALL, [hp[n] for n in reference.HPARAMS], marks)
        for mark, w in zip(marks, want):
            # float32 both sides, sums in another order; a lane whose
            # learning rate is near 1 amplifies that over nine steps
            assert reference.gap(reported[mark], w) < 2e-3, (hp, mark, reported[mark], w)


def test_the_row_counts_the_lanes_and_the_loop(swept):
    opt, _ = swept
    row = opt.run_stats[-1]
    assert row["evaluations"] == 13 and row["lane_steps"] == 27
    assert row["lane_tokens"] == 27 * 32 and row["lanes_at_once"] == 1
    # static facts of the loop: 2 layers run 3 times, an exit a pass
    assert (row["loop_passes"], row["layer_visits_per_pass"], row["exits_trained"]) == (3, 6, 3)
    # off the chip every layer's scores go through the plain form
    assert row["attn_scores_in_vmem"] == 0
    # from the device, over the held-out passes: the exit distribution's
    # last term and its entropy over ln 3
    assert 0.0 < row["exit_last_mass"] < 1.0
    assert 0.0 < row["exit_entropy_share"] <= 1.0
    # the counters are the model's: a lane with no experts counts none
    assert not [name for name in row if name.startswith("moe_")]
    assert not [name for name in opt.eval_fn.lane_facts.counters if name.startswith("moe_")]
    gauges = obs.get_metrics().snapshot()["gauges"]
    assert gauges["sweep.lane.loop_passes"] == 3.0
    assert gauges["sweep.lane.attn_scores_in_vmem"] == 0.0
    assert gauges["sweep.lane.exit_last_mass"] == pytest.approx(row["exit_last_mass"])
    assert gauges["sweep.lane.lane_steps"] == 27


def test_the_lane_names_its_parts_inside_the_trainer(swept):
    (phases,) = sweep_phase_maps().values()
    (parts,) = sweep_phase_maps(LANE_SCOPES).values()
    assert set(parts.values()) == {
        "lane.gqa", "lane.dense_ffn", "lane.head", "lane.update",
        "lane.exit", "lane.accumulate"}
    assert {"hpb.train", "hpb.promote"} <= set(phases.values()) <= set(DEVICE_SCOPES)
    # a lane's part lies inside the evaluation: no instruction has a part
    # and a phase other than the trainer's two
    inside = {phases.get(name) for name in parts}
    assert inside <= {"hpb.train", "hpb.validate"}
    # the backward pass is charged where the forward pass is: what the
    # differentiation makes of a part (a visit's pull-back inside the
    # backward loop, the exits' gradient) carries the part's name
    backward = collections.defaultdict(list)
    for line in swept[0].last_executable.as_text().splitlines():
        name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        made_of = re.search(r"transpose\(jvp\((lane\.\w+)\)\)", line)
        if name and made_of:
            backward[made_of.group(1)].append(name.group(1))
    assert set(backward) >= {"lane.gqa", "lane.dense_ffn", "lane.head", "lane.exit"}
    for part, names in backward.items():
        assert {parts.get(name, part) for name in names} == {part}, part


def test_the_trainer_names_its_passes(swept):
    """Forward, recomputed and backward (``obs.timeline.PASS_SCOPES``), in
    every part of the lane but the update and the sums."""
    (parts,) = sweep_phase_maps(LANE_SCOPES).values()
    (passes,) = sweep_phase_maps(PASS_SCOPES).values()
    text = swept[0].last_executable.as_text()
    assert passes == lane_names.check_the_trainer_names_its_passes(text, parts)


def test_the_older_readers_read_what_they_read(swept):
    lane_names.check_the_older_readers_read_what_they_read(
        swept[0].last_executable.as_text())


def test_a_lane_without_experts_names_no_piece(swept):
    # left out of the family's map, so its metrics read nothing
    assert sweep_phase_maps(MOE_SCOPES) == {}
