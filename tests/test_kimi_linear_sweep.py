"""A bracket of the small Kimi-Linear lane (``kimi_small.py``) through
``FusedBOHB``, its lanes taken in turn, every reported loss held to the
benchmark's plain reference. In a file of its own: the sweep's compilation
is the suite's cost here, and the workers share out files."""

import collections
import sys

import jax.numpy as jnp
import pytest

from hpbandster_tpu import obs
from hpbandster_tpu.obs.timeline import (
    DEVICE_SCOPES, LANE_SCOPES, MOE_SCOPES, PASS_SCOPES)
from hpbandster_tpu.ops import fused
from hpbandster_tpu.optimizers import FusedBOHB, sweep_phase_maps
from hpbandster_tpu.optimizers.fused_bohb import _SWEEP_EXE_CACHE
from hpbandster_tpu.workloads import kimi_linear as K

import lane_names
from kimi_small import SMALL, check_the_moe_backward_rule_is_named, load


@pytest.fixture(scope="module")
def swept():
    """One bracket of 9, 3, 1 lanes at 1, 3, 9 steps, float32 operands so
    that the reference can hold every loss tightly, one lane at a time."""
    sys.modules.setdefault("program", load("program.py"))
    cfg = load("configs", "kimi-linear-sgd.py").lane_config(SMALL)._replace(
        kda_chunk=16, kda_block=4, mla_heads_at_once=2)
    patch = pytest.MonkeyPatch()
    patch.setattr(K.lane, "_OPERAND", jnp.float32)
    eval_fn = K.make_kimi_linear_eval_fn(cfg, data_seed=SMALL["data_seed"])
    patch.setattr(fused, "_device_memory_bytes", lambda: eval_fn.lane_facts.bytes + 1)
    # the phase maps below are over every sweep executable the process
    # holds: this worker's earlier files have left theirs
    _SWEEP_EXE_CACHE.clear()
    try:
        opt = FusedBOHB(configspace=K.kimi_linear_space(seed=11), eval_fn=eval_fn,
                        run_id="kimi", min_budget=1, max_budget=9, eta=3, seed=11)
        with lane_names.compiled_here():
            result = opt.run(n_iterations=1)
        yield opt, result
    finally:
        patch.undo()


def test_every_reported_loss_is_the_references(swept):
    _, result = swept
    reference = load("reference", "kimi-linear-sgd.py")
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


def test_the_row_counts_the_lanes(swept):
    opt, _ = swept
    row = opt.run_stats[-1]
    assert row["evaluations"] == 13 and row["lane_steps"] == 27
    assert row["lane_tokens"] == 27 * 64 and row["lanes_at_once"] == 1
    # 4 of 16 experts held, top 4: a quarter of the choices if routing is even
    assert 0.1 < row["moe_held_choice_share"] < 0.5
    assert 1.0 <= row["moe_load_max_over_mean"] < 4.0
    # how the expert layer moves its rows: by gathers, in both passes
    assert row["moe_combine_by_gather"] == 1
    # and its products: off the chip the plain form's, whose reached tiles
    # pay for all their rows (a tile is four times the even load)
    assert row["moe_products_in_vmem"] == 0
    assert 2.0 < row["moe_rows_computed_over_held"] < 8.0
    gauges = obs.get_metrics().snapshot()["gauges"]
    assert gauges["sweep.lane.moe_combine_by_gather"] == 1.0
    assert gauges["sweep.lane.moe_products_in_vmem"] == 0.0
    assert gauges["sweep.lane.lane_steps"] == 27


def test_the_lane_names_its_parts_inside_the_trainer(swept):
    (phases,) = sweep_phase_maps().values()
    (parts,) = sweep_phase_maps(LANE_SCOPES).values()
    # every part of the list but the mixers of the other lanes and what only
    # a looped lane has (exits, a shared leaf's sum)
    assert set(parts.values()) == set(LANE_SCOPES) - {
        "lane.gdn", "lane.swa", "lane.gqa", "lane.bda", "lane.conv", "lane.exit",
        "lane.accumulate"}
    assert {"hpb.train", "hpb.promote"} <= set(phases.values()) <= set(DEVICE_SCOPES)
    # a lane's part lies inside the evaluation: no instruction has a part
    # and a phase other than the trainer's two
    inside = {phases.get(name) for name in parts}
    assert inside <= {"hpb.train", "hpb.validate"}
    check_the_moe_backward_rule_is_named(swept[0].last_executable.as_text(), parts)


def test_the_trainer_names_its_passes(swept):
    """Forward, recomputed and backward (``obs.timeline.PASS_SCOPES``), in
    every part of the lane but the update."""
    (parts,) = sweep_phase_maps(LANE_SCOPES).values()
    (passes,) = sweep_phase_maps(PASS_SCOPES).values()
    text = swept[0].last_executable.as_text()
    assert passes == lane_names.check_the_trainer_names_its_passes(text, parts)


def test_the_older_readers_read_what_they_read(swept):
    lane_names.check_the_older_readers_read_what_they_read(
        swept[0].last_executable.as_text())


def test_the_expert_layer_names_its_pieces(swept):
    (parts,) = sweep_phase_maps(LANE_SCOPES).values()
    (pieces,) = sweep_phase_maps(MOE_SCOPES).values()
    assert pieces == lane_names.check_the_expert_layer_names_its_pieces(
        swept[0].last_executable.as_text(), parts, shared=True)
