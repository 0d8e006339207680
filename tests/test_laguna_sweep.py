"""A bracket of the small Laguna-XS.2 lane (``laguna_small.py``: full layers
of 6 heads over half-rotated heads, window layers of 8, a gate a head, a dense
layer first and then sigmoid experts beside a shared one) through
``FusedBOHB``, its lanes taken in turn, every reported loss held to the
benchmark's plain reference and every promotion to
``benchmark/reference/halving.py``. In a file of its own: the sweep's
compilation is the suite's cost here, and the workers share out files."""

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
from hpbandster_tpu.workloads import laguna as L
from hpbandster_tpu.workloads import lane

import lane_names
from laguna_small import SMALL, check_the_moe_backward_rule_is_named, load


@pytest.fixture(scope="module")
def swept():
    """One bracket of 9, 3, 1 lanes at 1, 3, 9 steps, float32 operands so
    that the reference can hold every loss tightly, one lane at a time."""
    sys.modules.setdefault("program", load("program.py"))
    cfg = load("configs", "laguna-xs2-sgd.py").lane_config(SMALL)._replace(
        attn_query_block=16)
    patch = pytest.MonkeyPatch()
    patch.setattr(lane, "_OPERAND", jnp.float32)
    eval_fn = L.make_laguna_eval_fn(cfg, data_seed=SMALL["data_seed"])
    patch.setattr(fused, "_device_memory_bytes", lambda: eval_fn.lane_facts.bytes + 1)
    # the phase maps below are over every sweep executable the process
    # holds: this worker's earlier files have left theirs
    _SWEEP_EXE_CACHE.clear()
    try:
        opt = FusedBOHB(configspace=L.laguna_space(seed=11), eval_fn=eval_fn,
                        run_id="laguna", min_budget=1, max_budget=9, eta=3, seed=11)
        with lane_names.compiled_here():
            result = opt.run(n_iterations=1)
        yield opt, result
    finally:
        patch.undo()


def test_every_reported_loss_is_the_references(swept):
    _, result = swept
    reference = load("reference", "laguna-xs2-sgd.py")
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
        # float32 both sides, sums in another order; a lane whose learning
        # rate is near 1 amplifies that over nine steps. The lane of init
        # scale 7.0 is chaos from its first step (a loss of 17): its step
        # agrees with the reference's to a thousandth of its norm, and
        # between the two sets of weights a router's choice of its top 4
        # flips and the held-out loss jumps from 17.11 to 17.44 (read along
        # the line between them: 17.14 half way, 17.43 at three quarters)
        limit = 2e-3 if hp["init_scale"] < 5.0 else 5e-2
        for mark, w in zip(marks, want):
            assert reference.gap(reported[mark], w) < limit, (hp, mark, reported[mark], w)


def test_the_promotions_are_the_halving_references(swept):
    opt, result = swept
    program = sys.modules["program"]
    halving = load("reference", "halving.py")
    traffic = {"entry": "fused_bohb", "run": {"n_iterations": 1}}
    plans = halving.schedule(SMALL, traffic, 1)
    assert plans == [([9, 3, 1], [1.0, 3.0, 9.0])]
    record = program._runs_record(result, opt.total_evaluated)
    assert all(value <= limit for _, value, limit in halving.bookkeeping([record], plans))
    assert halving.promotion_violations(record, plans) == 0


def test_the_row_counts_the_lanes_the_blocks_and_the_layers_in_vmem(swept):
    opt, _ = swept
    row = opt.run_stats[-1]
    assert row["evaluations"] == 13 and row["lane_steps"] == 27
    assert row["lane_tokens"] == 27 * 64 and row["lanes_at_once"] == 1
    # 4 of 16 experts held, top 4: a quarter of the choices if routing is
    # even, over the four layers that have experts (the dense one counts none)
    assert 0.1 < row["moe_held_choice_share"] < 0.5
    assert 1.0 <= row["moe_load_max_over_mean"] < 4.0
    # static facts of the blocking, once a query head: 64 tokens in blocks of
    # 16; a full layer 1 + 2 + 3 + 4 blocks of 16 (6 heads), a window of 8
    # one block back, 1 + 3 x 2 of 16 (8 heads)
    assert row["attn_key_blocks_computed"] == 2 * 6 * 10 + 3 * 8 * 7
    assert row["attn_key_blocks_square"] == (2 * 6 + 3 * 8) * 16
    # a share of the five layers: off the chip none goes through the kernels
    assert row["attn_scores_in_vmem"] == 0
    assert row["moe_combine_by_gather"] == 1 and row["moe_products_in_vmem"] == 0
    assert opt.eval_fn.lane_facts.counters == (
        lane.LANE_COUNTERS + L.ATTENTION_COUNTERS
        + ("attn_scores_in_vmem", "attn_rotation_in_vmem")
        + tuple(name for name, _ in lane.MOE_COUNTERS) + ("moe_products_in_vmem",))
    gauges = obs.get_metrics().snapshot()["gauges"]
    assert gauges["sweep.lane.attn_scores_in_vmem"] == 0.0
    assert gauges["sweep.lane.attn_rotation_in_vmem"] == row["attn_rotation_in_vmem"] == 0.0
    assert gauges["sweep.lane.moe_held_choice_share"] == row["moe_held_choice_share"]
    assert gauges["sweep.lane.lane_steps"] == 27


def test_the_lane_names_its_parts_inside_the_trainer(swept):
    (phases,) = sweep_phase_maps().values()
    (parts,) = sweep_phase_maps(LANE_SCOPES).values()
    # both kinds of attention, the experts, the dense layer: no new scope
    assert set(parts.values()) == {
        "lane.swa", "lane.gqa", "lane.moe", "lane.dense_ffn", "lane.head", "lane.update"}
    assert {"hpb.train", "hpb.promote"} <= set(phases.values()) <= set(DEVICE_SCOPES)
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
