"""A bracket of the small LFM2 lane (``lfm2_small.py``: a convolution layer
with the dense SwiGLU, an attention layer and a convolution layer with
experts, the head tied to the embedding) through ``FusedBOHB``, its lanes
taken in turn, every reported loss held to the benchmark's plain reference.
In a file of its own: the sweep's compilation is the suite's cost here, and
the workers share out files."""

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
from hpbandster_tpu.workloads import lfm2 as L

import lane_names
from lfm2_small import SMALL, check_the_moe_backward_rule_is_named, load


@pytest.fixture(scope="module")
def swept():
    """One bracket of 9, 3, 1 lanes at 1, 3, 9 steps, float32 operands so
    that the reference can hold every loss tightly, one lane at a time."""
    sys.modules.setdefault("program", load("program.py"))
    cfg = load("configs", "lfm2-sgd.py").lane_config(SMALL)._replace(attn_query_block=16)
    patch = pytest.MonkeyPatch()
    patch.setattr(lane, "_OPERAND", jnp.float32)
    eval_fn = L.make_lfm2_eval_fn(cfg, data_seed=SMALL["data_seed"])
    patch.setattr(fused, "_device_memory_bytes", lambda: eval_fn.lane_facts.bytes + 1)
    # the phase maps below are over every sweep executable the process
    # holds: this worker's earlier files have left theirs
    _SWEEP_EXE_CACHE.clear()
    try:
        opt = FusedBOHB(configspace=L.lfm2_space(seed=11), eval_fn=eval_fn,
                        run_id="lfm2", min_budget=1, max_budget=9, eta=3, seed=11)
        with lane_names.compiled_here():
            result = opt.run(n_iterations=1)
        yield opt, result
    finally:
        patch.undo()


def test_every_reported_loss_is_the_references(swept):
    _, result = swept
    reference = load("reference", "lfm2-sgd.py")
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


def test_the_row_counts_the_lanes_and_the_layers(swept):
    opt, _ = swept
    row = opt.run_stats[-1]
    assert row["evaluations"] == 13 and row["lane_steps"] == 27
    assert row["lane_tokens"] == 27 * 32 and row["lanes_at_once"] == 1
    # static facts of the lane's make: two convolution layers, a tied head
    assert (row["conv_layers"], row["head_tied"]) == (2, 1)
    # 4 of 8 experts held, top 2: half of the choices if routing is even,
    # over the two layers that have experts (the dense one counts nothing)
    assert 0.2 < row["moe_held_choice_share"] < 0.8
    assert 1.0 <= row["moe_load_max_over_mean"] < 4.0
    # one attention layer, 32 tokens in blocks of 16: 3 of 4 blocks
    assert (row["attn_key_blocks_computed"], row["attn_key_blocks_square"]) == (3, 4)
    # off the chip both take their plain forms
    assert row["attn_scores_in_vmem"] == 0 and row["moe_products_in_vmem"] == 0
    assert row["moe_combine_by_gather"] == 1
    assert 1.0 <= row["moe_rows_computed_over_held"] < 8.0
    gauges = obs.get_metrics().snapshot()["gauges"]
    assert gauges["sweep.lane.conv_layers"] == 2.0 and gauges["sweep.lane.head_tied"] == 1.0
    assert gauges["sweep.lane.moe_held_choice_share"] == pytest.approx(
        row["moe_held_choice_share"])
    assert gauges["sweep.lane.attn_scores_in_vmem"] == 0.0
    assert gauges["sweep.lane.lane_steps"] == 27


def test_the_lane_names_its_parts_inside_the_trainer(swept):
    (phases,) = sweep_phase_maps().values()
    (parts,) = sweep_phase_maps(LANE_SCOPES).values()
    assert set(parts.values()) == {
        "lane.conv", "lane.gqa", "lane.moe", "lane.dense_ffn", "lane.head", "lane.update"}
    assert {"hpb.train", "hpb.promote"} <= set(phases.values()) <= set(DEVICE_SCOPES)
    inside = {phases.get(name) for name in parts}
    assert inside <= {"hpb.train", "hpb.validate"}
    text = swept[0].last_executable.as_text()
    check_the_moe_backward_rule_is_named(text, parts)
    # the backward pass is charged where the forward pass is: what the
    # differentiation makes of the convolution mixer (and of the tied head:
    # its product against the logits' gradient) carries the part's name
    backward = collections.defaultdict(list)
    for line in text.splitlines():
        name = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        made_of = re.search(r"transpose\(jvp\((lane\.\w+)\)\)", line)
        if name and made_of:
            backward[made_of.group(1)].append(name.group(1))
    assert set(backward) >= {"lane.conv", "lane.gqa", "lane.dense_ffn", "lane.head"}
    for part, names in backward.items():
        assert {parts.get(name, part) for name in names} == {part}, part
    forward = [n for n, part in parts.items() if part == "lane.conv"
               and n not in set(backward["lane.conv"])]
    assert len(forward) > 5 and len(backward["lane.conv"]) > 5


def test_the_trainer_names_its_passes(swept):
    """Forward, recomputed and backward (``obs.timeline.PASS_SCOPES``), in
    every part of the lane but the update."""
    (parts,) = sweep_phase_maps(LANE_SCOPES).values()
    (passes,) = sweep_phase_maps(PASS_SCOPES).values()
    text = swept[0].last_executable.as_text()
    assert passes == lane_names.check_the_trainer_names_its_passes(text, parts)
    # the tied head's gradient is added to in the embedding's step: the
    # backward pass's, under the head's name
    adds = [n for n, part in parts.items() if part == "lane.head"
            and passes.get(n) == "pass.backward"]
    assert adds


def test_the_older_readers_read_what_they_read(swept):
    lane_names.check_the_older_readers_read_what_they_read(
        swept[0].last_executable.as_text())


def test_the_expert_layer_names_its_pieces(swept):
    (parts,) = sweep_phase_maps(LANE_SCOPES).values()
    (pieces,) = sweep_phase_maps(MOE_SCOPES).values()
    assert pieces == lane_names.check_the_expert_layer_names_its_pieces(
        swept[0].last_executable.as_text(), parts, shared=False)
