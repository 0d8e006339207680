"""The harness end to end on the CPU at a tiny size, the references
against the program, the controls, and a broken timed path.

    python -m pytest benchmark/tests

Times and rates printed here are a CPU's and mean nothing; what is tested is
control flow, counts, keys and that ``correct`` says false when it should.
"""

import argparse
import gzip
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import run
import trace_reduce
from reference import halving

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
RECORDED_GZ = os.path.join(TINY, "recorded.xplane.pb.gz")
CELLS = ["branin-bohb.tiny-resident-8x27", "mlp-sgd.tiny-3x9", "mlp-sgd.tiny-mesh-16x9",
         "mlp-sgd.tiny-resident-6x9"]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="session")
def recorded(tmp_path_factory):
    """Two sweeps of the tiny resident Branin cell, traced on a v5e chip by
    ``run.traced_sweeps`` (PR 24); kept gzipped."""
    path = str(tmp_path_factory.mktemp("trace") / "recorded.xplane.pb")
    with gzip.open(RECORDED_GZ) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture
def on_cpu(monkeypatch, recorded):
    """Step round what only a TPU has: its row of peaks, its memory
    counters and its trace (the recorded one stands in)."""
    monkeypatch.setattr(run, "device_peaks", lambda kind: {"flops_per_s": 197e12})
    monkeypatch.setattr(run, "memory_peak_bytes", lambda devices: 1)
    monkeypatch.setattr(
        run, "traced_sweeps",
        lambda sweep, seed, first, devices: trace_reduce.reduce_file(recorded, 1))


def measure(workload, seed=7, trace=0, seconds=0.5):
    import jax

    cell, config, traffic, end_to_end, per_layer = run.load_cell(workload, root=TINY)
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds, trace=trace)
    result = run.measure(args, cell, config, traffic, end_to_end, per_layer,
                         jax.devices()[:cell["chips"]])
    return result, end_to_end, per_layer


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_end_to_end(on_cpu, workload, trace):
    result, end_to_end, per_layer = measure(workload, trace=trace)
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    wanted = per_layer if trace else end_to_end
    assert set(result["metrics"]) == {m["name"] for m in wanted}
    for m in wanted:
        assert result["metrics"][m["name"]]["unit"] == m["unit"]
    if trace:
        assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    json.dumps(result)


def test_large_seed_is_folded(on_cpu):
    result, _, _ = measure(CELLS[0], seed=2 ** 31 + 12345)
    assert result["correct"] is True


def test_run_exits_nonzero_without_a_tpu():
    done = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "mlp-sgd.bohb-8x2187", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
    assert done.returncode != 0
    assert "{" not in done.stdout


def test_unknown_device_has_no_peaks():
    assert run.device_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        run.device_peaks("cpu")


# ---------------------------------------------------------------- references
def test_schedules_hold_the_counts_the_cells_state():
    eight = [halving.hyperband_plan(i, 1, 2187, 3) for i in range(8)]
    assert [counts[0] for counts, _ in eight] == [2187, 834, 324, 130, 54, 24, 12, 8]
    assert halving.schedule_evaluations(eight) == 5343
    assert halving.schedule_lane_steps(eight) == 105861
    assert halving.schedule_evaluations(eight * 2) == 10686
    mesh = halving.mesh_aligned_plan(2048, 1, 729, 3, 4)
    assert mesh[0] == [2048, 684, 228, 76, 28, 8, 4]
    assert halving.schedule_evaluations([mesh] * 4) == 12304


def test_schedule_matches_the_programs():
    from hpbandster_tpu.ops.bracket import hyperband_bracket, mesh_aligned_plan

    for i in range(9):
        counts, budgets = halving.hyperband_plan(i, 1, 2187, 3)
        plan = hyperband_bracket(i, 1, 2187, 3)
        assert tuple(counts) == plan.num_configs
        assert np.allclose(budgets, plan.budgets)
    assert tuple(halving.mesh_aligned_plan(100, 1, 81, 3, 4)[0]) == (
        mesh_aligned_plan(100, 1, 81, 3, 4).num_configs)


def bracket_record(promoted):
    """One bracket 3 -> 1 at budgets 1, 3 with lane ``promoted`` kept."""
    return {
        "kind": "runs", "evaluations": 4, "trajectory": [0.5],
        "bracket": np.zeros(4, int), "lane": np.array([0, 1, 2, promoted]),
        "budget": np.array([1.0, 1.0, 1.0, 3.0]),
        "loss": np.array([0.3, 0.2, np.nan, 0.1]),
    }


@pytest.mark.parametrize("promoted,violations", [(1, 0), (0, 1), (2, 1)])
def test_promotion_is_rederived(promoted, violations):
    plans = [([3, 1], [1.0, 3.0])]
    assert halving.promotion_violations(bracket_record(promoted), plans) == violations
    numbers = dict((n, v) for n, v, _ in halving.bookkeeping([bracket_record(promoted)], plans))
    assert numbers["rungs_not_top_k"] == violations


# ------------------------------------------------------ controls, broken paths
def window_records(workload, seed=5):
    import jax

    import program

    cell, config, traffic, _, _ = run.load_cell(workload, root=TINY)
    sweep = run.load_module("configs", cell["config"] + ".py").build(
        config, traffic, seed, jax.devices()[:cell["chips"]])
    raws, _, _ = run.run_sweeps(sweep, seed, indices=range(3))
    reference = run.load_module("reference", cell["config"] + ".py")
    return reference, config, traffic, [r["extract"]() for r in raws]


@pytest.mark.parametrize("workload", CELLS)
def test_lower_precision_control_is_not_correct(workload):
    reference, config, traffic, records = window_records(workload)
    sound = reference.compare(config, traffic, records, 5)
    assert all(value <= limit for _, value, limit in sound), sound
    control = reference.compare(config, traffic, records, 5, control=True)
    assert any(value > limit for _, value, limit in control), control


def test_altered_objective_is_not_correct(on_cpu, monkeypatch):
    from hpbandster_tpu.workloads import toys

    sound = toys.branin_from_vector
    monkeypatch.setattr(toys, "branin_from_vector",
                        lambda vec, budget: sound(vec, budget) * 1.01)
    result, _, _ = measure(CELLS[0])
    assert result["correct"] is False


def break_ensemble(monkeypatch, change):
    """``make_mlp_ensemble`` whose ``step_fn`` result goes through
    ``change(state before, state after, losses)``."""
    from hpbandster_tpu.workloads import ensemble

    sound = ensemble.make_mlp_ensemble

    def broken(cfg, data_seed=0):
        se = sound(cfg, data_seed)

        def step_fn(state, vectors, budget, prev_budget):
            return change(state, *se.step_fn(state, vectors, budget, prev_budget))

        return se._replace(step_fn=step_fn)

    monkeypatch.setattr(ensemble, "make_mlp_ensemble", broken)


def test_step_that_returns_its_state_unchanged_is_not_correct(on_cpu, monkeypatch):
    # at this size a low learning rate moves a loss by less than the limits
    # see, so this fault is driven where one lane is followed to its last
    # step; on the chip, at the cell's size, see PERF.md section 2
    break_ensemble(monkeypatch, lambda before, after, losses: (before, losses))
    result, _, _ = measure(CELLS[2])
    assert result["correct"] is False


@pytest.mark.parametrize("workload", CELLS[1:])
def test_loss_altered_where_it_is_produced_is_not_correct(on_cpu, monkeypatch, workload):
    break_ensemble(monkeypatch, lambda before, after, losses: (after, losses * 1.01))
    result, _, _ = measure(workload)
    assert result["correct"] is False


# ------------------------------------------------------------ trace reduction
def test_reduction_of_the_recorded_trace(recorded):
    out = trace_reduce.reduce_file(recorded, 1)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["spans"] >= 2
    gaps = sum(out["gap_s"].values())
    assert gaps == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
    assert sum(out["op_s"].values()) == pytest.approx(out["busy_s"], rel=0.02)
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_gaps_are_named_by_the_span_that_covers_them():
    spans = [(0, 100, "bench:construct"), (100, 400, "bench:run"), (450, 500, "bench:construct")]
    ops = {"/device:TPU:0": [(150, 200, "loop"), (160, 180, "body"), (250, 300, "fusion")]}
    out = trace_reduce.reduce_events(spans, ops, 1)
    assert out["busy_s"] == pytest.approx(100e-9)
    assert out["window_s"] == pytest.approx(500e-9)
    assert out["op_s"] == pytest.approx({"loop": 30e-9, "body": 20e-9, "fusion": 50e-9})
    assert out["gap_s"] == pytest.approx({
        "bench:construct": 150e-9, "bench:run:before-first-op": 50e-9,
        "bench:run:between-ops": 50e-9, "bench:run:after-last-op": 100e-9,
        "between-spans": 50e-9})
    with pytest.raises(ValueError):
        trace_reduce.reduce_events(spans, ops, 4)
