"""The ``mellum2-sgd.bohb-1x9`` cell through the harness on the CPU: the
cell, its traffic and its metrics as the root ``BENCHMARK.json`` has them,
the configuration at the size of the program's own CPU tests
(``tests/mellum2_small.py``). What is tested is that every file the cell
needs is found and runs; whether a loss is right is the chip's to say, at
the published widths. And the counts of the published cell, as constants."""

import json
import os
import sys

import pytest

import run
from test_benchmark import on_cpu, recorded  # noqa: F401

ROOT = run.ROOT
CELL = "mellum2-sgd.bohb-1x9"
NEW_METRICS = {
    "mellum2.mfu", "lane.swa_device_share", "lane.gqa_device_share",
    "lane.swa_roofline_share", "lane.gqa_roofline_share", "mellum2.moe_roofline_share"}


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A root whose ``BENCHMARK.json`` is the repo's, its configuration's
    file the small one."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    try:
        from mellum2_small import SMALL
    finally:
        sys.path.remove(os.path.join(ROOT, "tests"))
    root = tmp_path_factory.mktemp("mellum2_root")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "mellum2-sgd")
    for path, content in (
            ("BENCHMARK.json", bench), (entry["file"], SMALL),
            ("benchmark/traffic/bohb-1x9.json",
             json.load(open(os.path.join(run.HERE, "traffic", "bohb-1x9.json"))))):
        os.makedirs(os.path.dirname(root / path), exist_ok=True)
        (root / path).write_text(json.dumps(content))
    return str(root)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_through_the_harness(on_cpu, small_root, trace, monkeypatch):  # noqa: F811
    import argparse

    import jax

    # the lane's roofline shares read the memory's peak too
    monkeypatch.setattr(run, "device_peaks", lambda kind: {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    cell, config, traffic, end_to_end, per_layer = run.load_cell(CELL, root=small_root)
    assert NEW_METRICS <= {m["name"] for m in per_layer}
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 11, seconds=0.5, trace=trace)
    result = run.measure(args, cell, config, traffic, end_to_end, per_layer,
                         jax.devices()[:1])
    assert result["failed"] == 0 and result["attempted"] >= 1
    json.dumps(result)
    if not trace:
        assert set(result["metrics"]) == {m["name"] for m in end_to_end}
        return
    # the recorded trace is another program's: a part's share is a number
    # only where a name of the small lane meets one of that program, else
    # the metric is left out; everything read off the window, the program's
    # counters and the recorded reduction is there
    assert set(result["metrics"]) >= {
        "lane.tokens_per_s", "mellum2.mfu", "moe.held_choice_share",
        "driver.dispatch_fetch_s", "driver.sweep_wall_p90_s", "cache.new_entries",
        "replay.host_s_per_keval", "program.build_compile_s", "device.idle_share",
        "device.peak_hbm_bytes"}
    assert set(result["metrics"]) <= {m["name"] for m in per_layer}
    assert 10 < result["metrics"]["moe.held_choice_share"]["value"] < 50
    assert result["metrics"]["lane.tokens_per_s"]["value"] > 0
    assert 0 < result["metrics"]["mellum2.mfu"]["value"] < 100


def test_lane_counts_of_the_published_cell():
    import lane_counts_mellum2 as counts
    from reference import halving

    cell, config, traffic, _, _ = run.load_cell(CELL)
    plans = halving.schedule(config, traffic, 1)
    assert counts.schedule_passes(plans) == (27, 13)
    assert counts.layers_of(config) == {"swa": 3, "gqa": 1, "moe": 4, "head": 1, "update": 0}
    assert counts.lane_params(config) == 595_153_152
    # a window layer's band against a full layer's half-square, a head
    assert counts.attended_pairs(config, "swa") == 1024 * 8192 - 1024 * 1023 // 2
    assert counts.attended_pairs(config, "gqa") == 8192 * 8193 // 2
    forward = counts.part_forward_flops(config)
    per_token = 3 * forward["swa"] + forward["gqa"] + 4 * forward["moe"] + forward["head"]
    assert per_token == pytest.approx(497.4e6, rel=1e-3)
    # 27 steps of three forward passes and 13 held-out passes of 8,192 tokens
    assert counts.sweep_flops(config, plans) == pytest.approx(383.3e12, rel=1e-3)
    assert counts.sweep_flops(config, plans) == pytest.approx(
        per_token * 8192 * (3 * 27 + 13) + counts.part_work(config, plans, "update")[0])
    _, moved = counts.part_work(config, plans, "update")
    assert moved == pytest.approx(27 * 20 * 595.1e6, rel=1e-3)
