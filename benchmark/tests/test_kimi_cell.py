"""The ``kimi-linear-sgd.bohb-1x9`` cell through the harness on the CPU:
the cell, its traffic and its metrics as the root ``BENCHMARK.json`` has
them, the configuration at the size of the program's own CPU tests
(``tests/kimi_small.py``). What is tested is that every file the cell needs
is found and runs; whether a loss is right is the chip's to say, at the
published widths."""

import json
import os
import sys

import pytest

import run
from test_benchmark import on_cpu, recorded  # noqa: F401

ROOT = run.ROOT
CELL = "kimi-linear-sgd.bohb-1x9"


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A root whose ``BENCHMARK.json`` is the repo's, its configuration's
    file the small one."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    try:
        from kimi_small import SMALL
    finally:
        sys.path.remove(os.path.join(ROOT, "tests"))
    root = tmp_path_factory.mktemp("kimi_root")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "kimi-linear-sgd")
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
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 7, seconds=0.5, trace=trace)
    result = run.measure(args, cell, config, traffic, end_to_end, per_layer,
                         jax.devices()[:1])
    assert result["failed"] == 0 and result["attempted"] >= 1
    json.dumps(result)
    if not trace:
        assert set(result["metrics"]) == {m["name"] for m in end_to_end}
        return
    # the recorded trace is another program's: the lane's parts are read off
    # its reduction by instruction name, and where a name of the small lane
    # meets one of that program the share is a number, else the metric is
    # left out; everything read off the window, the program's counters and
    # the recorded reduction is there
    assert set(result["metrics"]) >= {
        "lane.tokens_per_s", "lane.mfu", "moe.held_choice_share",
        "driver.dispatch_fetch_s", "driver.sweep_wall_p90_s", "cache.new_entries",
        "replay.host_s_per_keval", "program.build_compile_s", "device.idle_share",
        "device.peak_hbm_bytes"}
    assert set(result["metrics"]) <= {m["name"] for m in per_layer}
    assert 10 < result["metrics"]["moe.held_choice_share"]["value"] < 50
    assert result["metrics"]["lane.tokens_per_s"]["value"] > 0


def test_lane_counts_of_the_published_cell():
    import lane_counts
    from reference import halving

    cell, config, traffic, _, _ = run.load_cell(CELL)
    plans = halving.schedule(config, traffic, 1)
    assert lane_counts.schedule_passes(plans) == (27, 13)
    assert lane_counts.layers_of(config) == {
        "kda": 4, "mla": 1, "moe": 4, "dense_ffn": 1, "head": 1, "update": 0}
    flops = lane_counts.sweep_flops(config, plans)
    # 6 x ~0.35 B active parameters x 4,096 tokens a step, 27 steps, and
    # the validation passes and attention on top
    assert 27 * 6 * 0.3e9 * 4096 < flops < 27 * 6 * 0.6e9 * 4096
    _, moved = lane_counts.part_work(config, plans, "update")
    assert moved == pytest.approx(27 * 20 * 602.4e6, rel=1e-3)
