"""The ``ouro-sgd.bohb-1x9`` cell through the harness on the CPU: the cell,
its traffic and its metrics as the root ``BENCHMARK.json`` has them, the
configuration at the size of the program's own CPU tests
(``tests/ouro_small.py``). What is tested is that every file the cell needs
is found and runs; whether a loss is right is the chip's to say, at the
published widths. And the file's arithmetic and the counts of the published
cell, against a count by hand."""

import json
import os
import sys

import pytest

import run
from test_benchmark import on_cpu, recorded  # noqa: F401

ROOT = run.ROOT
CELL = "ouro-sgd.bohb-1x9"
NEW_METRICS = {
    "ouro.mfu", "ouro.ffn_roofline_share", "ouro.attn_roofline_share",
    "lane.dense_ffn_device_share", "lane.head_device_share",
    "lane.accumulate_device_share", "lane.exit_device_share", "lane.no_part_device_share"}


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A root whose ``BENCHMARK.json`` is the repo's, its configuration's
    file the small one."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    try:
        from ouro_small import SMALL
    finally:
        sys.path.remove(os.path.join(ROOT, "tests"))
    root = tmp_path_factory.mktemp("ouro_root")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-sgd")
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
    # other lanes' counts stay off this cell
    assert not {"lane.mfu", "mellum2.mfu", "moe.held_choice_share", "lane.moe_device_share",
                "lane.gqa_roofline_share"} & {m["name"] for m in per_layer}
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
        "lane.tokens_per_s", "ouro.mfu", "driver.dispatch_fetch_s",
        "driver.sweep_wall_p90_s", "cache.new_entries", "replay.host_s_per_keval",
        "program.build_compile_s", "device.idle_share", "device.peak_hbm_bytes"}
    assert set(result["metrics"]) <= {m["name"] for m in per_layer}
    assert result["metrics"]["lane.tokens_per_s"]["value"] > 0
    assert 0 < result["metrics"]["ouro.mfu"]["value"] < 100
    # the lane's gauges are its model's: the exits', no expert layer's
    import program_lane_parts

    gauges = program_lane_parts.lane_gauges()
    assert {"exit_last_mass", "exit_entropy_share", "loop_passes",
            "layer_visits_per_pass", "exits_trained"} <= set(gauges)
    assert not [name for name in gauges if name.startswith("moe_")]


def test_the_files_arithmetic():
    """The cut as the configuration's file states it: depth only, every
    published number of the catalog's row kept."""
    cell, config, traffic, _, _ = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "bohb-1x9"
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 8
    assert config["cut"]["layers"] == list(range(8))
    assert 48 // config["cut"]["chips_in_the_ring"] == 8
    d, f, rows = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    assert (d, f, rows, config["head_dim"]) == (2048, 5632, 49152, 128)
    assert config["num_attention_heads"] == config["num_key_value_heads"] == 16
    assert config["total_ut_steps"] == 4 and config["early_exit_threshold"] == 1
    layer = 4 * d * d + 3 * d * f + 4 * d
    assert layer == 51_388_416
    total = 8 * layer + 2 * rows * d + d + d + 1
    assert total == 612_438_017
    assert "612,438,017 parameters = 7.35 GB" in config["cut"]["parameters"]
    assert 12 * total == pytest.approx(7.35e9, rel=1e-3)
    for said in ("sandwich_norm", "final_norm_closes_every_pass", "gate",
                 "exit_distribution", "trained_loss", "beta", "reported_loss",
                 "rotary_pairing", "optimizer", "tokens", "data_seed"):
        assert said in config["assumed"]
    sys.modules.setdefault("program", run.load_module("program.py"))
    built = run.load_module("configs", "ouro-sgd.py").lane_config(config)
    from hpbandster_tpu.workloads.ouro import OuroConfig

    assert built == OuroConfig()


def test_lane_counts_of_the_published_cell():
    import lane_counts_ouro as counts
    from reference import halving

    cell, config, traffic, _, _ = run.load_cell(CELL)
    plans = halving.schedule(config, traffic, 1)
    assert counts.schedule_passes(plans) == (27, 13)
    assert counts.lane_params(config) == 612_438_017
    assert counts.attended_pairs(config) == 2048 * 2049 // 2
    forward = counts.visit_forward_flops(config)
    # by hand: four projections of 2,048 x 2,048 and the half-square; three
    # products of 2,048 x 5,632; one head of 2,048 x 49,152
    assert forward["gqa"] == 2 * 4 * 2048 * 2048 + 4 * 16 * 128 * 2049 / 2
    assert forward["dense_ffn"] == 2 * 3 * 2048 * 5632
    assert forward["head"] == 2 * 2048 * 49152
    visits = 8 * 4
    products = visits * (2 * 4 * 2048 * 2048 + forward["dense_ffn"])
    attention = visits * 4 * 16 * 128 * 2049 / 2
    assert products == pytest.approx(3.29e9, rel=2e-3)
    assert attention == pytest.approx(0.27e9, rel=1e-2)
    assert 4 * forward["head"] == pytest.approx(0.81e9, rel=1e-2)
    trained = products + attention + 4 * forward["head"]      # a token, forward, training
    held_out = products + attention + forward["head"]
    assert trained == pytest.approx(4.36e9, rel=2e-3)
    assert held_out == pytest.approx(3.76e9, rel=2e-3)
    update, moved = counts.part_work(config, plans, "update")
    assert counts.sweep_flops(config, plans) == pytest.approx(
        2048 * (3 * 27 * trained + 13 * held_out) + update)
    assert counts.sweep_flops(config, plans) == pytest.approx(824e12, rel=2e-3)
    assert moved == pytest.approx(27 * 20 * 612.4e6, rel=1e-3)
    # a layer's weights are read once a visit: 32 visits of 8 layers
    _, ffn_bytes = counts.part_work(config, plans, "dense_ffn")
    rows = 4 * 2 * 2048 * 2048
    assert ffn_bytes == 32 * ((12 * 3 * 2048 * 5632 + 3 * rows) * 27
                              + (4 * 3 * 2048 * 5632 + rows) * 13)
