"""The ``laguna-xs2-sgd.bohb-1x9`` cell through the harness on the CPU: the
cell, its traffic and its metrics as the root ``BENCHMARK.json`` has them, the
configuration at the size of the program's own CPU tests
(``tests/laguna_small.py``). What is tested is that every file the cell needs
is found and runs; whether a step is right is the chip's to say, at the
published widths. And the file's arithmetic and the counts of the published
cell, against a count by hand."""

import json
import os
import sys

import pytest

import run
from test_benchmark import on_cpu, recorded  # noqa: F401

ROOT = run.ROOT
CELL = "laguna-xs2-sgd.bohb-1x9"
NEW_METRICS = {"laguna.mfu", "laguna.attn_roofline_share", "laguna.moe_roofline_share",
               "laguna.attn_in_vmem_share"}
LISTED = {
    "replay.host_s_per_keval", "program.build_compile_s", "program.trace_lower_s",
    "program.compile_s", "lane.tokens_per_s", "lane.moe_device_share",
    "lane.update_device_share", "moe.held_choice_share", "lane.swa_device_share",
    "lane.gqa_device_share", "lane.dense_ffn_device_share", "lane.head_device_share",
    "lane.no_part_device_share", "lane.forward_device_share",
    "lane.recompute_device_share", "lane.backward_device_share",
    "moe.router_device_share", "moe.sort_device_share", "moe.dispatch_device_share",
    "moe.experts_device_share", "moe.combine_device_share"}


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A root whose ``BENCHMARK.json`` is the repo's, its configuration's
    file the small one."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    try:
        from laguna_small import SMALL
    finally:
        sys.path.remove(os.path.join(ROOT, "tests"))
    root = tmp_path_factory.mktemp("laguna_root")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "laguna-xs2-sgd")
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
    names = {m["name"] for m in per_layer}
    assert NEW_METRICS | LISTED <= names
    # other lanes' counts and parts stay off this cell
    assert not {"lane.mfu", "mellum2.mfu", "ouro.mfu", "lfm2.mfu", "sdar.mfu",
                "olmo_hybrid.mfu", "lane.kda_device_share", "lane.swa_roofline_share",
                "lane.gqa_roofline_share", "lane.conv_device_share", "lane.gdn_device_share",
                "lane.accumulate_device_share", "lane.exit_device_share"} & names
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
        "lane.tokens_per_s", "laguna.mfu", "laguna.attn_in_vmem_share",
        "moe.held_choice_share", "driver.dispatch_fetch_s", "driver.sweep_wall_p90_s",
        "cache.new_entries", "replay.host_s_per_keval", "program.build_compile_s",
        "device.idle_share", "device.peak_hbm_bytes"}
    assert set(result["metrics"]) <= names
    assert 10 < result["metrics"]["moe.held_choice_share"]["value"] < 50
    assert result["metrics"]["lane.tokens_per_s"]["value"] > 0
    assert 0 < result["metrics"]["laguna.mfu"]["value"] < 100
    # off the chip no layer's scores stay in VMEM
    assert result["metrics"]["laguna.attn_in_vmem_share"]["value"] == 0.0


def test_the_control_is_not_correct(on_cpu, small_root):  # noqa: F811
    """``control.py``'s readings at the small size: the program's sweep is
    ``correct`` and the reference with a bfloat16 state in its place is not,
    by the first step's change."""
    import jax

    import control

    cell, config, traffic, _, _ = run.load_cell(CELL, root=small_root)
    (row,) = control.readings(cell, config, traffic, jax.devices()[:1], [2 ** 31 + 5], 0.5)
    assert row["raised"] == 0 and row["sound_correct"], row
    assert not row["control_correct"], row
    assert max(v for k, v in row["control"].items() if k.startswith("change_gap_")) > 0.5


def test_a_program_without_the_lane_reads_nothing(monkeypatch):
    """On the parent commit's program, and in an untraced run, the new readers
    return nothing and do not raise; with the parts' seconds they read shares
    of their own parts, both kinds of attention together."""
    import lane_counts_laguna as counts
    import program_lane_parts
    from reference import halving

    _, config, traffic, _, _ = run.load_cell(CELL)
    ctx = {"trace": None, "config": config, "plans": halving.schedule(config, traffic, 1),
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "chips": 1}
    attn = run.load_module("layer_metrics", "laguna.attn_roofline_share.py")
    moe = run.load_module("layer_metrics", "laguna.moe_roofline_share.py")
    in_vmem = run.load_module("layer_metrics", "laguna.attn_in_vmem_share.py")
    assert attn.read(ctx) is None and moe.read(ctx) is None
    # traced, with parts but none of these names
    ctx["lane_spans"] = {"phase_s": {"lane.kda": 1.0}, "busy_s": 1.0, "sweeps": 4}
    assert attn.read(ctx) is None and moe.read(ctx) is None
    # a program that publishes no such gauge (or none at all)
    monkeypatch.setattr(program_lane_parts, "lane_gauges", lambda: None)
    assert in_vmem.read(ctx) is None
    monkeypatch.setattr(program_lane_parts, "lane_gauges", lambda: {"moe_held_choice_share": .1})
    assert in_vmem.read(ctx) is None
    monkeypatch.setattr(program_lane_parts, "lane_gauges", lambda: {"attn_scores_in_vmem": 0.6})
    assert in_vmem.read(ctx) == 60.0
    # four sweeps whose attention took the chip twice its least seconds
    least = sum(max(f / 197e12, b / 819e9) for f, b in (
        counts.part_work(config, ctx["plans"], part) for part in ("swa", "gqa")))
    ctx["lane_spans"] = {"phase_s": {"lane.swa": 3 * least, "lane.gqa": 5 * least,
                                     "lane.moe": 8.0}, "busy_s": 8 * least + 8.0, "sweeps": 4}
    assert attn.read(ctx) == pytest.approx(50.0)
    assert 0 < moe.read(ctx) < 100


def test_the_files_arithmetic():
    """The cut as the configuration's file states it, and the parameters by
    hand: 691.6 M, 8.30 GB of training state."""
    cell, config, traffic, _, _ = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "bohb-1x9" and len(cell["why"]) <= 200
    assert "attention 8 x" in cell["why"] and "depth 5 of 40" in cell["why"]
    cut = config["cut"]
    assert cut["layers"] == [0, 1, 2, 3, 4] and cut["chips_sharing_a_layer"] == 8
    assert len(cut["experts_held"]) * 8 == cut["router_outputs"] == 256
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] == 100352
    assert "8,192 x 8 / 256 = 256 token-choices" in cut["expert_load"]
    assert "8 chips share" not in config["deployment"] and "one chip of 8" in config["deployment"]
    d, dh, g = config["hidden_size"], config["head_dim"], config["num_key_value_heads"]
    assert (d, dh, g) == (2048, 128, 8)
    attention = lambda heads: 2 * d * heads * dh + 2 * d * g * dh + d * heads
    assert attention(48) == pytest.approx(29.5e6, rel=2e-3)
    assert attention(64) == pytest.approx(37.9e6, rel=2e-3)
    dense = 3 * d * config["intermediate_size"]
    expert = 3 * d * config["moe_intermediate_size"]
    outside = d * 256 + 3 * d * config["shared_expert_intermediate_size"]
    assert (dense, expert) == (50_331_648, 3_145_728)
    layer0 = attention(48) + dense + 2 * d
    sliding = attention(64) + outside + 32 * expert + 2 * d
    layer4 = attention(48) + outside + 32 * expert + 2 * d
    assert sliding == pytest.approx(142.2e6, rel=1e-3) and layer4 == pytest.approx(133.8e6, rel=1e-3)
    total = layer0 + 3 * sliding + layer4 + 2 * d * config["vocab_size"] + d
    assert total == 691_623_936 and 12 * total == pytest.approx(8.30e9, rel=1e-3)
    assert config["train"] == {"seq_len": 8192, "n_train": 32, "n_val": 1}
    assert (config["eta"], config["min_budget"], config["max_budget"]) == (3, 1, 9)
    sys.modules.setdefault("program", run.load_module("program.py"))
    builder = run.load_module("configs", "laguna-xs2-sgd.py")
    from hpbandster_tpu.workloads.laguna import LagunaConfig

    assert builder.lane_config(config) == LagunaConfig()
    unknown = dict(config, layer_types=["full_attention", "linear_attention"] + config[
        "layer_types"][2:])
    with pytest.raises(ValueError, match="full_attention or sliding_attention"):
        builder.lane_config(unknown)


def test_lane_counts_of_the_published_cell():
    import lane_counts_laguna as counts
    from reference import halving

    cell, config, traffic, _, _ = run.load_cell(CELL)
    plans = halving.schedule(config, traffic, 1)
    assert counts.schedule_passes(plans) == (27, 13)
    assert counts.lane_params(config) == 691_623_936
    assert counts.layers(config) == [
        ("gqa", "dense_ffn", 48), ("swa", "moe", 64), ("swa", "moe", 64), ("swa", "moe", 64),
        ("gqa", "moe", 48)]
    assert [len(counts.part_layers(config, part)) for part in counts.PARTS] == [3, 2, 4, 1, 1, 0]
    # the band and not the square: an eighth of the causal half, nearly
    band, half_square = counts.attended_pairs(config, "swa"), counts.attended_pairs(config, "gqa")
    assert band == 512 * 8192 - 512 * 511 // 2 and half_square == 8192 * 8193 // 2
    assert 0.12 < band / half_square < 0.125
    # by hand, a token: the projections and the gate, scores and values over
    # the pairs, the turned half (64 of 128 channels, 48 + 8 heads) and the gate
    full = counts.attention_forward_flops(config, "gqa", 48)
    assert full == (2 * (2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48)
                    + 4 * 48 * 128 * 8193 / 2 + 3 * 64 * 56 + 48 * 128)
    window = counts.attention_forward_flops(config, "swa", 64)
    assert window == (2 * (2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64)
                      + 4 * 64 * 128 * band / 8192 + 3 * 128 * 72 + 64 * 128)
    # the scores are two thirds of a full layer's operations, a fifth of a window layer's
    assert 0.6 < 4 * 48 * 128 * 8193 / 2 / full < 0.7
    assert 0.15 < 4 * 64 * 128 * band / 8192 / window < 0.2
    moe = counts.ffn_forward_flops(config, "moe")
    assert moe == 2 * 2048 * 256 + 6 * 2048 * 512 + (8 * 32 / 256) * 6 * 2048 * 512
    assert counts.ffn_forward_flops(config, "dense_ffn") == 6 * 2048 * 8192
    token = 2 * full + 3 * window + 4 * moe + 6 * 2048 * 8192 + 2 * 2048 * 12544
    update, moved = counts.part_work(config, plans, "update")
    assert counts.sweep_flops(config, plans) == pytest.approx(
        8192 * (3 * 27 + 13) * token + update)
    assert moved == 27 * 20 * 691_623_936
    # attention is over half of the step's operations: the mechanism's cell
    attn = sum(counts.part_work(config, plans, part)[0] for part in counts.ATTENTION)
    assert attn / counts.sweep_flops(config, plans) > 0.5
    # the expert layers' two bounds: 256 choices an expert is memory's
    moe_flops, moe_bytes = counts.part_work(config, plans, "moe")
    assert (moe_flops / 197e12) < (moe_bytes / 819e9)
