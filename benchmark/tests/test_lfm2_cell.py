"""The ``lfm2-sgd.bohb-1x9`` cell through the harness on the CPU: the cell,
its traffic and its metrics as the root ``BENCHMARK.json`` has them, the
configuration at the size of the program's own CPU tests
(``tests/lfm2_small.py``). What is tested is that every file the cell needs
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
CELL = "lfm2-sgd.bohb-1x9"
NEW_METRICS = {
    "lfm2.mfu", "lane.conv_device_share", "lfm2.conv_roofline_share",
    "lfm2.attn_roofline_share", "lfm2.moe_roofline_share"}
LISTED = {
    "replay.host_s_per_keval", "program.build_compile_s", "program.trace_lower_s",
    "program.compile_s", "lane.tokens_per_s", "lane.moe_device_share",
    "lane.update_device_share", "lane.gqa_device_share", "lane.dense_ffn_device_share",
    "lane.head_device_share", "lane.no_part_device_share", "lane.forward_device_share",
    "lane.recompute_device_share", "lane.backward_device_share", "moe.held_choice_share",
    "moe.router_device_share", "moe.sort_device_share", "moe.dispatch_device_share",
    "moe.experts_device_share", "moe.combine_device_share"}


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A root whose ``BENCHMARK.json`` is the repo's, its configuration's
    file the small one."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    try:
        from lfm2_small import SMALL
    finally:
        sys.path.remove(os.path.join(ROOT, "tests"))
    root = tmp_path_factory.mktemp("lfm2_root")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-sgd")
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
    assert not {"lane.mfu", "mellum2.mfu", "ouro.mfu", "lane.moe_roofline_share",
                "mellum2.moe_roofline_share", "lane.gqa_roofline_share",
                "lane.swa_device_share", "lane.kda_device_share",
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
        "lane.tokens_per_s", "lfm2.mfu", "moe.held_choice_share", "driver.dispatch_fetch_s",
        "driver.sweep_wall_p90_s", "cache.new_entries", "replay.host_s_per_keval",
        "program.build_compile_s", "device.idle_share", "device.peak_hbm_bytes"}
    assert set(result["metrics"]) <= names
    assert result["metrics"]["lane.tokens_per_s"]["value"] > 0
    assert 0 < result["metrics"]["lfm2.mfu"]["value"] < 100
    # the lane's gauges are its model's
    import program_lane_parts

    gauges = program_lane_parts.lane_gauges()
    assert gauges["conv_layers"] == 2 and gauges["head_tied"] == 1
    assert {"moe_held_choice_share", "moe_products_in_vmem", "attn_scores_in_vmem",
            "attn_key_blocks_computed"} <= set(gauges)


def test_the_files_arithmetic():
    """The cut as the configuration's file states it: every published
    number of the catalog's row kept, the five keys of ``reduced`` alone
    changed."""
    cell, config, traffic, _, _ = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "bohb-1x9"
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts",
                                 "vocab_size", "layer_types"]
    published = config["published"]
    assert [published[k] for k in config["reduced"][:4]] == [24, 2, 32, 65536]
    assert published["layer_types"].count("conv") == 18
    assert [i for i, kind in enumerate(published["layer_types"])
            if kind == "full_attention"] == [2, 6, 10, 14, 18, 21]
    cut = config["cut"]
    assert cut["layers"] == [0, 2, 3, 4, 5] and cut["chips_sharing_a_layer"] == 4
    assert config["layer_types"] == [published["layer_types"][i] for i in cut["layers"]]
    assert config["num_hidden_layers"] == 5 and config["num_dense_layers"] == 1
    assert cut["experts_held"] == list(range(8)) and config["num_experts"] == 8
    assert cut["router_outputs"] == 32 and config["vocab_size"] * 4 == 65536
    d, f, e = config["hidden_size"], config["intermediate_size"], config["moe_intermediate_size"]
    assert (d, f, e, config["conv_L_cache"]) == (2048, 7168, 1792, 3)
    assert (config["num_attention_heads"], config["num_key_value_heads"]) == (32, 8)
    assert config["num_experts_per_tok"] == 4 and config["routed_scaling_factor"] == 1
    assert config["norm_eps"] == 1e-5 and config["rope_theta"] == 1000000
    assert config["router_epsilon"] == 1e-6
    conv = d * 3 * d + 3 * d + d * d
    attention = 2 * d * d + 2 * d * 512 + 2 * 64
    expert, router = 3 * d * e, d * 32 + 32
    assert (conv, attention, expert) == (16_783_360, 10_485_888, 11_010_048)
    layer0 = 2 * d + conv + 3 * d * f
    layer2 = 2 * d + attention + router + 8 * expert
    layer3 = 2 * d + conv + router + 8 * expert
    assert (layer0, layer2, layer3) == (60_827_648, 98_635_936, 104_933_408)
    total = layer0 + layer2 + 3 * layer3 + 16384 * d + d
    assert total == 507_820_288
    assert "507,820,288 parameters = 6.09 GB" in cut["parameters"]
    assert 12 * total == pytest.approx(6.09e9, rel=1e-3)
    assert 8192 * 4 // 32 == 1024 and "1,024 token-choices" in cut["expert_load"]
    for said in ("head_dim", "conv_mixer", "qk_norm", "rotary_pairing", "router",
                 "expert_bias", "aux_loss", "tie_embedding", "final_norm", "init", "tokens",
                 "optimizer", "data_seed"):
        assert said in config["assumed"]
    sys.modules.setdefault("program", run.load_module("program.py"))
    built = run.load_module("configs", "lfm2-sgd.py").lane_config(config)
    from hpbandster_tpu.workloads.lfm2 import Lfm2Config

    assert built == Lfm2Config()


def test_lane_counts_of_the_published_cell():
    import lane_counts_lfm2 as counts
    from reference import halving

    cell, config, traffic, _, _ = run.load_cell(CELL)
    plans = halving.schedule(config, traffic, 1)
    assert counts.schedule_passes(plans) == (27, 13)
    assert counts.lane_params(config) == 507_820_288
    assert counts.layers_of(config) == {
        "conv": 4, "gqa": 1, "moe": 4, "dense_ffn": 1, "head": 1, "update": 0}
    assert counts.attended_pairs(config) == 8192 * 8193 // 2
    forward = counts.part_forward_flops(config)
    # by hand, multiply-adds a token: 2,048 x 6,144 + 2,048 x 2,048; four
    # projections and the half-square of 32 heads of 64; the router and an
    # eighth of 4 choices' three products; the dense SwiGLU; the tied head
    assert forward["conv"] == 2 * 16_777_216
    assert forward["gqa"] == 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 4 * 32 * 64 * 8193 / 2
    assert forward["moe"] == 2 * 2048 * 32 + (4 * 8 / 32) * 6 * 2048 * 1792
    assert forward["dense_ffn"] == 2 * 3 * 2048 * 7168
    assert forward["head"] == 2 * 2048 * 16384
    token = (4 * forward["conv"] + forward["gqa"] + 4 * forward["moe"]
             + forward["dense_ffn"] + forward["head"])
    assert token == pytest.approx(0.4325e9, rel=1e-3)      # 216 M multiply-adds
    update, moved = counts.part_work(config, plans, "update")
    assert counts.sweep_flops(config, plans) == pytest.approx(
        8192 * (3 * 27 + 13) * token + update)
    assert counts.sweep_flops(config, plans) == pytest.approx(333e12, rel=2e-3)
    assert moved == 27 * 20 * 507_820_288
    # a convolution mixer's bytes: weights, rows, and u, z, c, y written and read once
    _, conv_bytes = counts.part_work(config, plans, "conv")
    rows = 4 * 2 * 8192 * 2048
    between = 4 * 2 * 8192 * (3 * 2048 + 3 * 2048)
    assert conv_bytes == 4 * ((12 * 16_777_216 + 3 * (rows + between)) * 27
                              + (4 * 16_777_216 + rows + between) * 13)
    # near the ridge: operations and bytes take the chip about as long
    conv_flops, _ = counts.part_work(config, plans, "conv")
    assert 0.8 < (conv_bytes / 819e9) / (conv_flops / 197e12) < 1.0
