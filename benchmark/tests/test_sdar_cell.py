"""The ``sdar-sgd.bohb-1x9`` cell through the harness on the CPU: the cell,
its traffic and its metrics as the root ``BENCHMARK.json`` has them, the
configuration at the size of the program's own CPU tests
(``tests/sdar_small.py``). What is tested is that every file the cell needs
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
CELL = "sdar-sgd.bohb-1x9"
NEW_METRICS = {
    "sdar.mfu", "lane.bda_device_share", "sdar.attn_roofline_share", "sdar.moe_roofline_share"}
LISTED = {
    "replay.host_s_per_keval", "program.build_compile_s", "program.trace_lower_s",
    "program.compile_s", "lane.tokens_per_s", "lane.moe_device_share",
    "lane.update_device_share", "lane.head_device_share", "lane.no_part_device_share",
    "lane.forward_device_share", "lane.recompute_device_share",
    "lane.backward_device_share", "moe.held_choice_share", "moe.router_device_share",
    "moe.sort_device_share", "moe.dispatch_device_share", "moe.experts_device_share",
    "moe.combine_device_share"}


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A root whose ``BENCHMARK.json`` is the repo's, its configuration's
    file the small one."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    try:
        from sdar_small import SMALL
    finally:
        sys.path.remove(os.path.join(ROOT, "tests"))
    root = tmp_path_factory.mktemp("sdar_root")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "sdar-sgd")
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
    assert not {"lane.mfu", "mellum2.mfu", "ouro.mfu", "lfm2.mfu", "lane.moe_roofline_share",
                "mellum2.moe_roofline_share", "lfm2.moe_roofline_share",
                "lane.gqa_roofline_share", "lane.gqa_device_share", "lane.swa_device_share",
                "lane.kda_device_share", "lane.conv_device_share",
                "lane.accumulate_device_share", "lane.exit_device_share"} & names
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 13, seconds=0.5, trace=trace)
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
        "lane.tokens_per_s", "sdar.mfu", "moe.held_choice_share", "driver.dispatch_fetch_s",
        "driver.sweep_wall_p90_s", "cache.new_entries", "replay.host_s_per_keval",
        "program.build_compile_s", "device.idle_share", "device.peak_hbm_bytes"}
    assert set(result["metrics"]) <= names
    assert result["metrics"]["lane.tokens_per_s"]["value"] > 0
    assert 0 < result["metrics"]["sdar.mfu"]["value"] < 100
    # the lane's gauges are its model's
    import program_lane_parts

    gauges = program_lane_parts.lane_gauges()
    assert gauges["diffusion_rows_per_token"] == 2 and 0 < gauges["diffusion_masked_share"] < 1
    assert gauges["attn_scores_in_vmem"] == 0
    assert {"moe_held_choice_share", "moe_products_in_vmem", "attn_key_blocks_computed",
            "attn_key_blocks_square"} <= set(gauges)


def test_the_files_arithmetic():
    """The cut as the configuration's file states it: every published
    number of the catalog's row kept, the three keys of ``reduced`` alone
    changed."""
    cell, config, traffic, _, _ = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "bohb-1x9"
    assert config["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert [config["published"][k] for k in config["reduced"]] == [48, 128, 151936]
    # the catalog's row, letter for letter, but for the three
    row = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
           "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
           "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
           "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True,
           "num_attention_heads": 32, "num_experts_per_tok": 8, "num_key_value_heads": 4,
           "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
           "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False}
    assert {k: config[k] for k in row} == row
    cut = config["cut"]
    assert cut["layers"] == [0, 1, 2, 3] and cut["chips_sharing_a_layer"] == 8
    assert config["num_hidden_layers"] == 4
    assert cut["experts_held"] == list(range(16)) and config["num_experts"] == 16
    assert cut["router_outputs"] == 128 and config["vocab_size"] * 8 == 151936
    d, e = config["hidden_size"], config["moe_intermediate_size"]
    projections = 2 * d * 4096 + 2 * d * 512
    expert, router = 3 * d * e, d * 128
    assert (projections, expert, router) == (18_874_368, 4_718_592, 262_144)
    layer = projections + 2 * 128 + router + 16 * expert + 2 * d
    assert layer == 94_638_336
    total = 4 * layer + 2 * 18992 * d + d
    assert total == 456_346_624
    assert "456,346,624 parameters = 5.48 GB" in cut["parameters"]
    assert 12 * total == pytest.approx(5.48e9, rel=1e-3)
    assert 8192 * 8 // 128 == 512 and "512 token-choices" in cut["expert_load"]
    assert config["train"] == {"seq_len": 4096, "n_train": 32, "n_val": 1,
                               "block_length": 4, "noise_floor": 0.001}
    assert any("masks and noise levels" in g for g in config["guarantees"])
    for said in ("block_length", "noise_schedule", "mask_token", "rows", "sight", "loss",
                 "qk_norm", "rotary_pairing", "router", "aux_loss", "final_norm", "init",
                 "tokens", "optimizer", "data_seed", "unread_keys"):
        assert said in config["assumed"]
    sys.modules.setdefault("program", run.load_module("program.py"))
    built = run.load_module("configs", "sdar-sgd.py").lane_config(config)
    from hpbandster_tpu.workloads.sdar import SdarConfig

    assert built == SdarConfig()


def test_lane_counts_of_the_published_cell():
    import lane_counts_sdar as counts
    from reference import halving

    cell, config, traffic, _, _ = run.load_cell(CELL)
    plans = halving.schedule(config, traffic, 1)
    assert counts.schedule_passes(plans) == (27, 13)
    assert counts.lane_params(config) == 456_346_624
    assert counts.layers_of(config) == {"bda": 4, "moe": 4, "head": 1, "update": 0}
    # the pairs the rule of sight holds, by a loop over blocks at a small
    # size and by the formula at the cell's: a quarter of the square
    small = dict(config, train=dict(config["train"], seq_len=16))
    by_loop = sum((i // 4 + 1) * 4 for i in range(16)) + sum((i // 4) * 4 + 4 for i in range(16))
    assert counts.attended_pairs(small) == by_loop == 16 * 16 + 4 * 16
    assert counts.attended_pairs(config) == 4096 * 4096 + 4 * 4096
    assert counts.attended_pairs(config) / (2 * 4096) ** 2 == pytest.approx(0.25, rel=2e-3)
    forward = counts.part_forward_flops(config)
    # by hand, a pass: four projections on 8,192 rows and the pairs of 32
    # heads of 128; the router and the even load (8 x 16 / 128 = 1 expert a
    # row) on 8,192 rows; the head on the 4,096 masked rows
    assert forward["bda"] == 2 * 18_874_368 * 8192 + 4 * 32 * 128 * (4096 * 4096 + 4 * 4096)
    assert forward["moe"] == (2 * 2048 * 128 + 6 * 2048 * 768) * 8192
    assert forward["head"] == 2 * 2048 * 18992 * 4096
    one_pass = 4 * forward["bda"] + 4 * forward["moe"] + forward["head"]
    assert one_pass == pytest.approx(2.98e12, rel=2e-3)
    assert 4 * 4 * 32 * 128 * counts.attended_pairs(config) == pytest.approx(1.10e12, rel=2e-3)
    update, moved = counts.part_work(config, plans, "update")
    assert counts.sweep_flops(config, plans) == pytest.approx((3 * 27 + 13) * one_pass + update)
    assert counts.sweep_flops(config, plans) == pytest.approx(280e12, rel=2e-3)
    assert moved == 27 * 20 * 456_346_624
    # attention is bound by its operations: the bytes take a twelfth of the time
    flops, moved = counts.part_work(config, plans, "bda")
    assert (moved / 819e9) / (flops / 197e12) < 0.1
