"""The ``olmo-hybrid-sgd.bohb-1x9`` cell through the harness on the CPU: the
cell, its traffic and its metrics as the root ``BENCHMARK.json`` has them, the
configuration at the size of the program's own CPU tests
(``tests/olmo_hybrid_small.py``). What is tested is that every file the cell
needs is found and runs; whether a step is right is the chip's to say, at the
published widths. And the file's arithmetic and the counts of the published
cell, against a count by hand."""

import json
import os
import sys

import pytest

import run
from test_benchmark import on_cpu, recorded  # noqa: F401

ROOT = run.ROOT
CELL = "olmo-hybrid-sgd.bohb-1x9"
NEW_METRICS = {"lane.gdn_device_share", "olmo_hybrid.gdn_roofline_share", "olmo_hybrid.mfu"}
LISTED = {
    "replay.host_s_per_keval", "program.build_compile_s", "program.trace_lower_s",
    "program.compile_s", "lane.tokens_per_s", "lane.update_device_share",
    "lane.gqa_device_share", "lane.dense_ffn_device_share", "lane.head_device_share",
    "lane.no_part_device_share", "lane.forward_device_share",
    "lane.recompute_device_share", "lane.backward_device_share"}


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A root whose ``BENCHMARK.json`` is the repo's, its configuration's
    file the small one."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    try:
        from olmo_hybrid_small import SMALL
    finally:
        sys.path.remove(os.path.join(ROOT, "tests"))
    root = tmp_path_factory.mktemp("olmo_hybrid_root")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "olmo-hybrid-sgd")
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
                "lane.kda_device_share", "lane.kda_roofline_share", "lane.moe_device_share",
                "moe.held_choice_share", "lane.conv_device_share",
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
        "lane.tokens_per_s", "olmo_hybrid.mfu", "driver.dispatch_fetch_s",
        "driver.sweep_wall_p90_s", "cache.new_entries", "replay.host_s_per_keval",
        "program.build_compile_s", "device.idle_share", "device.peak_hbm_bytes"}
    assert set(result["metrics"]) <= names
    assert result["metrics"]["lane.tokens_per_s"]["value"] > 0
    assert 0 < result["metrics"]["olmo_hybrid.mfu"]["value"] < 100
    # the lane's gauges are its model's
    import program_lane_parts

    gauges = program_lane_parts.lane_gauges()
    assert gauges["gdn_gate_per_head"] == 1 and gauges["gdn_backward_by_rule"] == 1
    assert "attn_scores_in_vmem" in gauges


def test_a_program_without_the_scope_reads_nothing(monkeypatch):
    """The parent commit's program has no ``lane.gdn``: the new readers return
    nothing there and do not raise (a traced run of the accepted cells, and
    of this cell on a program that names no such part)."""
    import lane_counts

    _, config, traffic, _, _ = run.load_cell(CELL)
    from reference import halving

    ctx = {"trace": None, "config": config, "plans": halving.schedule(config, traffic, 1),
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "chips": 1}
    share = run.load_module("layer_metrics", "lane.gdn_device_share.py")
    roofline = run.load_module("layer_metrics", "olmo_hybrid.gdn_roofline_share.py")
    assert share.read(ctx) is None and roofline.read(ctx) is None
    # traced, with parts but none of this name
    ctx["lane_spans"] = {"phase_s": {"lane.gqa": 1.0}, "busy_s": 1.0, "sweeps": 4}
    assert roofline.read(ctx) is None and not share.read(ctx)
    ctx["lane_spans"] = {"phase_s": {"lane.gdn": 10.0, "lane.gqa": 10.0}, "busy_s": 20.0,
                         "sweeps": 4}
    assert share.read(ctx) == 50.0 and 0 < roofline.read(ctx) < 100
    assert lane_counts.device_share(ctx, "gdn") == 50.0


def test_the_files_arithmetic():
    """The cut as the configuration's file states it: every published number
    of the catalog's row kept, the three keys of ``reduced`` alone changed."""
    cell, config, traffic, _, _ = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "bohb-1x9"
    assert config["reduced"] == ["num_hidden_layers", "vocab_size", "layer_types"]
    assert config["published"] == {"num_hidden_layers": 32, "vocab_size": 100352}
    cut = config["cut"]
    assert cut["layers"] == [0, 1, 2, 3] and cut["chips_sharing_a_layer"] == 1
    assert cut["chips_sharing_the_vocabulary"] == 8 and config["vocab_size"] * 8 == 100352
    assert config["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    assert config["num_hidden_layers"] == 4
    d, f = config["hidden_size"], config["intermediate_size"]
    assert (d, f, config["num_attention_heads"], config["num_key_value_heads"]) == (
        3840, 11008, 30, 30)
    h, dk, dv = (config[k] for k in (
        "linear_num_key_heads", "linear_key_head_dim", "linear_value_head_dim"))
    assert (h, dk, dv, config["linear_num_value_heads"]) == (30, 96, 192, 30)
    assert config["linear_conv_kernel_dim"] == 4 and config["linear_allow_neg_eigval"] is True
    assert config["rms_norm_eps"] == 1e-6 and config["rope_parameters"] == {"rope_theta": None}
    assert not config["tie_word_embeddings"] and not config["attention_bias"]
    ffn = 3 * d * f
    linear = (2 * d * h * dk + 3 * d * h * dv + 4 * (2 * h * dk + h * dv) + 2 * d * h + 2 * h + dv
              + ffn + 2 * d)
    full = 4 * d * d + 2 * d + ffn + 2 * d
    assert (linear, full) == (215_570_172, 185_809_920)
    total = 3 * linear + full + 2 * config["vocab_size"] * d + d
    assert total == 928_862_196
    assert "928,862,196 parameters = 7.43 GB" in cut["parameters"]
    assert 8 * total == pytest.approx(7.43e9, rel=1e-3)
    assert 4 * linear == pytest.approx(0.86e9, rel=5e-3)
    for said in ("norm_after_the_sublayer", "qk_norm_span", "no_positions", "head_dim",
                 "gdn_projections", "gdn_init", "gdn_scale_and_norms", "output_gate", "beta",
                 "init", "tokens", "optimizer", "data_seed"):
        assert said in config["assumed"]
    assert config["train"] == {"seq_len": 2048, "n_train": 32, "n_val": 1}
    sys.modules.setdefault("program", run.load_module("program.py"))
    built = run.load_module("configs", "olmo-hybrid-sgd.py").lane_config(config)
    from hpbandster_tpu.workloads.olmo_hybrid import OlmoHybridConfig

    assert built == OlmoHybridConfig()


def test_lane_counts_of_the_published_cell():
    import lane_counts_olmo_hybrid as counts
    from reference import halving

    cell, config, traffic, _, _ = run.load_cell(CELL)
    plans = halving.schedule(config, traffic, 1)
    assert counts.schedule_passes(plans) == (27, 13)
    assert counts.lane_params(config) == 928_862_196
    assert counts.layers_of(config) == {
        "gdn": 3, "gqa": 1, "dense_ffn": 4, "head": 1, "update": 1}
    assert counts.attended_pairs(config) == 2048 * 2049 // 2
    forward = counts.part_forward_flops(config)
    # by hand, a token: the seven products of a linear mixer and the
    # recurrence's 7 d_k d_v a head; four projections and the half-square of
    # 30 heads of 128; the SwiGLU; the head over the slice
    assert forward["gdn"] == 2 * (2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30) \
        + 7 * 30 * 96 * 192
    assert forward["gqa"] == 2 * 4 * 3840 * 3840 + 4 * 30 * 128 * 2049 / 2
    assert forward["dense_ffn"] == 2 * 3 * 3840 * 11008
    assert forward["head"] == 2 * 3840 * 12544
    token = 3 * forward["gdn"] + forward["gqa"] + 4 * forward["dense_ffn"] + forward["head"]
    assert token == pytest.approx(1.788e9, rel=1e-3)       # 894 M multiply-adds
    update, moved = counts.part_work(config, plans, "update")
    assert counts.sweep_flops(config, plans) == pytest.approx(
        2048 * (3 * 27 + 13) * token + update)
    assert counts.sweep_flops(config, plans) == pytest.approx(344.4e12, rel=1e-3)
    assert moved == 27 * 20 * 928_862_196
    # the linear mixers are compute's: their operations take the chip some
    # four times as long as their bytes
    gdn_flops, gdn_bytes = counts.part_work(config, plans, "gdn")
    assert 3 < (gdn_flops / 197e12) / (gdn_bytes / 819e9) < 5
