"""The readers of a lane's passes and of its expert layer's pieces (PR 38):
``lane_pieces.split`` on hand-made seconds and maps, every new metric's
``read`` on the recorded trace's own ``op_s`` with maps made over its
instruction names (shares adding up to what the older readers read), the
build gauges, and all of them on a program that has none of it.

    python -m pytest benchmark/tests/test_lane_pieces.py
"""

import json
import os

import pytest

import lane_counts
import lane_pieces
import program_lane_parts
import program_lane_pieces
import run
import span_reduce
import trace_reduce
from test_benchmark import recorded  # noqa: F401

ROOT = run.ROOT
PASSES = ("pass.forward", "pass.recompute", "pass.backward")
PIECES = ("moe.router", "moe.sort", "moe.dispatch", "moe.experts", "moe.combine")
NEW_METRICS = (
    ["lane.%s_device_share" % p for p in ("forward", "recompute", "backward")]
    + ["%s_device_share" % p for p in PIECES]
    + ["program.trace_lower_s", "program.compile_s"])
LANE_CELLS = ["kimi-linear-sgd.bohb-1x9", "mellum2-sgd.bohb-1x9", "ouro-sgd.bohb-1x9"]


def read(name, ctx):
    return run.load_module("layer_metrics", name + ".py").read(ctx)


def test_split_adds_the_seconds_up_by_part_pass_and_piece():
    op_s = {"fusion.1": 4.0, "ragged-dot-none.2": 2.0, "fusion.3": 1.0, "fusion.4": 0.5,
            "fusion.5": 0.25, "copy.6": 0.125, "fusion.7": 0.0625}
    parts = {"fusion.1": "lane.moe", "ragged-dot-none.2": "lane.moe", "fusion.3": "lane.gqa",
             "fusion.4": "lane.update", "fusion.5": "lane.moe", "fusion.7": "lane.gqa"}
    passes = {"fusion.1": "pass.forward", "ragged-dot-none.2": "pass.recompute",
              "fusion.3": "pass.backward", "fusion.5": "pass.backward"}
    pieces = {"fusion.1": "moe.experts", "ragged-dot-none.2": "moe.experts",
              "fusion.3": "moe.router"}  # a piece outside the layer: told apart
    found = lane_pieces.split(op_s, parts, passes, pieces)
    assert found["busy_s"] == sum(op_s.values())
    assert found["part_pass_s"] == {
        "lane.moe": {"pass.forward": 4.0, "pass.recompute": 2.0, "pass.backward": 0.25},
        "lane.gqa": {"pass.backward": 1.0, "no pass": 0.0625},
        "lane.update": {"no pass": 0.5}, "no part": {"no pass": 0.125}}
    assert found["piece_s"] == {"moe.experts": 6.0, "no piece": 0.25}
    assert found["stray_piece_s"] == 1.0
    assert found["grouped_kernel_s"] == {"pass.recompute": 2.0}
    # the update's seconds are its own share's, not the no-pass share's
    assert lane_pieces.no_pass_s(found) == 0.0625 + 0.125
    assert sum(sum(p.values()) for p in found["part_pass_s"].values()) == found["busy_s"]


@pytest.fixture
def traced(recorded, monkeypatch):  # noqa: F811
    """A traced run's ``ctx`` (the recorded reduction) and maps over its own
    instruction names: a part, a pass and a piece each in turn, some left
    without."""
    trace = trace_reduce.reduce_file(recorded, 1)
    names = sorted(trace["op_s"], key=lambda n: -trace["op_s"][n])
    assert len(names) > 30
    parts = {n: ("lane.moe", "lane.gqa", "lane.update")[i % 3]
             for i, n in enumerate(names) if i % 7}
    passes = {n: PASSES[i // 3 % 3] for i, n in enumerate(names)
              if parts.get(n) not in (None, "lane.update") and i % 5}
    pieces = {n: (PIECES + ("moe.shared",))[i // 3 % 6] for i, n in enumerate(names)
              if parts.get(n) == "lane.moe" and i % 11}
    maps = {"parts": {"jit_hpb_sweep": parts}, "passes": {"jit_hpb_sweep": passes},
            "pieces": {"jit_hpb_sweep": pieces}}
    asked = []

    def family_maps(family):
        asked.append(family)
        return maps[family]

    monkeypatch.setattr(program_lane_pieces, "family_maps", family_maps)
    monkeypatch.setattr(program_lane_parts, "lane_maps", lambda: family_maps("parts"))
    return {"trace": trace, "chips": 1}, asked


def test_the_shares_add_up_to_what_the_older_readers_read(traced, capsys):
    ctx, asked = traced
    values = {name: read(name, ctx) for name in NEW_METRICS[:8]}
    assert all(v is not None and v > 0 for v in values.values()), values
    # one join for the eight metrics
    assert sorted(asked) == ["parts", "passes", "pieces"]
    found = ctx["lane_pieces"]
    busy = found["busy_s"]
    assert busy == pytest.approx(lane_counts.lane_spans(ctx)["busy_s"])
    # the passes, the update's share and what carries no pass: all of busy
    update = lane_counts.device_share(ctx, "update")
    no_pass = 100.0 * lane_pieces.no_pass_s(found) / busy
    assert sum(values[n] for n in NEW_METRICS[:3]) + update + no_pass == pytest.approx(100.0)
    # the pieces, the shared expert and no piece: the layer's share
    inside = sum(values[n] for n in NEW_METRICS[3:8]) + 100.0 * (
        found["piece_s"]["moe.shared"] + found["piece_s"]["no piece"]) / busy
    assert inside == pytest.approx(lane_counts.device_share(ctx, "moe"))
    assert found["stray_piece_s"] == 0.0
    # by part, the passes add up to the part's seconds
    for part, seconds in lane_counts.lane_spans(ctx)["phase_s"].items():
        part = "no part" if part == span_reduce.UNNAMED else part
        assert sum(found["part_pass_s"][part].values()) == pytest.approx(seconds)
    printed = capsys.readouterr().out
    assert "lane passes, busy seconds by part" in printed
    assert "expert layer, busy seconds by piece" in printed
    shares = json.loads(printed.split("lane shares of busy, %: ")[1].splitlines()[0])
    assert sum(shares.values()) == pytest.approx(100.0)


def test_a_lane_without_experts_reports_its_passes_alone(traced):
    ctx, _ = traced
    real = program_lane_pieces.family_maps
    program_lane_pieces.family_maps = lambda f: None if f == "pieces" else real(f)
    assert all(read(name, ctx) > 0 for name in NEW_METRICS[:3])
    assert [read(name, ctx) for name in NEW_METRICS[3:8]] == [None] * 5


def test_an_untraced_run_and_a_program_without_the_names_report_nothing(
        recorded, monkeypatch):  # noqa: F811
    assert [read(name, {"trace": None}) for name in NEW_METRICS[:8]] == [None] * 8
    # the parent commit: no such list of names, no such gauges
    from hpbandster_tpu.obs import get_metrics, timeline

    for family in program_lane_pieces.FAMILIES.values():
        monkeypatch.delattr(timeline, family)
    monkeypatch.setattr(get_metrics(), "snapshot", lambda: {"gauges": {"sweep.lane.x": 1.0}})
    ctx = {"trace": trace_reduce.reduce_file(recorded, 1), "chips": 1}
    assert [read(name, ctx) for name in NEW_METRICS] == [None] * 10
    assert ctx["lane_pieces"] is None


def test_the_program_offers_the_families_and_the_build_gauges():
    from hpbandster_tpu.obs import get_metrics, timeline

    for family, name in program_lane_pieces.FAMILIES.items():
        assert hasattr(timeline, name)
        # this process has built no sweep program that names any
        assert program_lane_pieces.family_maps(family) is None
    metrics = get_metrics()
    # what the process's builds before this test (another file's) left there
    built = program_lane_pieces.build_gauges() or {"trace_lower_s": 0.0, "compile_s": 0.0}
    metrics.gauge("sweep.build.trace_lower_s").inc(1.5)
    metrics.gauge("sweep.build.compile_s").inc(2.5)
    try:
        assert program_lane_pieces.build_gauges() == {
            "trace_lower_s": built["trace_lower_s"] + 1.5, "compile_s": built["compile_s"] + 2.5}
        assert read("program.trace_lower_s", {}) == built["trace_lower_s"] + 1.5
        assert read("program.compile_s", {}) == built["compile_s"] + 2.5
    finally:
        metrics.gauge("sweep.build.trace_lower_s").inc(-1.5)
        metrics.gauge("sweep.build.compile_s").inc(-2.5)


def test_new_metrics_are_entered_for_their_cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = [w["name"] for w in bench["workloads"]]
    entered = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert os.path.exists(os.path.join(run.HERE, "layer_metrics", name + ".py"))
        metric = entered[name]
        if name.startswith("program."):
            want, moves, source = cells, "setup_s", "program_counter"
        else:
            want = LANE_CELLS if name.startswith("lane.") else LANE_CELLS[:2]
            moves, source = "evals_per_s_per_chip", "device_trace"
        assert (metric["workloads"], metric["moves"], metric["source"]) == (want, moves, source)
    # appended: what the file had keeps its place
    assert [m["name"] for m in bench["per_layer"]][-10:] == NEW_METRICS
