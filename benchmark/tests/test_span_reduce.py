"""The readers of the program's own spans and phase names (PR 25):
``span_reduce``'s pure function on synthetic tuples, each new per-layer
metric's ``read`` on a made ``ctx``, and both on a program that has neither
spans nor a phase map (the recorded trace is the parent commit's).

    python -m pytest benchmark/tests/test_span_reduce.py
"""

import gzip
import json
import os
import shutil

import pytest

import program_phases
import run
import span_reduce

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
NEW_METRICS = [
    "driver.construct_s", "driver.dispatch_s", "driver.fetch_wait_s",
    "replay.span_s_per_keval", "replay.configs_s_per_keval",
    "replay.runs_s_per_keval", "device.idle_unnamed_share",
    "trainer.device_share", "trainer.roofline_share", "promotion.device_share",
    "model.fit_device_share", "device.unnamed_share"]

# one sweep: construction, then a run whose device work is a loop of two
# fusions and a copy nobody named; nanoseconds
THREAD = [
    (0, 100, "bench:construct"), (10, 90, "hpb:construct"),
    (20, 60, "hpb:construct.eval_shape"),
    (100, 1000, "bench:run"), (110, 990, "hpb:run"),
    (120, 200, "hpb:dispatch"), (200, 600, "hpb:fetch"),
    (600, 900, "hpb:bracket_replay"), (610, 700, "hpb:replay.configs"),
    (700, 890, "hpb:replay.runs"), (900, 980, "hpb:result"),
]
OPS = {"/device:TPU:0": [(150, 550, "while.1"), (160, 300, "fusion.2"),
                         (300, 500, "fusion.3"), (560, 580, "copy.4")]}
MODULES = {"/device:TPU:0": [(140, 590, "jit_hpb_sweep")]}
PHASES = {"jit_hpb_sweep": {"while.1": "hpb.train", "fusion.2": "hpb.train",
                        "fusion.3": "hpb.validate"},
          # another program's instruction of the same name: not this one's
          "jit_other": {"copy.4": "hpb.promote"}}
MLP = {"d_in": 8, "width": 8, "n_classes": 4, "n_train": 64, "batch_size": 16}
NS = 1e-9


def made_ctx(spans):
    return {"spans": spans, "chips": 1, "plans": [([4, 1], [1.0, 3.0])],
            "config": {"mlp": MLP}, "peaks": {"flops_per_s": 1e12}}


def read(name, ctx):
    return run.load_module("layer_metrics", name + ".py").read(ctx)


def test_reduction_of_synthetic_spans():
    out = span_reduce.reduce_spans([THREAD], OPS, MODULES, 1, PHASES)
    assert out["sweeps"] == 1 and out["window_s"] == pytest.approx(1000 * NS)
    assert out["span_s"]["hpb:run"] == pytest.approx(880 * NS)
    # self time: a span's seconds that no span inside it covers
    assert out["self_s"]["hpb:run"] == pytest.approx(20 * NS)
    assert out["self_s"]["bench:run"] == pytest.approx(20 * NS)
    assert out["self_s"]["hpb:bracket_replay"] == pytest.approx(20 * NS)
    assert out["self_s"]["hpb:fetch"] == pytest.approx(400 * NS)
    # idle: [0, 150), [550, 560), [580, 1000); under no hpb: span are
    # [0, 10), [90, 110) and [990, 1000)
    assert out["idle_s"] == pytest.approx(580 * NS)
    assert out["idle_unnamed_s"] == pytest.approx(40 * NS)
    # busy by phase, in self time: the loop keeps what its body leaves
    assert out["busy_s"] == pytest.approx(420 * NS)
    assert out["phase_s"] == pytest.approx(
        {"hpb.train": 200 * NS, "hpb.validate": 200 * NS, "unnamed": 20 * NS})
    assert out["phase_op_s"]["unnamed"] == pytest.approx({"copy.4": 20 * NS})
    assert out["phase_op_s"]["hpb.train"] == pytest.approx(
        {"while.1": 60 * NS, "fusion.2": 140 * NS})
    printed = json.loads(json.dumps(span_reduce.summary(out)))
    assert printed["phase_op_s"]["hpb.train"][0][0] == "fusion.2"


def test_operations_outside_every_module_event_join_by_name():
    """A trace that lost its ``XLA Modules`` events still tells the
    phases, as long as the programs agree on an instruction's phase."""
    out = span_reduce.reduce_spans([THREAD], OPS, {}, 1, PHASES)
    assert out["events"] == {"ops": 4, "modules": 0}
    assert out["phase_s"] == pytest.approx({
        "hpb.train": 200 * NS, "hpb.validate": 200 * NS, "hpb.promote": 20 * NS})
    clash = dict(PHASES, jit_other={"copy.4": "hpb.promote", "fusion.3": "hpb.sample"})
    out = span_reduce.reduce_spans([THREAD], OPS, {}, 1, clash)
    assert out["phase_s"] == pytest.approx({
        "hpb.train": 200 * NS, "unnamed": 200 * NS, "hpb.promote": 20 * NS})
    # inside a program the process does not know: not ours to name
    foreign = {"/device:TPU:0": [(140, 590, "jit_foreign")]}
    out = span_reduce.reduce_spans([THREAD], OPS, foreign, 1, PHASES)
    assert out["phase_s"] == pytest.approx({"unnamed": 420 * NS})


def test_each_new_metric_on_a_made_ctx():
    ctx = made_ctx(span_reduce.reduce_spans([THREAD], OPS, MODULES, 1, PHASES))
    step_flops = 3.0 * 2.0 * 16 * (8 * 8 + 8 * 8 + 8 * 4)
    expected = {
        "driver.construct_s": 80 * NS,
        "driver.dispatch_s": 80 * NS,
        "driver.fetch_wait_s": 400 * NS,
        # 5 evaluations a sweep; chunk_accounting and obs_fold not traced here
        "replay.span_s_per_keval": (300 + 80) * NS / 5 * 1000,
        "replay.configs_s_per_keval": 90 * NS / 5 * 1000,
        "replay.runs_s_per_keval": 190 * NS / 5 * 1000,
        "device.idle_unnamed_share": 100.0 * 40 / 580,
        "trainer.device_share": 100.0 * 400 / 420,
        # 4 lanes x 1 step + 1 lane x 2 more steps, over hpb.train alone
        "trainer.roofline_share": 100.0 * 6 * step_flops / (200 * NS) / 1e12,
        "promotion.device_share": 0.0,
        "model.fit_device_share": 0.0,
        "device.unnamed_share": 100.0 * 20 / 420,
    }
    assert sorted(expected) == sorted(NEW_METRICS)
    for name, value in expected.items():
        assert read(name, ctx) == pytest.approx(value), name


def test_two_sweeps_and_four_chips_are_per_sweep_and_per_chip():
    later = [(a + 2000, b + 2000, n) for a, b, n in THREAD]
    ops = {"/device:TPU:%d" % i: OPS["/device:TPU:0"] for i in range(4)}
    modules = {plane: MODULES["/device:TPU:0"] for plane in ops}
    out = span_reduce.reduce_spans([THREAD + later], ops, modules, 4, PHASES)
    assert out["sweeps"] == 2
    assert out["busy_s"] == pytest.approx(420 * NS)
    ctx = made_ctx(out)
    assert read("driver.fetch_wait_s", ctx) == pytest.approx(400 * NS)
    with pytest.raises(ValueError):
        span_reduce.reduce_spans([THREAD], OPS, MODULES, 4, PHASES)
    with pytest.raises(ValueError):
        span_reduce.reduce_spans([[s for s in THREAD if s[2].startswith("hpb:")]],
                                 OPS, MODULES, 1, PHASES)


def test_a_program_without_spans_or_map_reports_nothing(tmp_path):
    """The parent commit under this PR's benchmark files: every new metric
    leaves its place empty, and none raises."""
    path = str(tmp_path / "recorded.xplane.pb")
    with gzip.open(os.path.join(TINY, "recorded.xplane.pb.gz")) as src:
        with open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
    out = span_reduce.reduce_file(path, 1, phase_maps=None)
    assert out["sweeps"] == 2 and out["busy_s"] > 0
    assert not [n for n in out["span_s"] if n.startswith("hpb:")]
    assert out["phase_s"] is None
    for name in NEW_METRICS:
        assert read(name, made_ctx(out)) is None, name
    # an untraced run, and a traced one that left no file to read
    for name in NEW_METRICS:
        assert read(name, {"trace": None}) is None, name
    # spans without a map (a program between the two): the host's metrics
    # read, the device's phases do not
    half = made_ctx(span_reduce.reduce_spans([THREAD], OPS, MODULES, 1, None))
    assert read("driver.fetch_wait_s", half) == pytest.approx(400 * NS)
    assert read("device.idle_unnamed_share", half) == pytest.approx(100.0 * 40 / 580)
    for name in ("trainer.device_share", "trainer.roofline_share",
                 "promotion.device_share", "model.fit_device_share",
                 "device.unnamed_share"):
        assert read(name, half) is None, name
    # a configuration that trains no MLP has no step to count
    no_mlp = dict(made_ctx(span_reduce.reduce_spans(
        [THREAD], OPS, MODULES, 1, PHASES)), config={})
    assert read("trainer.roofline_share", no_mlp) is None


def test_the_program_offers_its_phase_maps(monkeypatch):
    maps = program_phases.phase_maps()
    assert isinstance(maps, dict)
    assert all(isinstance(m, dict) for m in maps.values())
    import hpbandster_tpu.optimizers as optimizers

    monkeypatch.delattr(optimizers, "sweep_phase_maps")
    assert program_phases.phase_maps() is None


def test_new_metrics_are_entered_for_both_cells():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    entered = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in NEW_METRICS:
        assert entered[name]["workloads"] == cells
        assert entered[name]["source"] == "device_trace"
        assert os.path.exists(os.path.join(run.HERE, "layer_metrics", name + ".py"))
    assert [m["name"] for m in bench["per_layer"]][-len(NEW_METRICS):] == NEW_METRICS
