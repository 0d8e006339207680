"""The fused sweep's own spans and device phase names (ISSUE 25).

Host side: every phase of ``FusedBOHB.run`` is one ``hpb:<name>`` region in
a profiler trace, nested by containment, and always one entry of the
chunk's ``run_stats`` row ``phase_s``. Device side: the program's
``jax.named_scope`` names are metadata only, and ``device_phase_map`` reads
them back off the compiled text by instruction name. Everything here runs on
the CPU at a tiny size: names, nesting and counts, never a time.
"""

import contextlib
import glob
import json
import re
import time

import numpy as np
import pytest

import jax

from hpbandster_tpu import obs
from hpbandster_tpu.obs.profile import device_phase_map, hlo_module_name
from hpbandster_tpu.obs.timeline import DEVICE_SCOPES, SPAN_PREFIX
from hpbandster_tpu.optimizers import FusedBOHB, sweep_phase_maps
from hpbandster_tpu.ops.sweep import plan_additions, pow2_capacities
from hpbandster_tpu.workloads.ensemble import make_mlp_ensemble
from hpbandster_tpu.workloads.mlp import MLPConfig, mlp_space
from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

import lane_names

#: span -> the span that encloses it (None: a root of the thread)
PARENT = {
    "construct": None,
    "run": None,
    "sweep_planning": "run",
    "sweep_setup": "run",
    "chunk_staging": "run",
    "compile_lookup": "run",
    "compile.trace_lower": "compile_lookup",
    "compile.compile": "compile_lookup",
    "dispatch": "run",
    "fetch": "run",
    "unstack": "run",
    "chunk_accounting": "run",
    "obs_fold": "run",
    "bracket_replay": "run",
    "replay.configs": "bracket_replay",
    "replay.runs": "bracket_replay",
    "result": "run",
}
#: what ``run`` and ``construct`` enclose directly: together, a sweep's wall
TOP_LEVEL = sorted(n for n, p in PARENT.items()
                   if p == "run" or n == "construct")


def branin_opt(seed=3, own_program=False, **kwargs):
    """``own_program``: an objective of a new identity, so that the sweep
    executable is in no cache of the process and has to be built."""
    eval_fn = branin_from_vector
    if own_program:
        eval_fn = lambda v, b: branin_from_vector(v, b)  # noqa: E731
    return FusedBOHB(
        configspace=branin_space(seed=seed), eval_fn=eval_fn,
        run_id="spans", min_budget=1, max_budget=9, eta=3, seed=seed, **kwargs)


@pytest.fixture
def compiled_here():
    """Compile, never load: an executable out of the persistent cache
    carries the metadata of the code that compiled it first, which may be a
    commit that had no scope (the cache's key leaves metadata out)."""
    with lane_names.compiled_here():
        yield


@pytest.fixture(scope="module")
def mlp_ensemble():
    return make_mlp_ensemble(
        MLPConfig(d_in=8, width=8, n_classes=4, n_train=64, n_val=32,
                  batch_size=16), data_seed=0)


def mlp_opt(ensemble, seed=3):
    return FusedBOHB(
        configspace=mlp_space(seed=seed), stateful_eval=ensemble,
        run_id="spans-mlp", min_budget=1, max_budget=9, eta=3, seed=seed)


# ------------------------------------------------------------- host spans
def traced_spans(tmp_path, body):
    """``[(start_ns, end_ns, name)]`` of the ``hpb:`` events one thread
    left in a profiler trace of ``body()``."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        body()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    threads = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [(e.start_ns, e.start_ns + e.duration_ns,
                       e.name[len(SPAN_PREFIX):])
                      for e in line.events if e.name.startswith(SPAN_PREFIX)]
            if events:
                threads.append(events)
    assert len(threads) == 1, "the fused driver's spans are one thread's"
    return threads[0]


def parents(events):
    """``{(name, parent name)}`` by containment, innermost parent."""
    found, open_spans = set(), []
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while open_spans and open_spans[-1][0] <= start:
            open_spans.pop()
        assert not open_spans or end <= open_spans[-1][0], (
            "%s straddles the end of %s" % (name, open_spans[-1][1]))
        found.add((name, open_spans[-1][1] if open_spans else None))
        open_spans.append((end, name))
    return found


@pytest.mark.parametrize("resident", [False, True])
def test_traced_run_leaves_every_span_nested_as_stated(tmp_path, resident):
    def body():
        # a program of its own: not in the process's cache, so the
        # compile's two halves are spans too
        opt = branin_opt(seed=11, own_program=True)
        opt.run(n_iterations=3, resident=resident)

    events = traced_spans(tmp_path, body)
    expected = {(n, p) for n, p in PARENT.items()
                if resident or n != "unstack"}
    assert parents(events) == expected
    # per bracket, never per evaluation
    names = [n for _, _, n in events]
    assert names.count("replay.configs") == names.count("replay.runs") == 3
    assert len(events) <= 200


def test_spans_cost_no_journal_and_no_profiler_to_measure():
    """No sink, no profiler session: ``phase_s`` is filled all the same,
    and no event is built."""
    assert not obs.get_bus().active
    opt = branin_opt()
    opt.run(n_iterations=2)
    assert set(opt.run_stats[0]["phase_s"]) >= set(TOP_LEVEL) - {"unstack"}


# ---------------------------------------------------------------- phase_s
@pytest.mark.parametrize("resident", [False, True])
def test_phase_s_has_every_phase_and_sums_to_the_wall(resident):
    # the trainer's sweep, not Branin's, and at a width that keeps the CPU
    # busy for tenths of a second, so that the interpreter's own
    # microseconds between two spans are no share of it (a second
    # constructor around one object no longer traces it: the wall of the
    # module's 8-wide ensemble is 7 ms, 0.4 ms of it between spans)
    mlp_ensemble = make_mlp_ensemble(
        MLPConfig(d_in=128, width=256, n_classes=4, n_train=512, n_val=512,
                  batch_size=128), data_seed=0)
    mlp_opt(mlp_ensemble).run(n_iterations=3, resident=resident)  # warm
    space = mlp_space(seed=5)
    t0 = time.perf_counter()
    opt = FusedBOHB(
        configspace=space, stateful_eval=mlp_ensemble, run_id="spans-mlp",
        min_budget=1, max_budget=9, eta=3, seed=5)
    t1 = time.perf_counter()
    opt.run(n_iterations=3, resident=resident)
    t2 = time.perf_counter()
    (row,) = opt.run_stats
    phase_s = row["phase_s"]
    # a warm sweep finds its executable: no compile.* children
    expected = set(PARENT) - {"compile.trace_lower", "compile.compile"}
    if not resident:
        expected.discard("unstack")
    assert set(phase_s) == expected
    assert all(v >= 0 for v in phase_s.values())
    wall = t2 - t0
    top = sum(phase_s[n] for n in TOP_LEVEL if n in phase_s)
    assert top == pytest.approx(wall, rel=0.05)
    assert phase_s["construct"] + phase_s["run"] == pytest.approx(wall, rel=0.05)
    # the inside agrees with the clocks the row already had
    assert phase_s["dispatch"] + phase_s["fetch"] == pytest.approx(
        row["execute_fetch_s"], rel=0.05, abs=2e-4)
    assert phase_s["replay.configs"] + phase_s["replay.runs"] <= (
        phase_s["bracket_replay"])


def test_overlapped_replay_lands_on_the_row_of_its_chunk():
    opt = branin_opt(seed=7)
    opt.run(n_iterations=4, chunk_brackets=2)
    first, last = opt.run_stats
    for row in (first, last):
        assert {"chunk_staging", "compile_lookup", "dispatch", "fetch",
                "chunk_accounting", "obs_fold", "bracket_replay",
                "replay.configs", "replay.runs"} <= set(row["phase_s"])
    # chunk 0's replay ran inside chunk 1's device window: chunk 1's row
    # says how long the window hid it, chunk 0's row owns the seconds
    assert last["replay_overlap_s"] == pytest.approx(
        first["phase_s"]["bracket_replay"], rel=0.05, abs=2e-4)
    assert "replay_overlap_s" not in first
    # construction and set-up ride the first row, the call's ends the last
    assert {"construct", "sweep_planning", "sweep_setup"} <= set(first["phase_s"])
    # whether the constructor traced the objective is a counter of the row,
    # not a span of its own: the module's objective was admitted before
    assert first["construct_traced"] in (0, 1) and last["construct_traced"] == 0
    assert not {"run", "result"} & set(first["phase_s"])
    assert {"run", "result"} <= set(last["phase_s"])
    assert "construct" not in last["phase_s"]
    # a second call on the same optimizer: its own rows, the old untouched
    before = json.dumps(opt.run_stats, sort_keys=True)
    opt.run(n_iterations=6, chunk_brackets=2)
    assert json.dumps(opt.run_stats[:2], sort_keys=True) == before
    assert {"sweep_planning", "run", "result"} <= set(
        opt.run_stats[2]["phase_s"])


def test_sidecar_and_journal_carry_the_breakdown(tmp_path):
    from hpbandster_tpu.core.result import json_result_logger

    records = []
    detach = obs.get_bus().subscribe(records.append)
    try:
        opt = branin_opt(
            seed=9, result_logger=json_result_logger(str(tmp_path), overwrite=True))
        opt.run(n_iterations=2)
    finally:
        detach()
    with open(tmp_path / "fused_timings.json") as fh:
        rows = json.load(fh)
    assert rows == opt.run_stats and "run" in rows[-1]["phase_s"]
    # a row restored from a checkpoint lacks the spans that closed later:
    # it is not written a second time
    stale = dict(opt.run_stats[0], phase_s={"fetch": 0.1})
    opt.run_stats[0] = stale
    opt._write_timings_sidecar()
    with open(tmp_path / "fused_timings.json") as fh:
        assert json.load(fh) == rows
    # every span of the call is a journal event of the sweep's trace, with
    # its phase; the per-evaluation records stay outside that trace
    spans = {e.name: e.fields for e in records if e.name in PARENT}
    assert set(spans) >= {"run", "dispatch", "fetch", "bracket_replay",
                          "replay.configs", "replay.runs", "result"}
    (chunk,) = [e.fields for e in records if e.name == "sweep_chunk"]
    assert {f["trace_id"] for n, f in spans.items()
            if not n.startswith("construct")} == {chunk["trace_id"]}
    assert all(f["phase"] in obs.PHASES and f["duration_s"] >= 0
               for f in spans.values())
    assert {"dispatch", "fetch", "chunk_staging"} <= set(chunk["phase_s"])
    jobs = [e.fields for e in records if e.name == obs.JOB_FINISHED]
    assert jobs and all("trace_id" not in f for f in jobs)
    # ... so the critical path of the sweep's journal sums to its wall
    from hpbandster_tpu.obs.journal import event_to_record

    cp = obs.critical_path([event_to_record(e) for e in records
                            if not e.name.startswith("construct")])
    assert cp["attributed_share"] >= 0.95


def test_incumbent_and_sharded_entries_use_the_same_names(mlp_ensemble):
    out = branin_opt(seed=13, own_program=True).run_incumbent(n_iterations=3)
    assert {"run", "sweep_planning", "chunk_staging", "compile_lookup",
            "compile.trace_lower", "compile.compile", "dispatch", "fetch",
            "result"} == set(out["phase_s"])
    assert set(out["phase_s"]) <= set(PARENT)

    from hpbandster_tpu.parallel import config_mesh
    from hpbandster_tpu.parallel.multihost import run_sharded_fused_sweep

    out = run_sharded_fused_sweep(
        None, mlp_space(seed=2), stateful_eval=mlp_ensemble, n_configs=16,
        n_brackets=2, min_budget=1, max_budget=9, eta=3, seed=2,
        mesh=config_mesh(jax.devices()[:4]), resident=True)
    # this ensemble's first sharded sweep: the program is built ahead of time
    assert {"run", "sweep_planning", "sweep_setup", "chunk_staging",
            "compile_lookup", "compile.trace_lower", "compile.compile",
            "dispatch", "fetch", "chunk_accounting",
            "result"} == set(out["phase_s"])


# ---------------------------------------------------------- device scopes
def instructions_with_op_name(text):
    return [m.group(1) for m in re.finditer(
        r'^\s+(?:ROOT )?%?([\w.\-]+) = .*op_name="', text, re.M)]


@pytest.mark.parametrize("case, used", [
    # static whole-sweep program of the stateful trainer, model on
    ("mlp", {"hpb.sample", "hpb.kde_fit", "hpb.kde_score", "hpb.train",
             "hpb.validate", "hpb.promote", "hpb.obs_update"}),
    # resident scanned program of a stateless objective, Pallas scorer
    ("branin", {"hpb.sample", "hpb.kde_fit", "hpb.kde_score", "hpb.train",
                "hpb.promote", "hpb.obs_update"}),
    # incumbent-only: the fold is the one scope the others never trace
    # (the observation buffers it never returns are dead code there)
    ("incumbent", {"hpb.sample", "hpb.train", "hpb.promote", "hpb.incumbent"}),
])
def test_device_phase_map_names_the_program(compiled_here, case, used):
    if case == "mlp":
        # an ensemble of its own: a new program for the process
        opt = mlp_opt(make_mlp_ensemble(
            MLPConfig(d_in=8, width=8, n_classes=4, n_train=64, n_val=32,
                      batch_size=16), data_seed=0))
        opt.run(n_iterations=3)
    elif case == "branin":
        opt = branin_opt(seed=17, own_program=True, use_pallas=True)
        opt.run(n_iterations=7, resident=True)
    else:
        opt = branin_opt(seed=19, own_program=True, min_points_in_model=2 ** 30)
        opt.run_incumbent(n_iterations=3)
    text = opt.last_executable.as_text()
    phases = device_phase_map(opt.last_executable)
    assert phases == device_phase_map(text)
    assert set(phases.values()) == used
    assert used <= set(DEVICE_SCOPES)
    with_name = instructions_with_op_name(text)
    named = sum(n in phases for n in with_name)
    assert named >= 0.9 * len(with_name), (named, len(with_name))


def test_sweep_phase_maps_joins_by_module_then_instruction(compiled_here):
    from hpbandster_tpu.optimizers.fused_bohb import _SWEEP_EXE_CACHE

    _SWEEP_EXE_CACHE.clear()
    assert sweep_phase_maps() == {}
    opt = branin_opt(seed=29, own_program=True)
    opt.run(n_iterations=2)
    one = device_phase_map(opt.last_executable)
    assert hlo_module_name(opt.last_executable) == "jit_hpb_sweep"
    assert sweep_phase_maps() == {"jit_hpb_sweep": one}
    # a second program of the same module name: an instruction name the two
    # give different phases is no longer told
    opt.run(n_iterations=5)
    two = device_phase_map(opt.last_executable)
    (merged,) = sweep_phase_maps().values()
    assert set(merged) == {n for n in set(one) | set(two)
                           if one.get(n, two.get(n)) == two.get(n, one.get(n))}
    assert all(merged[n] in (one.get(n), two.get(n)) for n in merged)
    assert 0 < len(merged) < len(set(one) | set(two))


def test_phase_map_inherits_through_nested_computations():
    text = """HloModule jit_f, is_scheduled=true

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/while/body/add"}
}

%fused_computation (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  ROOT %neg.3 = f32[4]{0} negate(%q)
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %while.2 = f32[4]{0} while(%x), condition=%cond, body=%body, metadata={op_name="jit(f)/vmap(hpb.train)/while"}
  %copy.4 = f32[4]{0} copy(%while.2)
  %sort.5 = f32[4]{0} sort(%copy.4), metadata={op_name="jit(f)/hpb.promote/hpb.not_a_scope/sort"}
  ROOT %fusion.7 = f32[4]{0} fusion(%sort.5), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/hpb.validate/neg"}
}
"""
    assert hlo_module_name(text) == "jit_f"
    assert device_phase_map(text) == {
        "while.2": "hpb.train", "p": "hpb.train", "add.1": "hpb.train",
        "sort.5": "hpb.promote", "fusion.7": "hpb.validate",
        "q": "hpb.validate", "neg.3": "hpb.validate",
    }
    with pytest.raises(ValueError):
        device_phase_map("HloModule nothing\n")


def lowered_text(opt, n_iterations, dynamic, resident):
    plans = [opt._plan(i) for i in range(n_iterations)]
    caps, args = None, (np.uint32(1),)
    if dynamic:
        caps = pow2_capacities(plan_additions(plans))
        d = int(opt.codec.kind.shape[0])
        args += ({b: np.zeros((c, d), np.float32) for b, c in caps.items()},
                 {b: np.full(c, np.inf, np.float32) for b, c in caps.items()},
                 {b: np.int32(0) for b in caps})
    fn = opt._sweep_driver(dynamic, resident=resident,
                           device_metrics=False).build(plans, caps)
    return fn.lower(*args).as_text()


@pytest.mark.parametrize("case", ["mlp-static", "mlp-resident", "branin-pallas"])
def test_scopes_are_metadata_only(monkeypatch, mlp_ensemble, case):
    """The lowered program (its text prints no locations) is byte for byte
    the one a program with no scope at all lowers to: nothing the compiler
    or the compile cache's key reads has changed."""
    if case == "branin-pallas":
        opt, shape = branin_opt(seed=23, use_pallas=True), (7, True, True)
    else:
        opt = mlp_opt(mlp_ensemble, seed=23)
        shape = (4, False, False) if case == "mlp-static" else (7, True, True)
    with_scopes = lowered_text(opt, *shape)
    entered = []

    def no_scope(name):
        entered.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax, "named_scope", no_scope)
    assert lowered_text(opt, *shape) == with_scopes
    assert set(entered) <= set(DEVICE_SCOPES) and "hpb.sample" in entered
    assert "hpb." not in with_scopes


# ------------------------------------------- one driver, three entry points
def own_objective():
    """An objective of a new identity: its programs are in no cache."""
    return lambda v, b: branin_from_vector(v, b)  # noqa: E731


def entry_call(entry, eval_fn, mesh):
    """One bracket of 9, 3, 1 through an entry point, dynamic counts on:
    ``(chunk rows, phase_s)``."""
    from hpbandster_tpu.parallel.multihost import run_sharded_fused_sweep

    if entry == "sharded":
        out = run_sharded_fused_sweep(
            eval_fn, branin_space(seed=3), n_configs=9, n_brackets=1,
            chunk_brackets=1, model=True, min_budget=1, max_budget=9, eta=3,
            mesh=mesh, seed=3)
        assert out["aligned_stage_counts"] == [9, 3, 1]
        return out["chunks"], out["phase_s"]
    opt = FusedBOHB(
        configspace=branin_space(seed=3), eval_fn=eval_fn, run_id="entries",
        min_budget=1, max_budget=9, eta=3, seed=3, mesh=mesh)
    if entry == "incumbent":
        out = opt.run_incumbent(n_iterations=1, resident=False)
        return [out], out["phase_s"]
    opt.run(n_iterations=1, dynamic_counts=True)
    return opt.run_stats, opt.run_stats[-1]["phase_s"]


@pytest.mark.parametrize("entry", ["run", "incumbent", "sharded"])
def test_every_entry_point_compiles_ahead_once(entry):
    from hpbandster_tpu.obs.runtime import get_compile_tracker
    from hpbandster_tpu.parallel import config_mesh

    eval_fn, mesh = own_objective(), config_mesh(jax.devices()[:1])
    compiles = lambda: get_compile_tracker().snapshot()["total_compiles"]  # noqa: E731
    before = compiles()
    rows, phase_s = entry_call(entry, eval_fn, mesh)
    assert [r["compile_cache_hit"] for r in rows] == [False]
    assert rows[0]["build_compile_s"] > 0
    assert {"compile.trace_lower", "compile.compile"} <= set(phase_s)
    assert phase_s["compile.trace_lower"] + phase_s["compile.compile"] <= (
        phase_s["compile_lookup"])
    assert compiles() == before + 1
    # the same call again: the executable is found, nothing is built
    rows, phase_s = entry_call(entry, eval_fn, mesh)
    assert [r["compile_cache_hit"] for r in rows] == [True]
    assert rows[0]["build_compile_s"] == 0.0
    assert not {"compile.trace_lower", "compile.compile"} & set(phase_s)
    assert compiles() == before + 1


@pytest.mark.parametrize("entry", ["run", "incumbent", "sharded"])
def test_a_build_adds_its_two_halves_to_the_gauges(entry):
    """``sweep.build.trace_lower_s`` + ``sweep.build.compile_s`` grow by what
    the row calls ``build_compile_s``, half by half the spans' seconds; a
    hit touches neither."""
    from hpbandster_tpu import obs
    from hpbandster_tpu.parallel import config_mesh

    def halves():
        gauges = obs.get_metrics().snapshot()["gauges"]
        return (gauges.get("sweep.build.trace_lower_s", 0.0),
                gauges.get("sweep.build.compile_s", 0.0))

    eval_fn, mesh = own_objective(), config_mesh(jax.devices()[:1])
    before = halves()
    rows, phase_s = entry_call(entry, eval_fn, mesh)
    built = halves()
    traced, compiled = built[0] - before[0], built[1] - before[1]
    assert traced > 0 and compiled > 0
    # the row rounds its seconds
    assert traced + compiled == pytest.approx(rows[0]["build_compile_s"], abs=1e-3)
    assert traced == pytest.approx(phase_s["compile.trace_lower"], rel=0.01, abs=1e-3)
    assert compiled == pytest.approx(phase_s["compile.compile"], rel=0.01, abs=1e-3)
    entry_call(entry, eval_fn, mesh)
    assert halves() == built


def test_entry_points_share_one_cache_and_never_an_entry():
    from hpbandster_tpu.optimizers.fused_bohb import _SWEEP_EXE_CACHE
    from hpbandster_tpu.parallel import config_mesh, multihost

    assert not hasattr(multihost, "_SHARDED_FN_CACHE")
    eval_fn, mesh = own_objective(), config_mesh(jax.devices()[:1])
    _SWEEP_EXE_CACHE.clear()
    for n, entry in enumerate(["sharded", "incumbent", "run"], start=1):
        # one objective, one space, one mesh, one bracket, one set of
        # capacities: what keys them apart is the entry point's own mode
        rows, _ = entry_call(entry, eval_fn, mesh)
        assert not rows[0]["compile_cache_hit"]
        assert len(_SWEEP_EXE_CACHE) == n


def test_sharded_entry_is_in_the_phase_maps(compiled_here):
    from hpbandster_tpu.optimizers.fused_bohb import _SWEEP_EXE_CACHE
    from hpbandster_tpu.parallel import config_mesh
    from hpbandster_tpu.parallel.multihost import run_sharded_fused_sweep

    _SWEEP_EXE_CACHE.clear()
    out = run_sharded_fused_sweep(
        own_objective(), branin_space(seed=0), n_configs=256,
        mesh=config_mesh(jax.devices()), seed=3)
    maps = sweep_phase_maps()
    assert list(maps) == [hlo_module_name(out["last_executable"])]
    (phases,) = maps.values()
    assert phases == device_phase_map(out["last_executable"])
    assert {"hpb.train", "hpb.promote"} <= set(phases.values())


# ------------------------------- the admission check, once an object (ISSUE 31)
def counted_objective(kind, loss="lanes"):
    """``(constructor keyword, Python calls)`` of a new evaluation object
    that takes a vector of any dimension: ``calls`` gains an entry whenever
    Python runs the objective, which after construction means a trace."""
    from hpbandster_tpu.ops.fused import LaneFacts, StatefulEval

    calls = []
    if kind == "stateful":
        def step_fn(state, vectors, budget, prev_budget):
            calls.append(budget)
            losses = (vectors ** 2).sum(-1) + state["p"]
            return state, losses if loss == "lanes" else losses.sum()

        return {"stateful_eval": StatefulEval(
            init_fn=lambda v: {"p": jax.numpy.zeros(v.shape[0])},
            step_fn=step_fn)}, calls

    def eval_fn(vector, budget):
        calls.append(budget)
        return (vector ** 2).sum() / budget

    if kind == "lane_facts":
        eval_fn.lane_facts = LaneFacts(bytes=1024)
    return {"eval_fn": eval_fn}, calls


def flat_space(d, seed=3):
    from hpbandster_tpu.space import (
        ConfigurationSpace,
        UniformFloatHyperparameter,
    )

    cs = ConfigurationSpace(seed=seed)
    for i in range(d):
        cs.add_hyperparameter(UniformFloatHyperparameter("x%d" % i, 0.0, 1.0))
    return cs


def construct(objective, d=2, min_budget=1, **kwargs):
    return FusedBOHB(configspace=flat_space(d), run_id="admit",
                     min_budget=min_budget, max_budget=9, eta=3, seed=3,
                     **objective, **kwargs)


@pytest.mark.parametrize("kind", ["stateful", "stateless"])
def test_a_second_constructor_does_not_trace_the_objective(kind):
    objective, calls = counted_objective(kind)
    first, second = construct(objective), construct(objective)
    assert calls == [1.0], "one trace, at the lowest budget, by the first"
    assert (first._construct_traced, second._construct_traced) == (1, 0)
    # the counter is the fact; the check has no span of its own (a lookup
    # on a hit), its seconds are the construction's
    for opt in (first, second):
        assert {"construct"} == set(opt._phase_carry)


@pytest.mark.parametrize("kind", ["stateful", "stateless"])
@pytest.mark.parametrize("other", [{"d": 3}, {"min_budget": 3}],
                         ids=["dimension", "min_budget"])
def test_another_dimension_or_lowest_budget_traces_again(kind, other):
    """The verdict is remembered under the three things the check reads."""
    objective, calls = counted_objective(kind)
    construct(objective)
    assert construct(objective, **other)._construct_traced == 1
    assert calls == [1.0, float(other.get("min_budget", 1))]
    for _ in range(2):
        assert construct(objective)._construct_traced == 0
        assert construct(objective, **other)._construct_traced == 0
    assert len(calls) == 2


def untraceable(vector, budget):
    return float(vector[0])  # concretizes a tracer


FAULTY = {
    "non-scalar": ({"eval_fn": lambda v, b: v},
                   "eval_fn must return a single SCALAR loss, got 1 output "
                   "leaves with shapes [(2,)] — reduce per-example losses "
                   "(e.g. .mean()) and drop aux outputs before returning"),
    "pytree": ({"eval_fn": lambda v, b: (v.sum(), {"aux": v})},
               "eval_fn must return a single SCALAR loss, got 2 output "
               "leaves with shapes [(), (2,)] — reduce per-example losses "
               "(e.g. .mean()) and drop aux outputs before returning"),
    "untraceable": ({"eval_fn": untraceable},
                    "eval_fn(config_vector f32[2], budget) failed under "
                    "abstract evaluation (jax.eval_shape) for this 2-dim "
                    "space: ConcretizationTypeError: "),
    "stateful-scalar": (counted_objective("stateful", loss="scalar")[0],
                        "stateful_eval.step_fn must return per-lane losses "
                        "f32[n], got shape ()"),
    "stateful-untraceable": (
        {"stateful_eval": counted_objective("stateful")[0]["stateful_eval"]
         ._replace(init_fn=lambda v: {"p": float(v[0, 0])})},
        "stateful_eval failed under abstract evaluation (init_fn + step_fn "
        "over f32[2, 2] vectors): ConcretizationTypeError: "),
}


@pytest.mark.parametrize("fault", sorted(FAULTY))
def test_a_faulty_objective_raises_the_same_words_every_time(fault):
    """A check that raised is never remembered."""
    from hpbandster_tpu.ops.sweep_driver import _ADMITTED

    objective, words = FAULTY[fault]
    (obj,) = objective.values()
    said = []
    for _ in range(3):
        with pytest.raises(ValueError) as raised:
            construct(objective)
        said.append(str(raised.value))
    assert said[0].startswith(words) and said[1:] == said[:1] * 2
    assert (obj, 2, 1.0) not in _ADMITTED


@pytest.mark.parametrize("kind", ["stateful", "stateless"])
def test_construct_traced_reads_one_then_zero(kind, tmp_path):
    """On the rows of two optimizers in turn around one object, and in the
    sidecar they share; only the first row of an optimizer can read 1."""
    from hpbandster_tpu.core.result import json_result_logger

    objective, calls = counted_objective(kind)
    logger, rows = json_result_logger(str(tmp_path), overwrite=True), []
    for _ in range(2):
        opt = construct(objective, result_logger=logger)
        opt.run(n_iterations=2, chunk_brackets=1)
        rows += opt.run_stats
    assert [r["construct_traced"] for r in rows] == [1, 0, 0, 0]
    assert [r["chunk_index"] for r in rows] == [0, 1, 0, 1]
    assert calls[0] == 1.0 and calls.count(1.0) == 2, (
        "the constructor's trace and the sweep program's, of one executable")
    with open(tmp_path / "fused_timings.json") as fh:
        assert [r["construct_traced"] for r in json.load(fh)] == [1, 0, 0, 0]
    # a later call on an optimizer carries nothing from its constructor
    opt.run(n_iterations=3, chunk_brackets=1)
    assert opt.run_stats[-1]["construct_traced"] == 0


def test_the_memo_is_bounded_as_the_executable_cache():
    from hpbandster_tpu.ops.sweep_driver import _ADMITTED, _SWEEP_EXE_CACHE

    assert _ADMITTED.maxsize == _SWEEP_EXE_CACHE.maxsize == 16
    oldest, calls = counted_objective("stateless")
    construct(oldest)
    for _ in range(_ADMITTED.maxsize):
        assert construct(counted_objective("stateless")[0])._construct_traced
        assert len(_ADMITTED) <= _SWEEP_EXE_CACHE.maxsize
    # sixteen later objects pushed the first out: it is checked again
    assert construct(oldest)._construct_traced == 1 and len(calls) == 2


def test_an_objective_with_lane_facts_is_never_traced_and_leaves_no_entry():
    from hpbandster_tpu.ops.sweep_driver import _ADMITTED

    objective, calls = counted_objective("lane_facts")
    before = len(_ADMITTED)
    assert [construct(objective)._construct_traced for _ in range(2)] == [0, 0]
    assert calls == [] and len(_ADMITTED) == before
    assert (objective["eval_fn"], 2, 1.0) not in _ADMITTED


def test_an_objective_that_cannot_be_hashed_is_checked_every_time():
    from hpbandster_tpu.ops.sweep_driver import _ADMITTED

    class Unhashable:
        calls = 0
        __hash__ = None

        def __call__(self, vector, budget):
            type(self).calls += 1
            return vector.sum()

    eval_fn, before = Unhashable(), len(_ADMITTED)
    with pytest.raises(TypeError):
        hash(eval_fn)
    assert [construct({"eval_fn": eval_fn})._construct_traced
            for _ in range(3)] == [1, 1, 1]
    assert Unhashable.calls == 3 and len(_ADMITTED) == before
