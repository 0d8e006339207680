"""The fused replay walks the device's stages, rung by rung (ISSUE 26), and
decodes a bracket's configurations in one piece (ISSUE 29).

``FusedBOHB._replay_runs`` writes every lane's ``Datum`` from the stage's
arrays and calls ``process_results()`` once a rung; it no longer polls
``get_next_run()`` / ``register_result()`` once an evaluation. The oracle
here is the loop it replaced, copied as it stood: the reference's own state
machine, driven one ``Job`` at a time. Everything is compared by content and
by counts on the CPU, never by a clock, and no device program is built
except by the whole-sweep tests at the end (Branin, budgets 1..9).

``FusedBOHB._replay_bracket`` decodes the first rung's vectors with one
``from_vectors`` and writes its ``Datum``s in one pass; its oracle is the
loop that replaced: ``dict(from_vector(row))`` and ``add_configuration``
once a configuration.
"""

import functools
import itertools
import json
import types

import numpy as np
import pytest

from hpbandster_tpu import obs
from hpbandster_tpu.core.iteration import BaseIteration
from hpbandster_tpu.core.job import Job
from hpbandster_tpu.core.result import json_result_logger
from hpbandster_tpu.optimizers import FusedBOHB
from hpbandster_tpu.optimizers import fused_bohb as fused_module
from hpbandster_tpu.obs.timeline import sweep_span
from hpbandster_tpu.optimizers.fused_bohb import _ReplayIteration
from hpbandster_tpu.space import (
    CategoricalHyperparameter,
    ConfigurationSpace,
    Constant,
    EqualsCondition,
    OrdinalHyperparameter,
    UniformFloatHyperparameter,
    UniformIntegerHyperparameter,
)
from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

JOB_INFO = {"fused_chunk": 0, "chunk_execute_s": 0.25, "chunk_evaluations": 7}


# ------------------------------------------------------------------ oracle
def oracle_replay_runs(self, it, stages, job_info):
    """``FusedBOHB._replay_runs`` as it stood before ISSUE 26."""
    loss_of = [dict(zip(map(int, idx), map(float, losses)))
               for idx, losses in stages]
    stage_no = 0
    while True:
        nr = it.get_next_run()
        if nr is None:
            if not it.process_results():
                break
            stage_no += 1
            continue
        config_id, cfg, budget = nr
        job = Job(config_id, config=cfg, budget=budget,
                  working_directory=self.working_directory)
        job.time_it("submitted")
        job.time_it("started")
        loss = loss_of[stage_no][config_id[2]]
        if not np.isnan(loss):
            job.result = {"loss": loss, "info": dict(job_info or {})}
        else:
            job.result = None
            job.exception = f"non-finite loss {loss!r} at budget {budget}"
        job.time_it("finished")
        obs.emit(
            obs.JOB_FAILED if job.exception is not None else obs.JOB_FINISHED,
            config_id=list(config_id), budget=budget,
            loss=float(loss) if np.isfinite(loss) else None,
        )
        if self.result_logger is not None:
            self.result_logger(job)
        it.register_result(job)
        self.total_evaluated += 1
    return 0


def oracle_replay_bracket(self, b_i, plan, out, stages, job_info, span, stat):
    """``FusedBOHB._replay_bracket`` as it stood before ISSUE 29, over the
    runs' loop as it stood before ISSUE 26 (which counted nothing)."""
    vectors = np.asarray(out.vectors)
    mb_mask = np.asarray(out.model_based)
    promotion_sets = [set(int(i) for i in idx) for idx, _ in stages[1:]]
    promotion_sets.append(set())
    obs.emit_bracket_created(
        b_i, plan.num_configs, plan.budgets,
        eta=self.eta, random_fraction=self.random_fraction,
    )
    it = _ReplayIteration(
        HPB_iter=b_i, num_configs=list(plan.num_configs),
        budgets=list(plan.budgets), config_sampler=None,
        promotion_sets=promotion_sets, result_logger=self.result_logger)
    self.iterations.append(it)
    for i in range(plan.num_configs[0]):
        cfg = dict(self.configspace.from_vector(vectors[i]))
        it.add_configuration(
            cfg,
            {"model_based_pick": bool(mb_mask[i]),
             "sample_reason": "fused_sweep", "fused_sweep": True})
    oracle_replay_runs(self, it, stages, job_info)


# ------------------------------------------------------------------ spaces
def mixed_space(seed=None, conditional=True):
    """Branin's two floats first (the whole-sweep tests evaluate them),
    then an integer, a categorical, a float that is active under one of
    its choices only, an ordinal and a constant."""
    cs = ConfigurationSpace(seed=seed)
    opt_hp = CategoricalHyperparameter("opt", ["sgd", "adam", "lion"])
    momentum = UniformFloatHyperparameter("momentum", 0.0, 0.99)
    cs.add_hyperparameters([
        UniformFloatHyperparameter("x", -5.0, 10.0),
        UniformFloatHyperparameter("y", 0.0, 15.0),
        UniformIntegerHyperparameter("width", 16, 512, log=True),
        opt_hp,
        momentum,
        OrdinalHyperparameter("batch", [16, 32, 64]),
        Constant("schedule", "cosine"),
    ])
    if conditional:
        cs.add_condition(EqualsCondition(momentum, opt_hp, "sgd"))
    return cs


def mixed_from_vector(vec, budget):
    # inactive dimensions reach an evaluation as 0.0
    return branin_from_vector(vec[:2], budget) + 0.1 * vec[4] + 0.01 * vec[2]


SPACES = {
    "branin": (branin_space, branin_from_vector),
    "mixed-flat": (functools.partial(mixed_space, conditional=False),
                   mixed_from_vector),
    "mixed-conditional": (mixed_space, mixed_from_vector),
}


# ---------------------------------------------------------------- fixtures
def device_output(space, n0, seed):
    """What a sweep's output holds for one bracket's replay: float32
    vectors in the codec's layout (a unit value or a choice index a
    dimension; an inactive dimension arrives as 0.0) and the model's picks."""
    rng = np.random.default_rng(seed)
    cols = [rng.random(n0) if hp.vartype == "c"
            else rng.integers(0, hp.num_choices, n0).astype(float)
            for hp in space.get_hyperparameters()]
    return types.SimpleNamespace(
        vectors=np.stack(cols, axis=1).astype(np.float32),
        model_based=rng.random(n0) < 0.5)


def replay_bracket(replay, opt, plan, out, stages, job_info, b_i=4):
    """One bracket through ``replay`` as ``run`` calls it; returns the
    iteration it appended and the row it counted on."""
    stat = {"replay_jobs_built": 0, "replay_configs_by_column": 0, "phase_s": {}}
    num_configs, budgets = plan
    replay(opt, b_i, types.SimpleNamespace(num_configs=num_configs, budgets=budgets),
           out, stages, job_info,
           functools.partial(sweep_span, totals=stat["phase_s"]), stat)
    return opt.iterations[-1], stat


def device_stages(num_configs, seed, nan_lanes=0, inf_lanes=0):
    """What ``_unpack_stages`` hands the replay: per rung the lane indices
    and their float32 losses, the promoted lanes in rank order (best
    first, so not ascending), crashed lanes (NaN) never promoted."""
    rng = np.random.default_rng(seed)
    idx = np.arange(num_configs[0], dtype=np.int32)
    stages = []
    for r, n in enumerate(num_configs):
        losses = rng.standard_normal(n).astype(np.float32)
        if r == 0:
            bad = rng.permutation(n)
            losses[bad[:nan_lanes]] = np.nan
            losses[bad[nan_lanes:nan_lanes + inf_lanes]] = np.inf
            losses[bad[nan_lanes + inf_lanes:nan_lanes + 2 * inf_lanes]] = -np.inf
        stages.append((idx, losses))
        if r + 1 < len(num_configs):
            rank = np.argsort(np.where(np.isnan(losses), np.inf, losses),
                              kind="stable")
            idx = idx[rank[:num_configs[r + 1]]]
    return stages


class RecordingLogger:
    """A result logger that keeps what it was shown."""

    def __init__(self):
        self.configs, self.jobs = [], []

    def new_config(self, config_id, config, config_info):
        self.configs.append((config_id, config, config_info,
                             [type(v) for v in config.values()]))

    def __call__(self, job):
        self.jobs.append((job.id, dict(job.kwargs), job.result, job.exception,
                          list(job.timestamps), list(job.mono)))


def optimizer(result_logger=None, space="branin", **kwargs):
    space_fn, eval_fn = SPACES[space]
    return FusedBOHB(
        configspace=space_fn(seed=3), eval_fn=eval_fn,
        run_id="replay", min_budget=1, max_budget=9, eta=3, seed=3,
        result_logger=result_logger, **kwargs)


def bracket(opt, num_configs, budgets, stages, b_i=4):
    """A ``_ReplayIteration`` as ``_replay_bracket`` leaves it before the
    runs: every lane added at stage 0, promotion sets from the stages."""
    promotion_sets = [set(int(i) for i in idx) for idx, _ in stages[1:]]
    promotion_sets.append(set())
    it = _ReplayIteration(
        HPB_iter=b_i, num_configs=list(num_configs), budgets=list(budgets),
        config_sampler=None, promotion_sets=promotion_sets,
        result_logger=opt.result_logger)
    for lane in range(num_configs[0]):
        it.add_configuration({"x": float(lane)}, {"fused_sweep": True})
    return it


@pytest.fixture
def ticking_clock(monkeypatch):
    """``time.time`` of the replay and of ``Job`` counts up by one a read:
    stamps then order the runs exactly, and no wall clock is compared."""
    ticks = itertools.count(1)
    fake = types.SimpleNamespace(
        time=lambda: float(next(ticks)),
        monotonic=fused_module.time.monotonic,
        perf_counter=fused_module.time.perf_counter)
    monkeypatch.setattr(fused_module, "time", fake)
    monkeypatch.setattr("hpbandster_tpu.core.job.time", fake)
    return ticks


@pytest.fixture
def journal():
    """Every event of the default bus, while the test runs."""
    events = []
    detach = obs.get_bus().subscribe(events.append)
    yield events
    detach()


def records(events):
    """Journal records by content: a promotion record's ``costs`` are wall
    spans of the stamps, the one field a clock decides; the replay's own
    two spans are events the oracle never opened."""
    return [(ev.name, {k: v for k, v in ev.fields.items() if k != "costs"})
            for ev in events if not ev.name.startswith("replay.")]


def bracket_state(it):
    """Every field of every ``Datum`` but the stamps' values, and the
    bracket's own counters."""
    return {
        "data": {
            cid: (d.config, list(d.config),
                  [type(v) for v in d.config.values()], type(d.config),
                  d.config_info, type(d.config_info.get("model_based_pick")),
                  d.results, d.exceptions, d.infos, d.status, d.budget,
                  {b: list(ts) for b, ts in d.time_stamps.items()})
            for cid, d in it.data.items()
        },
        "order": list(it.data),
        "actual_num_configs": it.actual_num_configs,
        "stage": it.stage,
        "is_finished": it.is_finished,
        "num_running": it.num_running,
    }


def runs_by_finished(it):
    """The runs in the order ``Result.get_incumbent_trajectory`` sorts them
    by, and whether each run's three stamps are in order."""
    stamped = [(ts["finished"], cid, b, ts["submitted"] <= ts["started"] <= ts["finished"])
               for cid, d in it.data.items() for b, ts in d.time_stamps.items()]
    assert all(ok for *_, ok in stamped)
    finished = [t for t, *_ in stamped]
    assert len(set(finished)) == len(finished)
    return [(cid, b) for _, cid, b, _ in sorted(stamped)]


#: eta 3, budgets 1..2187: the benchmark cells' deepest and narrowest brackets
DEEP = ((2187, 729, 243, 81, 27, 9, 3, 1),
        (1.0, 3.0, 9.0, 27.0, 81.0, 243.0, 729.0, 2187.0))
ONE_RUNG = ((8,), (2187.0,))
SMALL = ((27, 9, 3, 1), (1.0, 3.0, 9.0, 27.0))

CASES = {
    "deepest-2187-lanes": dict(plan=DEEP, job_info=JOB_INFO),
    "one-rung-8-lanes": dict(plan=ONE_RUNG, job_info=JOB_INFO),
    "nan-losses-crashed": dict(plan=SMALL, job_info=JOB_INFO, nan_lanes=5),
    "inf-losses-kept": dict(plan=SMALL, job_info=JOB_INFO, inf_lanes=2),
    "nan-and-inf-no-job-info": dict(plan=SMALL, job_info=None, nan_lanes=3,
                                    inf_lanes=1),
    "job-info-none": dict(plan=SMALL, job_info=None),
    "integer-categorical-condition": dict(plan=SMALL, job_info=JOB_INFO,
                                          space="mixed-conditional", nan_lanes=2),
    "integer-categorical-no-condition": dict(plan=SMALL, job_info=JOB_INFO,
                                             space="mixed-flat"),
}


# ------------------------------------------------------------------- tests
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("observed", [False, True], ids=["bare", "observed"])
def test_replay_equals_the_state_machine(case, observed, ticking_clock):
    """Field by field over every ``Datum`` (its configuration's keys, their
    order and the values' types too), the bracket's counters and the order
    of the runs' ``finished`` stamps: one decode of the bracket and the
    stage-by-stage replay leave what the reference's own loops leave, with
    a listener and with none."""
    spec = CASES[case]
    (num_configs, budgets), job_info = spec["plan"], spec["job_info"]
    space = spec.get("space", "branin")
    stages = device_stages(num_configs, seed=11,
                           nan_lanes=spec.get("nan_lanes", 0),
                           inf_lanes=spec.get("inf_lanes", 0))
    out = device_output(SPACES[space][0](), num_configs[0], seed=11)
    sides = {}
    for side, replay in (("oracle", oracle_replay_bracket),
                         ("change", FusedBOHB._replay_bracket)):
        opt = optimizer(RecordingLogger() if observed else None, space=space)
        it, stat = replay_bracket(replay, opt, spec["plan"], out, stages, job_info)
        sides[side] = (opt, it, stat)
    (o_opt, o_it, _), (c_opt, c_it, stat) = sides["oracle"], sides["change"]
    built = stat["replay_jobs_built"]
    assert stat["replay_configs_by_column"] == (
        0 if space == "mixed-conditional" else num_configs[0])
    if space == "mixed-conditional":
        active = ["momentum" in d.config for d in c_it.data.values()]
        assert any(active) and not all(active)

    evaluations = sum(num_configs)
    assert c_opt.total_evaluated == o_opt.total_evaluated == evaluations
    assert built == (evaluations if observed else 0)
    assert bracket_state(c_it) == bracket_state(o_it)
    assert c_it.is_finished and c_it.stage == len(num_configs) - 1
    assert runs_by_finished(c_it) == runs_by_finished(o_it)
    # a run's info is its own dict: a caller may add to one run's record
    infos = [info for d in c_it.data.values() for info in d.infos.values()]
    assert len({id(info) for info in infos}) == len(infos)
    nan = spec.get("nan_lanes", 0)
    crashed = [d for d in c_it.data.values() if d.results[budgets[0]] is None]
    assert len(crashed) == nan
    assert all(list(d.results) == [budgets[0]] and not d.infos
               and "non-finite loss nan at budget" in d.exceptions[budgets[0]]
               for d in crashed)
    if observed:
        assert c_opt.result_logger.jobs == o_opt.result_logger.jobs
        assert len(c_opt.result_logger.jobs) == evaluations
        assert c_opt.result_logger.configs == o_opt.result_logger.configs
        assert len(c_opt.result_logger.configs) == num_configs[0]


@pytest.mark.parametrize("case", ["deepest-2187-lanes", "nan-and-inf-no-job-info",
                                  "integer-categorical-condition"])
@pytest.mark.parametrize("listener", ["sink", "logger", "sink+logger"])
def test_listener_gets_the_oracles_records_in_order(case, listener, ticking_clock):
    """With a sink or a result logger attached every configuration is
    announced and every evaluation is a ``Job`` again: the journal's
    records and the logger's calls equal the oracle's, the bracket's plan,
    then its configurations in ascending id, then a rung's results before
    the rung's promotion records."""
    spec = CASES[case]
    (num_configs, budgets), job_info = spec["plan"], spec["job_info"]
    space = spec.get("space", "branin")
    stages = device_stages(num_configs, seed=5,
                           nan_lanes=spec.get("nan_lanes", 0),
                           inf_lanes=spec.get("inf_lanes", 0))
    out = device_output(SPACES[space][0](), num_configs[0], seed=5)
    seen = {}
    for side, replay in (("oracle", oracle_replay_bracket),
                         ("change", FusedBOHB._replay_bracket)):
        opt = optimizer(RecordingLogger() if "logger" in listener else None,
                        space=space)
        events = []
        detach = (obs.get_bus().subscribe(events.append)
                  if "sink" in listener else lambda: None)
        try:
            _, stat = replay_bracket(replay, opt, spec["plan"], out, stages, job_info)
        finally:
            detach()
        logger = opt.result_logger
        seen[side] = (records(events), logger and logger.jobs,
                      logger and logger.configs, stat["replay_jobs_built"])
    (o_records, o_jobs, o_configs, _) = seen["oracle"]
    (c_records, c_jobs, c_configs, built) = seen["change"]
    assert built == sum(num_configs)
    assert c_records == o_records
    assert c_jobs == o_jobs
    assert c_configs == o_configs
    if "logger" in listener:
        assert [cid for cid, *_ in c_configs] == [
            (4, 0, i) for i in range(num_configs[0])]
    if "sink" in listener:
        results = [name in (obs.JOB_FINISHED, obs.JOB_FAILED) for name, _ in c_records
                   if name in (obs.JOB_FINISHED, obs.JOB_FAILED,
                               "bracket_promotion")]
        # n results, then the rung's promotion record, rung after rung
        runs = [sum(1 for _ in g) for is_result, g in itertools.groupby(results)
                if is_result]
        assert runs == list(num_configs)
        failed = sum(name == obs.JOB_FAILED for name, _ in c_records)
        assert failed == spec.get("nan_lanes", 0)
        assert [name for name, _ in c_records[:num_configs[0] + 1]] == (
            ["bracket_created"] + ["config_sampled"] * num_configs[0])
        assert [f["config_id"] for _, f in c_records[1:num_configs[0] + 1]] == [
            [4, 0, i] for i in range(num_configs[0])]


@pytest.mark.parametrize("plan", [DEEP, ONE_RUNG], ids=["deepest", "one-rung"])
def test_nobody_listening_builds_no_job_and_polls_nothing(plan, monkeypatch):
    """By counts: no ``Job``, no ``get_next_run``, no ``register_result``,
    and ``process_results`` once a rung."""
    num_configs, budgets = plan
    stages = device_stages(num_configs, seed=2)
    opt = optimizer()
    it = bracket(opt, num_configs, budgets, stages)

    def never(*args, **kwargs):
        raise AssertionError("the replay polled the state machine")

    calls = []
    process_results = BaseIteration.process_results

    def counted(self):
        calls.append(self.stage)
        return process_results(self)

    monkeypatch.setattr(BaseIteration, "get_next_run", never)
    monkeypatch.setattr(BaseIteration, "register_result", never)
    monkeypatch.setattr(BaseIteration, "process_results", counted)
    monkeypatch.setattr(fused_module, "Job", never)
    assert not obs.get_bus().active
    assert opt._replay_runs(it, stages, JOB_INFO) == 0
    assert calls == list(range(len(num_configs)))
    assert it.is_finished and opt.total_evaluated == sum(num_configs)


@pytest.mark.parametrize("space", ["branin", "mixed-flat"])
def test_nobody_listening_decodes_by_column_and_announces_nothing(space, monkeypatch):
    """A space without a condition, no logger and no sink: the bracket's
    configurations never pass through ``from_vector`` or
    ``add_configuration``, and nothing is announced to anyone."""
    num_configs, budgets = DEEP
    stages = device_stages(num_configs, seed=2)
    opt = optimizer(space=space)
    out = device_output(opt.configspace, num_configs[0], seed=2)
    want = [dict(opt.configspace.from_vector(v)) for v in out.vectors]

    def never(*args, **kwargs):
        raise AssertionError("the replay decoded or announced one at a time")

    monkeypatch.setattr(ConfigurationSpace, "from_vector", never)
    monkeypatch.setattr(BaseIteration, "add_configuration", never)
    monkeypatch.setattr(json_result_logger, "new_config", never)
    monkeypatch.setattr(obs, "emit_config_sampled", never)
    assert not obs.get_bus().active
    it, stat = replay_bracket(FusedBOHB._replay_bracket, opt, DEEP, out, stages,
                              JOB_INFO)
    assert stat["replay_configs_by_column"] == num_configs[0] == len(want)
    assert stat["replay_jobs_built"] == 0
    assert [it.data[(4, 0, i)].config for i in range(num_configs[0])] == want
    assert [d.config_info["model_based_pick"] for d in it.data.values()] == (
        out.model_based.tolist())
    assert it.actual_num_configs[0] == num_configs[0] and it.is_finished


@pytest.mark.parametrize("fault", ["stage-full", "more-than-the-stage-holds",
                                   "finished"])
def test_a_full_stage_or_a_finished_bracket_still_raises(fault):
    """``add_configuration``'s checks, made once for the bracket."""
    it = _ReplayIteration(HPB_iter=0, num_configs=[3, 1], budgets=[1.0, 3.0],
                          config_sampler=None, promotion_sets=[{0}, set()])
    configs = [{"x": float(i)} for i in range(4)]
    infos = [{"fused_sweep": True} for _ in configs]
    if fault == "stage-full":
        it.add_configurations(configs[:3], infos[:3])
        rest, match = (configs[3:], infos[3:]), "stage 0 of iteration 0 is already full"
    elif fault == "more-than-the-stage-holds":
        rest, match = (configs, infos), "stage 0 of iteration 0 is already full"
    else:
        it.is_finished = True
        rest, match = (configs[:1], infos[:1]), "iteration is finished"
    before = dict(it.data)
    with pytest.raises(RuntimeError, match=match):
        it.add_configurations(*rest)
    assert it.data == before
    if fault != "more-than-the-stage-holds":
        # one at a time raises the same; four into an empty stage of three
        # would have raised at the fourth, after writing three
        with pytest.raises(RuntimeError, match=match):
            it.add_configuration(*(part[0] for part in rest))


@pytest.mark.parametrize("fault", ["lane-not-in-rung", "rung-short"])
def test_stages_that_contradict_the_bracket_raise(fault):
    """The device is authoritative, but a lane it reports in a rung the
    bracket never promoted it to is a fault to stop at, not to record."""
    num_configs, budgets = SMALL
    stages = device_stages(num_configs, seed=1)
    opt = optimizer()
    it = bracket(opt, num_configs, budgets, stages)
    if fault == "lane-not-in-rung":
        idx, losses = stages[1]
        outsider = next(i for i in range(num_configs[0]) if i not in set(idx))
        stages[1] = (np.concatenate([idx[:-1], [outsider]]).astype(idx.dtype),
                     losses)
        match = "the bracket holds it"
    else:
        stages[0] = (stages[0][0][:-1], stages[0][1][:-1])
        match = "did not advance past rung 0"
    with pytest.raises(RuntimeError, match=match):
        opt._replay_runs(it, stages, None)


# ------------------------------------------------- whole sweeps (Branin, CPU)
def sweep(tmp_path=None, sink=False, space="branin"):
    """One three-bracket sweep; returns (optimizer, result, journal)."""
    logger = json_result_logger(str(tmp_path), overwrite=True) if tmp_path else None
    opt = optimizer(logger, space=space)
    events = []
    detach = obs.get_bus().subscribe(events.append) if sink else (lambda: None)
    try:
        result = opt.run(n_iterations=3)
    finally:
        detach()
    return opt, result, events


def result_content(result):
    """Every ``Datum`` of a ``Result`` but the clock's part: the stamps'
    values and the chunk's seconds in a run's info."""
    return {
        cid: (d.config, list(d.config), [type(v) for v in d.config.values()],
              d.config_info, d.results, d.exceptions,
              {b: {k: v for k, v in info.items() if not k.endswith("_s")}
               for b, info in d.infos.items()},
              d.status, d.budget,
              {b: list(ts) for b, ts in d.time_stamps.items()})
        for cid, d in result.data.items()}


@pytest.mark.parametrize("listener", ["nobody", "sink", "logger"])
def test_run_stats_count_the_jobs_built(listener, tmp_path):
    """``replay_jobs_built`` on the chunk's row: 0 with nobody listening,
    the row's evaluations under a sink or a logger, and in the sidecar."""
    opt, result, _ = sweep(tmp_path if listener == "logger" else None,
                           sink=listener == "sink")
    (row,) = opt.run_stats
    assert row["evaluations"] == opt.total_evaluated == len(result.get_all_runs())
    assert row["replay_jobs_built"] == (0 if listener == "nobody"
                                        else row["evaluations"])
    assert {"replay.configs", "replay.runs", "bracket_replay"} <= set(row["phase_s"])
    if listener == "logger":
        (on_disk,) = json.load(open(tmp_path / "fused_timings.json"))
        assert on_disk["replay_jobs_built"] == row["evaluations"]
        assert len(open(tmp_path / "results.json").readlines()) == row["evaluations"]


@pytest.mark.parametrize("space", ["branin", "mixed-flat", "mixed-conditional"])
def test_run_stats_count_the_configurations_decoded_by_column(space, tmp_path):
    """``replay_configs_by_column`` on the chunk's row and in the sidecar:
    the brackets' first-rung total for a space without a condition, 0 for
    one with (every configuration then went through ``from_vector``)."""
    opt, result, _ = sweep(tmp_path, space=space)
    (row,) = opt.run_stats
    first_rungs = sum(it.num_configs[0] for it in opt.iterations)
    assert first_rungs == len(result.get_id2config_mapping()) > 0
    assert row["replay_configs_by_column"] == (
        0 if space == "mixed-conditional" else first_rungs)
    (on_disk,) = json.load(open(tmp_path / "fused_timings.json"))
    assert on_disk["replay_configs_by_column"] == row["replay_configs_by_column"]
    assert len(open(tmp_path / "configs.json").readlines()) == first_rungs
    if space != "branin":
        configs = [e["config"] for e in result.get_id2config_mapping().values()]
        assert {type(c["width"]) for c in configs} == {int}
        assert {c["opt"] for c in configs} <= {"sgd", "adam", "lion"}
        assert all(("momentum" in c) == (c["opt"] == "sgd") for c in configs) == (
            space == "mixed-conditional")


def test_chunked_sweep_counts_on_the_row_it_replays(journal):
    """A chunk's replay may run inside the next chunk's device window: its
    count still lands on its own row."""
    opt = optimizer()
    opt.run(n_iterations=3, chunk_brackets=1)
    assert [r["replay_jobs_built"] for r in opt.run_stats] == [
        r["evaluations"] for r in opt.run_stats]
    assert sum(r["evaluations"] for r in opt.run_stats) == opt.total_evaluated
    assert [r["replay_configs_by_column"] for r in opt.run_stats] == [
        it.num_configs[0] for it in opt.iterations]


@pytest.mark.parametrize("space", ["branin", "mixed-flat", "mixed-conditional"])
def test_a_sweeps_journal_and_result_are_the_oracles(space, monkeypatch):
    """A whole fused sweep under a sink: record for record, and ``Datum``
    for ``Datum``, what the loops it replaced leave."""
    with monkeypatch.context() as patch:
        patch.setattr(FusedBOHB, "_replay_bracket", oracle_replay_bracket)
        o_opt, o_result, o_events = sweep(sink=True, space=space)
    c_opt, c_result, c_events = sweep(sink=True, space=space)
    quiet_opt, quiet_result, _ = sweep(space=space)

    def content(events):
        keep = (obs.JOB_FINISHED, obs.JOB_FAILED, "config_sampled",
                "bracket_created", "bracket_promotion", "promotion_decision")
        return [r for r in records(events) if r[0] in keep]

    def names(events):
        return [ev.name for ev in events if not ev.name.startswith("replay.")]

    assert content(c_events) == content(o_events)
    assert names(c_events) == names(o_events)
    assert c_result.get_id2config_mapping() == o_result.get_id2config_mapping()
    assert result_content(c_result) == result_content(o_result)
    assert result_content(quiet_result) == result_content(o_result)
    assert (c_result.get_incumbent_trajectory()["config_ids"]
            == o_result.get_incumbent_trajectory()["config_ids"]
            == quiet_result.get_incumbent_trajectory()["config_ids"])
    assert c_opt.total_evaluated == o_opt.total_evaluated == quiet_opt.total_evaluated
