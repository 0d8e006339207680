"""Promotion-rule subsystem (hpbandster_tpu/promote, docs/promotion.md).

Coverage map:

* unit — ASHA promotion mechanics driven directly on the iteration
  (eager top-1/eta, promotions-before-samples dispatch order, crashed
  configs never promoted, finalize statuses);
* unit — Pareto / learning-curve-early-stop promotion masks on
  hand-built rungs;
* registry — name resolution, BOHB(promotion_rule=...) wiring,
  SweepSpec validation;
* audit — straggler ledger -> ``promotion_decision.straggler_observed``,
  the labeled ``bracket_promotions`` Prometheus family (hostile-name
  escaping round trip, mirroring the serve tenant family test);
* e2e over real sockets — ASHA parity with the synchronous rule on a
  straggler-free run (acceptance: same final incumbent, same seed), and
  liveness under one injected straggler (acceptance: sibling promotions
  proceed, barrier stall ~ 0, exactly-once lineage stays duplicate-free);
* replay — deterministic byte-identical re-scoring of recorded journals
  under every rule.
"""

import json
import threading
import time

import numpy as np
import pytest

from hpbandster_tpu import obs
from hpbandster_tpu.core.iteration import Status
from hpbandster_tpu.core.job import Job
from hpbandster_tpu.core.nameserver import NameServer
from hpbandster_tpu.core.worker import Worker
from hpbandster_tpu.optimizers import BOHB
from hpbandster_tpu.parallel.dispatcher import Dispatcher
from hpbandster_tpu.promote import RULE_NAMES, resolve_rule
from hpbandster_tpu.promote.asha import ASHAIteration
from hpbandster_tpu.promote.earlystop import LCEarlyStopIteration
from hpbandster_tpu.promote.pareto import ParetoIteration
from hpbandster_tpu.promote.replay import (
    format_replay,
    promotion_waits,
    replay_records,
    worker_utilization,
)
from hpbandster_tpu.space import ConfigurationSpace
from hpbandster_tpu.space import UniformFloatHyperparameter


# ------------------------------------------------------------ unit helpers
def sampler_factory():
    counter = {"n": 0}

    def sampler(budget):
        counter["n"] += 1
        return {"x": float(counter["n"])}, {}

    return sampler, counter


def finish(it, config_id, budget, loss=None, exception=None, cost=None):
    job = Job(config_id, config=it.data[config_id].config, budget=budget)
    job.time_it("submitted").time_it("started").time_it("finished")
    if exception is None:
        info = {"cost": cost} if cost is not None else {}
        job.result = {"loss": loss, "info": info}
    else:
        job.result = None
        job.exception = exception
    it.register_result(job)
    it.process_results()


class TestASHAIterationUnit:
    def test_promotes_on_partial_rung_no_barrier(self):
        it = ASHAIteration(0, [9, 3, 1], [1.0, 3.0, 9.0],
                           sampler_factory()[0], eta=3)
        runs = [it.get_next_run() for _ in range(3)]
        assert all(r[2] == 1.0 for r in runs)
        finish(it, runs[0][0], 1.0, 3.0)
        finish(it, runs[1][0], 1.0, 1.0)
        # 2 of 9 done: floor(2/3) = 0, nothing promotable yet
        assert not any(
            d.status == Status.QUEUED and d.budget == 3.0
            for d in it.data.values()
        )
        finish(it, runs[2][0], 1.0, 4.0)
        # 3 done: floor(3/3) = 1 — the best of the COMPLETED subset
        # promotes now, six rung-0 evaluations still outstanding
        queued = [
            cid for cid, d in it.data.items()
            if d.status == Status.QUEUED and d.budget == 3.0
        ]
        assert queued == [runs[1][0]]

    def test_promotion_dispatches_before_fresh_samples(self):
        sampler, counter = sampler_factory()
        it = ASHAIteration(0, [9, 3, 1], [1.0, 3.0, 9.0], sampler, eta=3)
        runs = [it.get_next_run() for _ in range(3)]
        for r, loss in zip(runs, [3.0, 1.0, 4.0]):
            finish(it, r[0], 1.0, loss)
        sampled_before = counter["n"]
        nxt = it.get_next_run()
        # the promoted config's budget-3 job, not a fresh rung-0 sample
        assert nxt[0] == runs[1][0] and nxt[2] == 3.0
        assert counter["n"] == sampled_before

    def test_crashed_configs_never_promote_and_finalize_statuses(self):
        it = ASHAIteration(0, [3, 1], [1.0, 3.0], sampler_factory()[0],
                           eta=3)
        runs = [it.get_next_run() for _ in range(3)]
        finish(it, runs[0][0], 1.0, exception="boom")
        finish(it, runs[1][0], 1.0, 0.5)
        finish(it, runs[2][0], 1.0, 0.7)
        # crashed config ranks last: the finite-loss winner promoted
        promoted = [
            cid for cid, d in it.data.items() if d.budget == 3.0
        ]
        assert promoted == [runs[1][0]]
        nxt = it.get_next_run()
        finish(it, nxt[0], 3.0, 0.4)
        assert it.is_finished
        statuses = {cid: d.status for cid, d in it.data.items()}
        assert statuses[runs[0][0]] == Status.CRASHED
        assert statuses[runs[1][0]] == Status.COMPLETED
        assert statuses[runs[2][0]] == Status.TERMINATED

    def test_full_rung_promotion_set_contains_sync_topk(self):
        # zero stragglers, sequential completion: after the rung fully
        # completes, every sync-rule survivor has been promoted
        it = ASHAIteration(0, [9, 3, 1], [1.0, 3.0, 9.0],
                           sampler_factory()[0], eta=3)
        losses = [5.0, 2.0, 8.0, 1.0, 9.0, 3.0, 7.0, 4.0, 6.0]
        runs = [it.get_next_run() for _ in range(9)]
        for r, loss in zip(runs, losses):
            finish(it, r[0], 1.0, loss)
        promoted = {
            cid for cid, d in it.data.items() if d.budget == 3.0
        }
        sync_top3 = {
            r[0] for r, l in zip(runs, losses)
            if l in sorted(losses)[:3]
        }
        assert sync_top3 <= promoted

    def test_eta_derived_from_budget_ladder(self):
        it = ASHAIteration(0, [9, 3, 1], [1.0, 3.0, 9.0],
                           sampler_factory()[0])
        assert it.eta == pytest.approx(3.0)


class TestParetoIterationUnit:
    def test_hand_built_front_promotes_pareto_best(self):
        # (loss, cost): a dominates b; c is on the front via cheap cost
        costs = {1.0: 1.0, 2.0: 4.0, 3.0: 0.1, 4.0: 5.0}

        def cost_fn(datum, budget):
            return costs[datum.config["x"]]

        it = ParetoIteration(
            0, [4, 2, 1], [1.0, 3.0, 9.0], sampler_factory()[0],
            cost_fn=cost_fn,
        )
        runs = [it.get_next_run() for _ in range(4)]
        # x=1: loss 0.2/cost 1.0 (front), x=2: loss 0.3/cost 4.0
        # (dominated by x=1), x=3: loss 0.9/cost 0.1 (front, cheapest),
        # x=4: loss 1.0/cost 5.0 (dominated by everything)
        for r, loss in zip(runs, [0.2, 0.3, 0.9, 1.0]):
            finish(it, r[0], 1.0, loss)
        promoted = {
            d.config["x"] for d in it.data.values() if d.budget == 3.0
        }
        assert promoted == {1.0, 3.0}

    def test_audit_record_carries_pareto_ranks_and_costs(self, tmp_path):
        journal = str(tmp_path / "j.jsonl")
        handle = obs.configure(journal_path=journal)
        try:
            it = ParetoIteration(
                0, [2, 1], [1.0, 3.0], sampler_factory()[0],
                cost_fn=lambda d, b: d.config["x"],
            )
            runs = [it.get_next_run() for _ in range(2)]
            finish(it, runs[0][0], 1.0, 0.5)
            finish(it, runs[1][0], 1.0, 0.9)
        finally:
            handle.close()
        promos = [
            r for r in obs.read_journal(journal)
            if r["event"] == "promotion_decision"
        ]
        assert len(promos) == 1
        assert promos[0]["rule"] == "pareto"
        assert promos[0]["pareto_rank"] == [0, 1]
        assert promos[0]["costs"] == [1.0, 2.0]


class TestLCEarlyStopUnit:
    def test_hopeless_config_terminated_despite_rank(self):
        # two promotion slots, but one candidate's flat curve cannot
        # reach the incumbent cut -> only one promotes
        it = LCEarlyStopIteration(
            0, [3, 2, 1], [1.0, 3.0, 9.0], sampler_factory()[0],
            cut_fn=lambda target: 0.05,
        )
        runs = [it.get_next_run() for _ in range(3)]
        # decreasing curve heading under the cut needs 3+ points -> with
        # one rung of history both fall back to last-value; candidate 0's
        # last value sits under the cut, candidate 1's far above it
        finish(it, runs[0][0], 1.0, 0.04)
        finish(it, runs[1][0], 1.0, 0.5)
        finish(it, runs[2][0], 1.0, 0.6)
        promoted = [d for d in it.data.values() if d.budget == 3.0]
        assert len(promoted) == 1
        assert promoted[0].config["x"] == 1.0

    def test_without_cut_behaves_like_sync_topk(self):
        it = LCEarlyStopIteration(
            0, [3, 2, 1], [1.0, 3.0, 9.0], sampler_factory()[0],
        )
        runs = [it.get_next_run() for _ in range(3)]
        for r, loss in zip(runs, [0.3, 0.1, 0.9]):
            finish(it, r[0], 1.0, loss)
        promoted = {
            d.config["x"] for d in it.data.values() if d.budget == 3.0
        }
        assert promoted == {1.0, 2.0}


# ------------------------------------------------------- registry / wiring
class TestRuleRegistry:
    def test_known_rules_resolve(self):
        from hpbandster_tpu.core.successive_halving import SuccessiveHalving

        assert resolve_rule("sync") is SuccessiveHalving
        assert resolve_rule("successive_halving") is SuccessiveHalving
        assert resolve_rule("asha") is ASHAIteration
        assert resolve_rule("pareto") is ParetoIteration
        assert resolve_rule("lc_earlystop") is LCEarlyStopIteration
        assert set(
            ("asha", "pareto", "lc_earlystop", "successive_halving")
        ) <= set(RULE_NAMES)

    def test_unknown_rule_rejected_with_vocabulary(self):
        with pytest.raises(ValueError, match="asha"):
            resolve_rule("warp_speed")

    def test_promote_package_imports_light(self):
        # the serve tier validates names without paying for jax/numpy
        import subprocess
        import sys

        code = (
            "import sys; import hpbandster_tpu.promote; "
            "sys.exit(1 if ('jax' in sys.modules or "
            "'numpy' in sys.modules) else 0)"
        )
        assert subprocess.run(
            [sys.executable, "-c", code], timeout=60
        ).returncode == 0

    def test_bohb_promotion_rule_selects_iteration_class(self):
        cs = ConfigurationSpace(seed=1)
        cs.add_hyperparameter(UniformFloatHyperparameter("x", 0.0, 1.0))
        opt = BOHB(
            configspace=cs, run_id="pr", executor=_NullExecutor(),
            min_budget=1, max_budget=9, eta=3, promotion_rule="asha",
        )
        try:
            assert opt.iteration_class is ASHAIteration
            assert opt.config["promotion_rule"] == "asha"
            it = opt.get_next_iteration(0, {})
            assert isinstance(it, ASHAIteration)
            assert it.eta == pytest.approx(3.0)
        finally:
            opt.shutdown()

    def test_invalid_rule_rejected_before_executor_starts(self):
        # resolve_rule must run BEFORE Master.__init__ starts the
        # executor: a typo'd name raising afterwards would leak the
        # running dispatcher with no handle to shut it down
        cs = ConfigurationSpace(seed=1)
        cs.add_hyperparameter(UniformFloatHyperparameter("x", 0.0, 1.0))
        started = []

        class Recorder(_NullExecutor):
            def start(self, new_result_callback, new_worker_callback):
                started.append(True)

        with pytest.raises(ValueError, match="unknown promotion rule"):
            BOHB(
                configspace=cs, run_id="pr-bad", executor=Recorder(),
                min_budget=1, max_budget=9, eta=3,
                promotion_rule="ahsa",
            )
        assert started == []

    def test_sweep_spec_promotion_rule_validation(self):
        from hpbandster_tpu.serve.session import SweepSpec

        spec = SweepSpec(promotion_rule="asha")
        assert spec.to_dict()["promotion_rule"] == "asha"
        assert SweepSpec.from_dict(
            {"promotion_rule": "pareto"}
        ).promotion_rule == "pareto"
        with pytest.raises(ValueError, match="promotion rule"):
            SweepSpec(promotion_rule="warp_speed")
        with pytest.raises(ValueError, match="random"):
            SweepSpec(optimizer="random", promotion_rule="asha")


class _NullExecutor:
    """Minimal executor for wiring tests that never run jobs."""

    def start(self, new_result_callback, new_worker_callback):
        pass

    def number_of_workers(self):
        return 1

    def submit_job(self, job):  # pragma: no cover
        raise AssertionError("wiring test must not submit")

    def shutdown(self, shutdown_workers=False):
        pass


# ------------------------------------------------------------------- audit
class TestStragglerAuditLoop:
    def test_flagged_config_rides_next_promotion_decision(self, tmp_path):
        journal = str(tmp_path / "j.jsonl")
        handle = obs.configure(journal_path=journal)
        try:
            obs.note_straggler((0, 0, 1))
            obs.note_straggler((7, 0, 0))  # another rung's straggler
            obs.emit_promotion_decision(
                0, 0, 1.0, 3.0,
                config_ids=[(0, 0, 0), (0, 0, 1)],
                losses=[0.5, 0.9], promoted=[True, False],
            )
            obs.emit_promotion_decision(
                0, 1, 3.0, 9.0,
                config_ids=[(0, 0, 0)], losses=[0.4], promoted=[True],
            )
        finally:
            handle.close()
        promos = [
            r for r in obs.read_journal(journal)
            if r["event"] == "promotion_decision"
        ]
        assert promos[0]["straggler_observed"] == [[0, 0, 1]]
        # drained: the marker rides exactly one record; the foreign
        # rung's marker does not leak into an unrelated decision
        assert "straggler_observed" not in promos[1]
        # report surfaces the correlation on the decision row
        from hpbandster_tpu.obs.report import build_report

        rep = build_report(obs.read_journal(journal))
        rows = rep["promotion_regret"]["decisions"]
        assert rows[0]["stragglers_observed"] == 1
        assert rows[1]["stragglers_observed"] == 0
        # cleanup: the unmatched (7,0,0) marker must not leak into
        # other tests' process-global ledger
        obs.drain_stragglers([(7, 0, 0)])

    def test_ledger_scoped_by_budget_rung(self):
        # under ASHA a config promoted from rung 0 and flagged while
        # running at budget 3 appears in BOTH rungs' candidate censuses;
        # the marker must ride the rung that actually stalled
        obs.note_straggler((0, 0, 2), budget=3.0)
        assert obs.drain_stragglers([(0, 0, 2)], budget=1.0) == []
        assert obs.drain_stragglers([(0, 0, 2)], budget=3.0) == [(0, 0, 2)]
        # budget-less notes (hand-rolled / foreign journals) wildcard
        obs.note_straggler((0, 0, 9))
        assert obs.drain_stragglers([(0, 0, 9)], budget=1.0) == [(0, 0, 9)]

    def test_ledger_scoped_by_run_and_tenant(self):
        # config-id triples restart at (0,0,0) every sweep: a marker
        # noted in one run (or tenant) must not drain into another's
        # promotion decision — sequential sync/asha pairs of one process
        # and concurrent serve tenants both depend on it
        with obs.use_run("run-a"):
            obs.note_straggler((0, 0, 3))
        with obs.use_tenant("acme"):
            obs.note_straggler((0, 0, 4))
        with obs.use_run("run-b"):
            assert obs.drain_stragglers([(0, 0, 3)]) == []
        with obs.use_tenant("bob"):
            assert obs.drain_stragglers([(0, 0, 4)]) == []
        with obs.use_run("run-a"):
            assert obs.drain_stragglers([(0, 0, 3)]) == [(0, 0, 3)]
        with obs.use_tenant("acme"):
            assert obs.drain_stragglers([(0, 0, 4)]) == [(0, 0, 4)]
        # inside a job's trace the run identity comes from the trace
        # itself — the path the anomaly detector notes through
        with obs.use_trace(obs.new_trace("run-c")):
            obs.note_straggler((0, 0, 5))
        assert obs.drain_stragglers([(0, 0, 5)]) == []
        with obs.use_run("run-c"):
            assert obs.drain_stragglers([(0, 0, 5)]) == [(0, 0, 5)]

    def test_live_detector_feeds_ledger(self):
        from hpbandster_tpu.obs.anomaly import AnomalyDetector, AnomalyRules

        det = AnomalyDetector(
            rules=AnomalyRules(
                straggler_min_samples=3, straggler_factor=2.0,
                cooldown_s=0.0,
            ),
            bus=obs.get_bus(),
        )
        base = {"event": "job_finished", "budget": 1.0, "loss": 0.5,
                "t_wall": 1.0, "t_mono": 1.0}
        for i in range(4):
            det.process(dict(base, run_s=0.1, config_id=[0, 0, i]))
        fired = det.process(
            dict(base, run_s=30.0, config_id=[0, 0, 9])
        )
        assert fired and fired[0]["rule"] == "straggler"
        assert obs.drain_stragglers([(0, 0, 9)]) == [(0, 0, 9)]


class TestPromotionMetricFamily:
    def test_rule_rung_label_round_trip(self):
        from hpbandster_tpu.obs.export import (
            metric_family,
            parse_prometheus_text,
            render_snapshot,
        )

        fam, labels = metric_family("bracket.promotions.asha.2")
        assert fam == "hpbandster_bracket_promotions"
        assert labels == {"rule": "asha", "rung": "2"}
        # hostile rule names survive the escaping round trip, exactly
        # like the serve tenant family
        evil = 'a.b"x\nY\\z'
        snap = {
            "counters": {f"bracket.promotions.{evil}.0": 5},
            "gauges": {}, "histograms": {},
        }
        text = render_snapshot(snap)
        parsed = parse_prometheus_text(text)
        fam_total = "hpbandster_bracket_promotions_total"
        (labels, value), = parsed[fam_total]["samples"]
        assert labels == {"rule": evil, "rung": "0"} and value == 5.0

    def test_emitter_advances_labeled_counter(self):
        before = obs.get_metrics().counter(
            "bracket.promotions.test_rule_xyz.1"
        ).value
        obs.emit_bracket_promotion(
            0, 1, "test_rule_xyz", promoted=3, candidates=9,
            budget=1.0, next_budget=3.0,
        )
        after = obs.get_metrics().counter(
            "bracket.promotions.test_rule_xyz.1"
        ).value
        assert after - before == 3


# ------------------------------------------------------------- e2e harness
class _PacedWorker(Worker):
    """Budget-independent loss (promotion parity needs rank stability
    across budgets) with optional injected per-evaluation delay."""

    straggle_s = 0.0

    def compute(self, config_id, config, budget, working_directory):
        if self.straggle_s:
            time.sleep(self.straggle_s)
        x = float(config["x"])
        return {"loss": (x - 0.37) ** 2, "info": {}}


def _space(seed):
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameter(UniformFloatHyperparameter("x", 0.0, 1.0))
    return cs


def _run_sweep(seed, rule, n_workers=1, straggler_s=0.0, journal=None,
               anomaly=None):
    handle = (
        obs.configure(journal_path=journal, anomaly=anomaly)
        if journal else None
    )
    run_id = f"promote-e2e-{seed}-{rule or 'sync'}"
    ns = NameServer(run_id=run_id, host="127.0.0.1", port=0)
    host, port = ns.start()
    opt = None
    try:
        for i in range(n_workers):
            w = _PacedWorker(
                run_id=run_id, nameserver=host, nameserver_port=port, id=i,
            )
            if i == 0:
                w.straggle_s = straggler_s
            w.run(background=True)
        d = Dispatcher(
            run_id=run_id, nameserver=host, nameserver_port=port,
            ping_interval=0.1, discover_interval=0.1,
        )
        opt = BOHB(
            configspace=_space(seed), run_id=run_id, executor=d,
            min_budget=1, max_budget=9, eta=3, seed=seed,
            min_points_in_model=10_000,  # pure seeded sampling
            promotion_rule=rule,
        )
        res = opt.run(n_iterations=1, min_n_workers=n_workers)
        return res
    finally:
        if opt is not None:
            opt.shutdown(shutdown_workers=True)
        ns.shutdown()
        if handle is not None:
            handle.close()


class TestASHAEndToEnd:
    def test_parity_with_sync_on_straggler_free_run(self):
        """Acceptance: zero stragglers -> the ASHA sweep's final
        incumbent matches the synchronous sweep on the same seed."""
        res_sync = _run_sweep(11, None)
        res_asha = _run_sweep(11, "asha")
        inc_sync = res_sync.get_incumbent_id()
        inc_asha = res_asha.get_incumbent_id()
        assert inc_sync is not None
        assert inc_asha == inc_sync
        loss_sync = res_sync.data[inc_sync].results[9.0]
        loss_asha = res_asha.data[inc_asha].results[9.0]
        assert loss_asha == pytest.approx(loss_sync)
        # same seeded rung-0 configs in both sweeps
        cfg_sync = {
            cid: d.config["x"] for cid, d in res_sync.data.items()
        }
        cfg_asha = {
            cid: d.config["x"] for cid, d in res_asha.data.items()
        }
        assert cfg_sync == cfg_asha

    def test_straggler_no_longer_stalls_sibling_promotions(self, tmp_path):
        """Acceptance: with one delayed worker, ASHA promotions proceed
        (higher-budget results land before the straggler's rung-0
        result), barrier stall ~ 0 vs sync's full-rung stall, and the
        exactly-once audit lineage stays duplicate-free."""
        from hpbandster_tpu.obs.anomaly import AnomalyRules

        rules = AnomalyRules(
            straggler_min_samples=3, straggler_factor=2.0, cooldown_s=0.0,
        )
        j_sync = str(tmp_path / "sync.jsonl")
        j_asha = str(tmp_path / "asha.jsonl")
        _run_sweep(7, None, n_workers=2, straggler_s=0.5,
                   journal=j_sync, anomaly=rules)
        _run_sweep(7, "asha", n_workers=2, straggler_s=0.5,
                   journal=j_asha, anomaly=rules)
        rec_sync = obs.read_journal(j_sync)
        rec_asha = obs.read_journal(j_asha)

        def first_higher_before_last_low(records):
            last_low = None
            first_high = None
            for i, r in enumerate(records):
                if r.get("event") != "job_finished" or "loss" not in r:
                    continue
                if r.get("budget") == 1.0:
                    last_low = i
                elif first_high is None:
                    first_high = i
            return (
                first_high is not None and last_low is not None
                and first_high < last_low
            )

        # sync: the barrier forbids any budget-3 result before the rung
        # completes; asha: sibling promotions overtook the straggler
        assert not first_higher_before_last_low(rec_sync)
        assert first_higher_before_last_low(rec_asha)

        # the barrier stall, in results and not in seconds (a wall-clock
        # bound on the waits read the load of a shared CPU): under sync no
        # rung-0 promotion is decided while a rung-0 result is outstanding
        # (the rung could not cut until the delayed result landed); under
        # asha the first promotion wave fired the moment its quota opened,
        # with rung-0 results (the straggler's among them) still to come.
        # (Later asha waves can legitimately wait: floor(n_done/eta) grows
        # with completions, so the k-th promotion needs k*eta results — a
        # quota, not a barrier.)
        def rung0_results_after_first_decision(records):
            first = next(
                i for i, r in enumerate(records)
                if r.get("event") == "promotion_decision" and r.get("rung") == 0
            )
            return sum(
                r.get("event") == "job_finished" and "loss" in r
                and r.get("budget") == 1.0
                for r in records[first:]
            )

        assert rung0_results_after_first_decision(rec_sync) == 0
        assert rung0_results_after_first_decision(rec_asha) >= 1
        waits_sync = promotion_waits(rec_sync)
        waits_asha = promotion_waits(rec_asha)
        assert waits_sync["max_wait_s"] is not None
        assert waits_asha["per_decision"][0]["rung"] == 0
        # worker utilization must not regress under async promotion
        util_sync = worker_utilization(rec_sync)["busy_fraction"]
        util_asha = worker_utilization(rec_asha)["busy_fraction"]
        assert util_sync is not None and util_asha is not None
        assert util_asha >= util_sync - 0.05

        # exactly-once lineage on the async journal: every submission
        # joined exactly one terminal result, no duplicates
        submitted, terminals = [], []
        for r in rec_asha:
            if r["event"] == "job_submitted":
                submitted.append((tuple(r["config_id"]), r["budget"]))
            elif r["event"] in ("job_finished", "job_failed") and "loss" in r:
                terminals.append((tuple(r["config_id"]), r["budget"]))
        assert len(submitted) == len(set(submitted))
        assert len(terminals) == len(set(terminals))
        assert set(submitted) == set(terminals)

        # asha decisions are journaled under their rule name
        asha_promos = [
            r for r in rec_asha if r.get("event") == "promotion_decision"
        ]
        assert asha_promos
        assert all(p["rule"] == "asha" for p in asha_promos)


# ------------------------------------------------------------------ replay
class TestReplayHarness:
    @pytest.fixture(scope="class")
    def journal_records(self, tmp_path_factory):
        path = str(tmp_path_factory.mktemp("replay") / "j.jsonl")
        _run_sweep(5, None, journal=path)
        return obs.read_journal(path)

    @pytest.mark.parametrize(
        "rule", ["successive_halving", "asha", "pareto", "lc_earlystop"]
    )
    def test_byte_identical_across_invocations(self, journal_records, rule):
        rep_a = replay_records(journal_records, rule)
        rep_b = replay_records(journal_records, rule)
        assert (
            json.dumps(rep_a, sort_keys=True)
            == json.dumps(rep_b, sort_keys=True)
        )
        assert format_replay(rep_a) == format_replay(rep_b)
        assert rep_a["aggregate"]["decisions"] >= 2

    def test_identity_replay_changes_nothing(self, journal_records):
        rep = replay_records(journal_records, "successive_halving")
        assert rep["aggregate"]["decisions_changed"] == 0
        assert rep["aggregate"]["configs_changed"] == 0
        for row in rep["decisions"]:
            assert row["regret_delta"] in (0.0, None)
            assert row["inversion_delta"] in (0, None)

    def test_asha_replay_reports_floor_n_over_eta(self, journal_records):
        rep = replay_records(journal_records, "asha", eta=3.0)
        for row in rep["decisions"]:
            assert row["n_promoted_replay"] <= row["n_candidates"] // 3 + 1

    def test_tied_scores_do_not_fake_zero_regret(self):
        # Pareto's integer domination counts tie across a whole front;
        # the hindsight tie-break must be candidate order, not the next
        # loss — else every tied group scores a free zero regret
        from hpbandster_tpu.promote.replay import _hindsight

        lineages = {
            (0, 0, 0): {"sampled": None, "results": {3.0: 0.9}, "rungs": []},
            (0, 0, 1): {"sampled": None, "results": {3.0: 0.1}, "rungs": []},
        }
        out = _hindsight(
            [(0, 0, 0), (0, 0, 1)], [0.0, 0.0], [True, True], 3.0,
            lineages,
        )
        # the rule's (tied) top pick is candidate 0, whose next loss is
        # 0.8 worse than the best promoted — regret must say so
        assert out["rank1_regret"] == pytest.approx(0.8)
        assert out["inversions"] == 1

    def test_unknown_rule_rejected(self, journal_records):
        with pytest.raises(ValueError, match="unknown promotion rule"):
            replay_records(journal_records, "warp_speed")

    def test_cli_replay_subcommand(self, tmp_path, capsys):
        from hpbandster_tpu.obs.__main__ import main

        path = str(tmp_path / "j.jsonl")
        _run_sweep(6, None, journal=path)
        assert main(["replay", path, "--rule", "asha"]) == 0
        out_a = capsys.readouterr().out
        assert "promotion replay under rule 'asha'" in out_a
        assert main(["replay", path, "--rule", "asha"]) == 0
        assert capsys.readouterr().out == out_a  # byte-identical
        assert main(["replay", path, "--rule", "asha", "--json"]) == 0
        json.loads(capsys.readouterr().out)
