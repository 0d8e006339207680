"""Fleet SLO engine tests: burn-rate math, alert lifecycle, replay
parity, journal batching, fleet rollup, and the ``obs slo``/``obs
alerts`` CLIs.

The replay-parity class is the load-bearing one: a live-managed
journaled run, re-scanned offline, must reproduce every published gauge
value and every alert transition byte-identically (the contract
``obs slo --journal`` enforces, and
``tests/test_program_counts.py`` holds over a real pool's wave).
"""

import io
import json

import pytest

from hpbandster_tpu import obs
from hpbandster_tpu.obs import events as E
from hpbandster_tpu.obs.__main__ import run_alerts, run_slo
from hpbandster_tpu.obs.alerts import (
    STATE_CODES,
    AlertManager,
    scan_slo_records,
)
from hpbandster_tpu.obs.journal import JsonlJournal, read_journal
from hpbandster_tpu.obs.slo import (
    DEFAULT_WINDOWS,
    BurnWindow,
    Selector,
    SLOEvaluator,
    SLOSpec,
    default_slo_pack,
)
from hpbandster_tpu.obs.summarize import read_merged_ex


def R(event, t, **fields):
    """A minimal journal-schema record."""
    rec = {"event": event, "t_wall": float(t)}
    rec.update(fields)
    return rec


def threshold_spec(objective=0.9, windows=(BurnWindow(10.0, 60.0, 2.0, "page"),),
                   **kw):
    """A controllable threshold-shape spec: `u` records, good when ok<=0
    is declared via good_when on the `ok` field being True."""
    return SLOSpec(
        name=kw.pop("name", "s"),
        objective=objective,
        total=Selector("u"),
        good_when=Selector(where=(("ok", True),)),
        windows=tuple(windows),
        **kw,
    )


class TestSelector:
    def test_event_name_and_tuple(self):
        assert Selector("a").matches(R("a", 0))
        assert not Selector("a").matches(R("b", 0))
        assert Selector(("a", "b")).matches(R("b", 0))
        assert not Selector(("a", "b")).matches(R("c", 0))

    def test_where_equality(self):
        s = Selector(where=(("ok", True),))
        assert s.matches(R("x", 0, ok=True))
        assert not s.matches(R("x", 0, ok=False))
        assert not s.matches(R("x", 0))

    def test_numeric_bounds_reject_missing_and_bools(self):
        s = Selector(field="wait_s", le=0.25)
        assert s.matches(R("x", 0, wait_s=0.1))
        assert not s.matches(R("x", 0, wait_s=0.3))
        # absence of evidence is not good service
        assert not s.matches(R("x", 0))
        assert not s.matches(R("x", 0, wait_s=True))
        assert not s.matches(R("x", 0, wait_s=float("nan")))
        ge = Selector(field="n", ge=2.0)
        assert ge.matches(R("x", 0, n=3))
        assert not ge.matches(R("x", 0, n=1))


class TestSpecValidation:
    def test_objective_must_be_open_interval(self):
        for bad in (0.0, 1.0, -0.1, 1.5):
            with pytest.raises(ValueError, match="objective"):
                SLOSpec(name="s", objective=bad, total=Selector("u"),
                        good_when=Selector(where=(("ok", True),)))

    def test_exactly_one_shape(self):
        with pytest.raises(ValueError, match="exactly one"):
            SLOSpec(name="s", objective=0.9, total=Selector("u"))
        with pytest.raises(ValueError, match="exactly one"):
            SLOSpec(name="s", objective=0.9, total=Selector("u"),
                    bad=Selector("v"),
                    good_when=Selector(where=(("ok", True),)))

    def test_counter_needs_both_fields(self):
        with pytest.raises(ValueError, match="BOTH"):
            SLOSpec(name="s", objective=0.9, total=Selector("u"),
                    total_field="evaluations")

    def test_staleness_needs_both_halves(self):
        with pytest.raises(ValueError, match="BOTH"):
            SLOSpec(name="s", objective=0.9, total=Selector("u"),
                    fresh=Selector("v"))

    def test_windows_required(self):
        with pytest.raises(ValueError, match="BurnWindow"):
            threshold_spec(windows=())

    def test_budget_horizon_defaults_to_longest_window(self):
        assert threshold_spec().budget_horizon_s == 60.0
        assert threshold_spec(budget_window_s=7.0).budget_horizon_s == 7.0

    def test_duplicate_names_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            SLOEvaluator([threshold_spec(), threshold_spec()])


class TestBurnRate:
    """Golden multi-window burn-rate cases (objective 0.9 => a 10%
    error budget, so burn = 10 x error_rate)."""

    def test_all_bad_burns_at_inverse_budget(self):
        ev = SLOEvaluator([threshold_spec()])
        out = None
        for i in range(10):
            out = ev.update(R("u", i, ok=False))
        meas = out[0]
        assert meas["burn_rate"] == 10.0
        sev = meas["severities"]["page"]
        assert sev["burn_short"] == 10.0 and sev["burn_long"] == 10.0
        assert sev["breached"] is True
        # budget: 100% errors against a 10% allowance => 10x overspent
        assert meas["budget_remaining"] == -9.0

    def test_error_rate_at_objective_burns_at_one(self):
        ev = SLOEvaluator([threshold_spec()])
        out = None
        for i in range(10):
            out = ev.update(R("u", i, ok=(i != 0)))
        meas = out[0]
        assert meas["burn_rate"] == 1.0
        assert meas["budget_remaining"] == 0.0
        assert meas["severities"]["page"]["breached"] is False

    def test_breach_needs_both_windows(self):
        """Short window screaming is not enough: the long window must
        agree the burn is sustained (the SRE multi-window condition)."""
        ev = SLOEvaluator([threshold_spec(
            windows=(BurnWindow(10.0, 100.0, 2.0, "page"),),
            budget_window_s=100.0,
        )])
        for i in range(90):
            ev.update(R("u", i, ok=True))
        out = None
        for i in range(90, 100):
            out = ev.update(R("u", i, ok=False))
        sev = out[0]["severities"]["page"]
        # short window (last 10s): nearly all bad
        assert sev["burn_short"] > 2.0
        # long window (100s): 10 bad / 100 => burn 1.0 < 2.0
        assert sev["burn_long"] == 1.0
        assert sev["breached"] is False
        # keep burning: the long window catches up and the breach holds
        for i in range(100, 160):
            out = ev.update(R("u", i, ok=False))
        assert out[0]["severities"]["page"]["breached"] is True

    def test_window_pruning_forgets_old_errors(self):
        ev = SLOEvaluator([threshold_spec(budget_window_s=10.0)])
        for i in range(5):
            ev.update(R("u", i, ok=False))
        out = None
        for i in range(5, 30):
            out = ev.update(R("u", i, ok=True))
        meas = out[0]
        # bads at t<5 left both the 10s short window and the 10s budget
        assert meas["severities"]["page"]["burn_short"] == 0.0
        assert meas["budget_remaining"] == 1.0

    def test_rounding_is_six_places(self):
        ev = SLOEvaluator([threshold_spec()])
        ev.update(R("u", 0, ok=False))
        out = ev.update(R("u", 1, ok=True))
        out = ev.update(R("u", 2, ok=True))
        # error rate 1/3 => burn 3.3333333... rounded to 6 places
        assert out[0]["burn_rate"] == round((1 / 3) / 0.1, 6) == 3.333333

    def test_no_data_measures_none(self):
        ev = SLOEvaluator([threshold_spec()])
        assert ev.update(R("other", 0)) == []
        meas = ev.measure_all()[0]
        assert meas["burn_rate"] is None
        assert meas["budget_remaining"] == 1.0

    def test_out_of_order_records_do_not_rewind_now(self):
        ev = SLOEvaluator([threshold_spec()])
        ev.update(R("u", 100.0, ok=True))
        ev.update(R("u", 50.0, ok=False))  # merged-journal straggler
        assert ev.last_t == 100.0

    def test_window_cap_bounds_memory(self, monkeypatch):
        import hpbandster_tpu.obs.slo as slo_mod

        monkeypatch.setattr(slo_mod, "_WINDOW_CAP", 4)
        ev = SLOEvaluator([threshold_spec(windows=(
            BurnWindow(1e6, 1e6, 2.0, "page"),
        ))])
        for i in range(10):
            ev.update(R("u", i, ok=False))
        state = ev.states["s"]
        assert all(len(w.items) <= 4 for w in state.windows.values())

    def test_ratio_shape_separate_bad_stream(self):
        spec = SLOSpec(
            name="rpc", objective=0.9, total=Selector("call"),
            bad=Selector("retry"),
            windows=(BurnWindow(100.0, 100.0, 2.0, "page"),),
        )
        ev = SLOEvaluator([spec])
        for i in range(9):
            ev.update(R("call", i))
        out = ev.update(R("retry", 9))
        assert out[0]["burn_rate"] == 1.0

    def test_counter_shape_clamps_and_skips_empty(self):
        spec = SLOSpec(
            name="crash", objective=0.9, total=Selector("tele"),
            total_field="evaluations", bad_field="crashes",
            windows=(BurnWindow(100.0, 100.0, 2.0, "page"),),
        )
        ev = SLOEvaluator([spec])
        # zero-evaluation telemetry contributes nothing
        assert ev.update(R("tele", 0, evaluations=0, crashes=3)) == []
        out = ev.update(R("tele", 1, evaluations=4, crashes=9))
        # crashes clamp to evaluations: error rate 1.0, never >1
        assert out[0]["burn_rate"] == 10.0

    def test_staleness_fresh_resets_age_clock(self):
        spec = SLOSpec(
            name="stale", objective=0.9, total=Selector("chunk"),
            fresh=Selector("refit"), max_age_s=10.0,
            windows=(BurnWindow(1000.0, 1000.0, 2.0, "page"),),
        )
        ev = SLOEvaluator([spec])
        # no fresh mark yet: the first probe is its own baseline
        out = ev.update(R("chunk", 0))
        assert out[0]["severities"]["page"]["burn_short"] == 0.0
        ev.update(R("refit", 5))
        out = ev.update(R("chunk", 14))  # 9s after refit: fresh
        assert out[0]["burn_rate"] == 0.0
        out = ev.update(R("chunk", 20))  # 15s after refit: stale
        assert out[0]["severities"]["page"]["burn_short"] > 0.0
        ev.update(R("refit", 21))
        out = ev.update(R("chunk", 22))  # refreshed again
        assert out[0]["severities"]["page"]["burn_short"] < 10.0

    def test_default_pack_constructs(self):
        pack = default_slo_pack()
        assert len(pack) == 6
        assert len({s.name for s in pack}) == 6
        ev = SLOEvaluator(pack)
        out = ev.update(R("serve_admission", 0.0, wait_s=0.01))
        assert [m["slo"] for m in out] == ["serve_admission"]
        assert DEFAULT_WINDOWS[0].severity == "page"


class TestAlertLifecycle:
    def spec(self, **kw):
        kw.setdefault("windows", (BurnWindow(10.0, 10.0, 2.0, "page"),))
        return threshold_spec(**kw)

    def states(self, mgr):
        return [t["state"] for t in mgr.transitions]

    def test_immediate_fire_is_deduped_while_firing(self):
        mgr = AlertManager(specs=[self.spec()], bus=None)
        for i in range(20):
            mgr.process(R("u", i, ok=False))
        # one firing transition, no matter how many breached measurements
        assert self.states(mgr) == ["firing"]
        tr = mgr.transitions[0]
        assert tr["slo"] == "s" and tr["severity"] == "page"
        assert tr["key"] == "s:page"
        assert tr["event"] == "slo_alert"

    def test_pending_hold_then_fire(self):
        mgr = AlertManager(specs=[self.spec(for_s=5.0)], bus=None)
        mgr.process(R("u", 0, ok=False))
        assert self.states(mgr) == ["pending"]
        mgr.process(R("u", 2, ok=False))
        assert self.states(mgr) == ["pending"]  # hold not yet served
        mgr.process(R("u", 6, ok=False))
        assert self.states(mgr) == ["pending", "firing"]

    def test_short_blip_resolves_pending_silently(self):
        mgr = AlertManager(specs=[self.spec(for_s=5.0)], bus=None)
        mgr.process(R("u", 0, ok=False))
        # healthy records flush the window before the hold is served
        for i in range(1, 15):
            mgr.process(R("u", i, ok=True))
        assert self.states(mgr) == ["pending"]  # no firing, no resolved
        assert mgr.snapshot()["firing"] == 0

    def test_flapping_yields_one_firing_resolved_cycle(self):
        """The satellite's hysteresis contract: breach, flap inside
        clear_for_s, then stay clear — exactly ONE firing and ONE
        resolved transition."""
        mgr = AlertManager(
            specs=[self.spec(clear_for_s=30.0)], bus=None
        )
        for i in range(5):  # t=0..4: breach => firing at t=0
            mgr.process(R("u", i, ok=False))
        for i in range(5, 21):  # clear: bads prune out of the 10s window
            mgr.process(R("u", i, ok=True))
        for i in range(21, 26):  # re-breach INSIDE the 30s clear hold
            mgr.process(R("u", i, ok=False))
        for i in range(26, 80):  # now stay clear long enough to resolve
            mgr.process(R("u", i, ok=True))
        states = self.states(mgr)
        assert states.count("firing") == 1
        assert states.count("resolved") == 1
        assert states == ["firing", "resolved"]
        assert mgr.snapshot()["firing"] == 0
        assert mgr.transition_counts == {"s": 2}

    def test_published_state_codes(self):
        mgr = AlertManager(specs=[self.spec()], bus=None)
        mgr.process(R("u", 0, ok=True))
        assert mgr.published()["s"]["state"] == STATE_CODES["ok"] == 0
        for i in range(1, 6):
            mgr.process(R("u", i, ok=False))
        assert mgr.published()["s"]["state"] == STATE_CODES["firing"] == 2

    def test_own_alert_records_are_skipped(self):
        mgr = AlertManager(specs=[self.spec()], bus=None)
        assert mgr.process(R("slo_alert", 0, slo="s")) == []
        assert mgr.process(R("alert", 1, rule="x")) == []
        assert mgr.published() == {}

    def test_sink_never_raises(self):
        mgr = AlertManager(specs=[self.spec()], bus=None)
        mgr(object())  # not an Event, not a dict: swallowed + logged


class TestReplayParity:
    """live == offline: the tentpole's byte-identical contract."""

    def churn(self, journal_path):
        h = obs.configure(journal_path=journal_path, slo=True)
        try:
            for i in range(120):
                E.emit("serve_admission", wait_s=1.0, tenant="t0")
            for i in range(30):
                E.emit("serve_admission", wait_s=0.01, tenant="t0")
            E.emit("tenant_auth", tenant="t0", ok=True)
            E.emit("tenant_auth", tenant="t0", ok=False)
            live_transitions = list(h.slo.transitions)
            live_published = h.slo.published()
        finally:
            h.close()
        return live_transitions, live_published

    def test_offline_scan_reproduces_live_manager(self, tmp_path):
        jp = str(tmp_path / "run.jsonl")
        live_transitions, live_published = self.churn(jp)
        assert live_transitions, "churn must actually breach"
        records, skipped = read_merged_ex([jp])
        assert skipped == 0
        mgr = scan_slo_records(records)
        # full-dict equality: timestamps included (transition times come
        # from the triggering record, never a clock)
        assert list(mgr.transitions) == live_transitions
        assert mgr.published() == live_published

    def test_journaled_slo_alert_records_match_recomputation(self, tmp_path):
        jp = str(tmp_path / "run.jsonl")
        self.churn(jp)
        records, _ = read_merged_ex([jp])
        mgr = scan_slo_records(records)
        payload = ("slo", "severity", "state", "burn_short", "burn_long",
                   "budget_remaining", "key")
        recorded = [
            {k: r.get(k) for k in payload}
            for r in records if r.get("event") == "slo_alert"
        ]
        recomputed = [{k: t.get(k) for k in payload} for t in mgr.transitions]
        assert recorded == recomputed
        assert recorded  # the live manager journaled its transitions

    def test_double_scan_is_deterministic(self, tmp_path):
        jp = str(tmp_path / "run.jsonl")
        self.churn(jp)
        records, _ = read_merged_ex([jp])
        a, b = scan_slo_records(records), scan_slo_records(records)
        assert list(a.transitions) == list(b.transitions)
        assert a.published() == b.published()

    def test_live_gauges_published(self, tmp_path):
        jp = str(tmp_path / "run.jsonl")
        h = obs.configure(journal_path=jp, slo=True)
        try:
            for i in range(10):
                E.emit("serve_admission", wait_s=1.0, tenant="t0")
            gauges = obs.get_metrics().snapshot()["gauges"]
        finally:
            h.close()
        assert gauges["slo.serve_admission.state"] == 2.0
        assert gauges["slo.serve_admission.burn_rate"] == 20.0
        assert gauges["alert.firing"] >= 1.0


class TestSloCLI:
    def journal(self, tmp_path, live=True):
        jp = str(tmp_path / "run.jsonl")
        if live:
            h = obs.configure(journal_path=jp, slo=True)
            try:
                for i in range(50):
                    E.emit("serve_admission", wait_s=1.0, tenant="t0")
            finally:
                h.close()
        else:
            j = JsonlJournal(jp, buffer_bytes=0)
            for i in range(50):
                j.write_record(R("serve_admission", float(i), wait_s=1.0))
            j.close()
        return jp

    def test_run_slo_json_verdict_and_parity(self, tmp_path):
        jp = self.journal(tmp_path)
        buf = io.StringIO()
        assert run_slo([jp], as_json=True, stream=buf) == 0
        doc = json.loads(buf.getvalue())
        assert doc["replay"]["identical"] is True
        assert doc["verdict"]["firing"] == 2  # page + ticket both firing
        assert doc["verdict"]["ok"] is False
        assert doc["verdict"]["budget_remaining"] < 0
        assert set(doc["verdict"]) == {"firing", "budget_remaining", "ok"}

    def test_run_slo_text_table(self, tmp_path):
        jp = self.journal(tmp_path)
        buf = io.StringIO()
        assert run_slo([jp], stream=buf) == 0
        text = buf.getvalue()
        assert "slo verdict: FAIL" in text
        assert "serve_admission" in text
        assert "replay parity: identical" in text

    def test_run_slo_offline_journal_has_no_parity_claim(self, tmp_path):
        jp = self.journal(tmp_path, live=False)
        buf = io.StringIO()
        assert run_slo([jp], as_json=True, stream=buf) == 0
        doc = json.loads(buf.getvalue())
        assert doc["replay"]["recorded_transitions"] == 0
        assert doc["replay"]["identical"] is None
        # verdict still computes from the offline scan
        assert doc["verdict"]["firing"] == 2

    def test_run_alerts_sources(self, tmp_path):
        live = self.journal(tmp_path)
        buf = io.StringIO()
        assert run_alerts([live], as_json=True, stream=buf) == 0
        doc = json.loads(buf.getvalue())
        assert doc["source"] == "journal" and doc["count"] >= 1
        offline_dir = tmp_path / "off"
        offline_dir.mkdir()
        off = self.journal(offline_dir, live=False)
        buf = io.StringIO()
        assert run_alerts([off], as_json=True, stream=buf) == 0
        doc = json.loads(buf.getvalue())
        assert doc["source"] == "offline_scan" and doc["count"] >= 1
        assert all("at_s" in r for r in doc["transitions"])

    def test_missing_journal_is_usage_error(self, tmp_path):
        assert run_slo([str(tmp_path / "nope.jsonl")]) == 2
        assert run_alerts([str(tmp_path / "nope.jsonl")]) == 2


class TestJournalBatching:
    """Satellite: the journal sink buffers writes and flushes on
    span-close/durability events, not per record."""

    def test_micro_records_buffer_until_flush_event(self, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        j = JsonlJournal(jp, buffer_bytes=64 * 1024)
        for i in range(100):
            j.write_record(R("rpc_client_call", float(i), duration_s=0.001))
        assert j.flushes == 0
        assert read_journal(jp) == []  # nothing on disk yet
        j.write_record(R("sweep_chunk", 100.0))  # span close: barrier
        assert j.flushes == 1
        assert len(read_journal(jp)) == 101
        j.close()

    def test_flushes_stay_far_below_record_count(self, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        j = JsonlJournal(jp, buffer_bytes=64 * 1024)
        n = 500
        for i in range(n):
            name = "sweep_chunk" if i % 50 == 49 else "rpc_client_call"
            j.write_record(R(name, float(i)))
        j.close()
        assert len(read_journal(jp)) == n
        assert 0 < j.flushes < n // 10

    def test_byte_threshold_forces_flush(self, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        j = JsonlJournal(jp, buffer_bytes=256)
        for i in range(10):
            j.write_record(R("tiny", float(i), pad="x" * 64))
        assert j.flushes >= 1
        j.close()
        assert len(read_journal(jp)) == 10

    def test_write_through_mode(self, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        j = JsonlJournal(jp, buffer_bytes=0)
        for i in range(5):
            j.write_record(R("tiny", float(i)))
        assert j.flushes == 5
        assert len(read_journal(jp)) == 5
        j.close()

    def test_close_drains_buffer(self, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        j = JsonlJournal(jp, buffer_bytes=64 * 1024)
        j.write_record(R("tiny", 0.0))
        j.close()
        assert len(read_journal(jp)) == 1

    def test_rotation_flushes_buffered_lines_to_old_file(self, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        j = JsonlJournal(jp, max_bytes=512, max_files=10,
                         buffer_bytes=64 * 1024)
        for i in range(32):
            j.write_record(R("tiny", float(i), pad="y" * 48))
        j.close()
        assert j.rotations >= 1, "rotation must have happened"
        # read_journal merges the rotated generations: no record lost
        # across any rotation boundary
        assert len(read_journal(jp)) == 32

    def test_explicit_flush(self, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        j = JsonlJournal(jp, buffer_bytes=64 * 1024)
        j.write_record(R("tiny", 0.0))
        assert read_journal(jp) == []
        j.flush()
        assert len(read_journal(jp)) == 1
        j.close()


class TestFleetRollup:
    """Satellite: the collector's ONE slo_gauges parser feeds the
    endpoint row, the `top` fleet line, and the watch snapshot part."""

    def gauges(self, burn=3.5, state=2.0):
        return {
            "slo.serve_admission.burn_rate": burn,
            "slo.serve_admission.budget_remaining": -1.0,
            "slo.serve_admission.state": state,
            "slo.rpc_retry_rate.burn_rate": 0.5,
            "slo.rpc_retry_rate.state": 0.0,
            "alert.firing": 1.0,
        }

    def test_slo_gauges_parser(self):
        from hpbandster_tpu.obs.collector import slo_gauges

        out = slo_gauges(self.gauges())
        assert out == {"worst_burn_rate": 3.5, "firing": 1, "slos": 2}
        assert slo_gauges({"queue_depth": 4.0}) == {}
        assert slo_gauges({}) == {}

    def snap(self, **kw):
        from tests.test_collector import snap_of

        return snap_of(**kw)

    def test_fleet_fold_and_table_line(self):
        from hpbandster_tpu.obs.collector import (
            _endpoint_row,
            derive_fleet,
            format_fleet_table,
        )

        rows = {
            "a": _endpoint_row(self.snap(gauges=self.gauges())),
            "b": _endpoint_row(self.snap(gauges=self.gauges(burn=9.0))),
        }
        fleet = derive_fleet(rows, ok=2, stale=0, lost=0, churn_events=0)
        assert fleet["slo_worst_burn_rate"] == 9.0
        assert fleet["slo_firing"] == 2
        table = format_fleet_table({"fleet": fleet, "endpoints": rows})
        assert "slo: worst_burn=9.00  firing=2" in table

    def test_slo_free_fleet_renders_without_slo_line(self):
        from hpbandster_tpu.obs.collector import (
            _endpoint_row,
            derive_fleet,
            format_fleet_table,
        )

        rows = {"a": _endpoint_row(self.snap(gauges={"queue_depth": 1.0}))}
        fleet = derive_fleet(rows, ok=1, stale=0, lost=0, churn_events=0)
        assert fleet["slo_worst_burn_rate"] is None
        assert fleet["slo_firing"] is None
        assert "slo:" not in format_fleet_table(
            {"fleet": fleet, "endpoints": rows}
        )

    def test_watch_snapshot_part(self):
        from hpbandster_tpu.obs.summarize import _snapshot_slo_part

        part = _snapshot_slo_part(self.snap(gauges=self.gauges()))
        assert part == " slo: worst_burn=3.50 firing=1"
        assert _snapshot_slo_part(self.snap(gauges={})) == ""

    def test_health_snapshot_carries_slo_verdict(self):
        mgr = AlertManager(
            specs=[threshold_spec(
                windows=(BurnWindow(10.0, 10.0, 2.0, "page"),)
            )],
            bus=None,
        )
        for i in range(5):
            mgr.process(R("u", i, ok=False))
        ep = obs.HealthEndpoint(component="worker", slo=mgr)
        snap = ep.snapshot()
        assert snap["slo"]["firing"] == 1
        assert snap["slo"]["by_slo"]["s"]["state"] == 2
