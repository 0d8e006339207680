"""Unified sweep timeline tests (ISSUE 19).

The tentpole contract: ``obs/timeline.py`` assembles every recorded
signal — host spans, RPC hop envelopes, compile events, serve lane
lifecycle, the device metrics plane's ``rung_seq``-ordered per-rung
sections — into one causally-ordered per-trace timeline, exported as
Chrome trace-event JSON (Perfetto-loadable), and attributes end-to-end
wall-clock to the named phase taxonomy with a machine-readable verdict.

Pinned here:

* a GOLDEN Chrome trace for a deterministic two-hop sweep journal
  (regenerate with ``python tests/test_timeline.py``), plus spec
  validity (ph/pid/tid/ts types, paired s/f flows) on the same journal;
* the critical-path partition property — phase seconds sum to <= the
  end-to-end span — for fuzzed arbitrary journals, not just happy paths;
* cross-host clock alignment: a wall-clock step mid-run on one host is
  re-anchored by the median wall-mono offset and cannot shuffle the
  merged order;
* the acceptance run: a journaled fused sweep (device metrics on, the
  8-device CPU mesh) through the ``obs timeline`` / ``obs
  critical-path`` CLI — Perfetto-loadable JSON with seq-ordered device
  rung slices, >= 95% of wall-clock attributed.
"""

import json
import random
from pathlib import Path

import pytest

from hpbandster_tpu import obs
from hpbandster_tpu.obs.__main__ import main as obs_main
from hpbandster_tpu.obs.timeline import (
    ADMISSION,
    PHASES,
    PROMOTION,
    RUNG_COMPUTE,
    TimelineRecorder,
    align_clocks,
    clock_offsets,
    critical_path,
    format_critical_path,
    mark,
    normalized_time,
    phase_span,
    sweep_span,
    to_chrome_trace,
)

GOLDEN = Path(__file__).parent / "timeline_golden" / "two_hop_trace.json"

#: wall-clock anchor for synthesized journals (any fixed epoch works —
#: the exporter emits timestamps relative to the earliest record)
T0 = 1_700_000_000.0


def two_hop_records():
    """A deterministic two-hop sweep journal: the master plans and
    RPC-dispatches one job to a worker host (hop 1), the result delivers
    back (hop 2 — stage fields on the worker's job record), then the
    fused chunk runs with a compile split and a decoded device-metrics
    section. Fixed twin stamps, zero skew: byte-stable export."""
    recs = []

    def rec(host, pid, mono, event, **fields):
        r = {
            "event": event, "t_wall": T0 + mono, "t_mono": 1000.0 + mono,
            "host": host, "pid": pid,
        }
        r.update(fields)
        recs.append(r)
        return r

    rec("master", 11, 0.000, "job_submitted",
        trace_id="tr-1", config_id="c0", budget=1.0)
    rec("master", 11, 0.010, "sweep_planning",
        duration_s=0.01, phase=ADMISSION, trace_id="tr-1")
    rec("master", 11, 0.030, "rpc_client_call",
        duration_s=0.02, method="evaluate", trace_id="tr-1")
    rec("worker0", 22, 0.120, "job_finished",
        trace_id="tr-1", worker="w0", budget=1.0,
        queue_wait_s=0.01, dispatch_s=0.02, compute_s=0.05,
        delivery_s=0.01)
    rec("master", 11, 0.400, "sweep_chunk",
        duration_s=0.2, compile_s=0.05, compile_cache_hit=False,
        evaluations=13, seq=0, trace_id="tr-1")
    rec("master", 11, 0.401, "device_telemetry",
        execute_s=0.12, evaluations=13, trace_id="tr-1",
        rung_order=[
            {"seq": 0, "bracket": 0, "stage": 0, "budget": 1.0,
             "est_s": 0.06, "evals": 9},
            {"seq": 1, "bracket": 0, "stage": 1, "budget": 3.0,
             "est_s": 0.04, "evals": 3},
            {"seq": 2, "bracket": 0, "stage": 2, "budget": 9.0,
             "est_s": 0.02, "evals": 1},
        ])
    rec("master", 11, 0.410, "kde_refit",
        duration_s=0.005, budget=3.0, trace_id="tr-1")
    rec("master", 11, 0.420, "sweep_incumbent",
        trace_id="tr-1", budget=9.0)
    return recs


def _golden_payload() -> str:
    return json.dumps(
        to_chrome_trace(two_hop_records()), indent=1, sort_keys=True
    ) + "\n"


class TestChromeExport:
    def test_two_hop_export_matches_golden(self):
        """Byte-for-byte against the checked-in trace: any change to the
        export schema is a deliberate golden regeneration, never drift.
        Regenerate with ``python tests/test_timeline.py``."""
        assert GOLDEN.exists(), (
            f"golden missing: run `python {Path(__file__).name}` "
            "from tests/ to generate it"
        )
        assert _golden_payload() == GOLDEN.read_text(), (
            "Chrome trace export changed; if intentional, regenerate "
            f"the golden with `python tests/{Path(__file__).name}`"
        )

    def test_trace_events_are_spec_valid(self):
        """Every emitted event satisfies the trace-event format contract
        Perfetto's importer checks: known ph, integer pid/tid, integer
        non-negative ts, X slices with dur >= 1, metadata rows first."""
        doc = to_chrome_trace(two_hop_records())
        evs = doc["traceEvents"]
        assert evs
        for e in evs:
            assert e["ph"] in {"M", "X", "i", "s", "f"}, e
            assert isinstance(e["pid"], int) and e["pid"] > 0, e
            assert isinstance(e["tid"], int) and e["tid"] >= 0, e
            if e["ph"] != "M":
                assert isinstance(e["ts"], int) and e["ts"] >= 0, e
            if e["ph"] == "X":
                assert isinstance(e["dur"], int) and e["dur"] >= 1, e
            if e["ph"] == "i":
                assert e["s"] in {"t", "p", "g"}, e
            if e["ph"] == "f":
                assert e["bp"] == "e", e
        # metadata rows precede every timed event (viewer row naming)
        phs = [e["ph"] for e in evs]
        assert phs[: phs.count("M")] == ["M"] * phs.count("M")
        meta_names = {e["name"] for e in evs if e["ph"] == "M"}
        assert meta_names == {"process_name", "thread_name"}
        # two hosts -> two process rows; worker + device + main rows exist
        assert doc["otherData"]["processes"] == 2
        thread_rows = {
            e["args"]["name"] for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert {"main", "worker w0", "device"} <= thread_rows

    def test_flow_arrows_are_paired_and_cross_rows(self):
        """Every flow start has exactly one matching finish (same id),
        the finish lands on a DIFFERENT row (a flow within one row would
        be noise), and time moves forward along the arrow."""
        evs = to_chrome_trace(two_hop_records())["traceEvents"]
        starts = {e["id"]: e for e in evs if e["ph"] == "s"}
        ends = {e["id"]: e for e in evs if e["ph"] == "f"}
        assert starts, "two-hop journal produced no flow arrows"
        assert set(starts) == set(ends)
        assert len([e for e in evs if e["ph"] == "s"]) == len(starts)
        for fid, s in starts.items():
            f = ends[fid]
            assert (s["pid"], s["tid"]) != (f["pid"], f["tid"])
            assert f["ts"] > s["ts"]
            assert f["args"]["trace_id"] == s["args"]["trace_id"]
        # the two-hop journal crosses rows at least twice: master ->
        # worker (dispatch) and worker -> master (delivery)
        assert len(starts) >= 2

    def test_device_rung_slices_seq_ordered_filling_execute_window(self):
        """The decoded ``rung_order`` section lays one slice per rung on
        the device row, in ``rung_seq`` order, back to back across the
        sweep's measured ``execute_s`` window."""
        doc = to_chrome_trace(two_hop_records())
        dev = [
            e for e in doc["traceEvents"]
            if e["ph"] == "X" and e["name"].startswith("rung b")
        ]
        assert [e["name"].split(" budget")[0] for e in dev] == [
            "rung b0 r0", "rung b0 r1", "rung b0 r2"
        ]
        # back to back: each slice starts where the previous ended
        for a, b in zip(dev, dev[1:]):
            assert (a["pid"], a["tid"]) == (b["pid"], b["tid"])
            assert b["ts"] == a["ts"] + a["dur"]
        # ... and together they span execute_s (0.12s = 120000us)
        assert sum(e["dur"] for e in dev) == pytest.approx(120_000, abs=3)
        assert all(e["args"]["phase"] == RUNG_COMPUTE for e in dev)


class TestCriticalPath:
    def test_two_hop_attribution(self):
        cp = critical_path(two_hop_records())
        assert set(cp["phases"]) <= set(PHASES)
        # the compile split and the device/chunk compute both surface
        assert cp["phases"]["compile"]["s"] == pytest.approx(0.05, abs=1e-6)
        assert cp["phases"]["rung_compute"]["s"] > 0
        assert cp["phases"]["rpc"]["s"] > 0
        assert cp["phases"]["admission_wait"]["s"] > 0
        assert cp["attributed_s"] <= cp["end_to_end_s"] + 1e-9
        assert cp["attributed_s"] == pytest.approx(
            sum(p["s"] for p in cp["phases"].values()), abs=1e-6
        )
        assert cp["verdict"]["threshold"] == 0.95

    def test_overlapping_spans_never_double_count(self):
        """Two fully overlapping spans of different phases attribute the
        window ONCE, to the higher-priority phase."""
        recs = [
            {"event": "sweep_chunk", "t_wall": T0 + 1.0, "t_mono": 1.0,
             "host": "h", "pid": 1, "duration_s": 1.0},
            {"event": "rpc_client_call", "t_wall": T0 + 1.0, "t_mono": 1.0,
             "host": "h", "pid": 1, "duration_s": 1.0},
        ]
        cp = critical_path(recs)
        assert cp["end_to_end_s"] == pytest.approx(1.0)
        assert cp["phases"]["rung_compute"]["s"] == pytest.approx(1.0)
        assert "rpc" not in cp["phases"]
        assert cp["attributed_s"] <= cp["end_to_end_s"] + 1e-9

    def test_empty_journal(self):
        cp = critical_path([])
        assert cp["end_to_end_s"] == 0.0
        assert cp["verdict"]["ok"] is False

    def test_phase_sums_bounded_for_arbitrary_journals(self):
        """Property (satellite 3): for ANY journal — random events,
        overlapping spans, multiple skewed hosts, stage fields, device
        sections, garbage durations — attributed phase seconds partition
        the end-to-end span: each >= 0, summing to <= end-to-end."""
        rng = random.Random(0xC0FFEE)
        names = [
            "sweep_chunk", "xla_compile", "kde_refit", "rpc_retry",
            "job_finished", "wave_evaluate", "serve_chunk",
            "device_telemetry", "unknown_blob", "promotion_decision",
        ]
        for _trial in range(30):
            recs = []
            for _i in range(rng.randrange(1, 30)):
                host = rng.choice(["a", "b", "c"])
                mono = rng.uniform(0.0, 5.0)
                r = {
                    "event": rng.choice(names),
                    "host": host, "pid": rng.choice([1, 2]),
                    "t_mono": 100.0 * (ord(host) - ord("a")) + mono,
                    "t_wall": T0 + mono + 40.0 * (ord(host) - ord("a"))
                    + (30.0 if rng.random() < 0.2 else 0.0),
                }
                if rng.random() < 0.6:
                    r["duration_s"] = rng.choice(
                        [rng.uniform(0, 2.0), 0.0, -1.0]
                    )
                if rng.random() < 0.3:
                    r["compile_s"] = rng.uniform(0, 3.0)
                if rng.random() < 0.3:
                    r["queue_wait_s"] = rng.uniform(0, 0.5)
                    r["compute_s"] = rng.uniform(0, 0.5)
                if rng.random() < 0.2:
                    r["execute_s"] = rng.uniform(0, 1.0)
                    r["rung_order"] = [
                        {"seq": s, "bracket": 0, "stage": s,
                         "budget": 1.0, "est_s": rng.uniform(0, 1.0)}
                        for s in range(rng.randrange(0, 4))
                    ]
                recs.append(r)
            cp = critical_path(recs)
            total = sum(p["s"] for p in cp["phases"].values())
            assert all(p["s"] >= 0 for p in cp["phases"].values())
            assert total <= cp["end_to_end_s"] + 1e-6, recs
            assert cp["unattributed_s"] >= 0.0
            assert cp["attributed_s"] == pytest.approx(total, abs=1e-5)
            # ...and the exporter survives the same garbage
            doc = to_chrome_trace(recs)
            assert json.dumps(doc)  # serializable
            for e in doc["traceEvents"]:
                if e["ph"] == "X":
                    assert e["dur"] >= 1 and e["ts"] >= 0

    def test_format_includes_verdict_line(self):
        cp = critical_path(two_hop_records())
        text = format_critical_path(cp)
        assert "verdict:" in text and "threshold 95%" in text
        assert "rung_compute" in text


class TestClockAlignment:
    def test_wall_step_on_one_host_is_reanchored(self):
        """Satellite 2: host B's wall clock steps +30s for a MINORITY of
        its records mid-run (an NTP jump); the median wall-mono offset
        ignores the step and the merged order stays the true causal
        interleaving — stepped records do NOT teleport 30s forward."""
        recs = []
        for i in range(9):
            recs.append({
                "event": "tick", "host": "A", "pid": 1,
                "t_wall": T0 + float(i), "t_mono": 10.0 + i,
            })
        for i in range(9):
            step = 30.0 if i >= 6 else 0.0  # minority of stamps stepped
            recs.append({
                "event": "tock", "host": "B", "pid": 2,
                "t_wall": T0 + 0.5 + i + step, "t_mono": 20.0 + i,
            })
        offsets = clock_offsets(recs)
        # median anchors on the stable majority: offset excludes the step
        assert offsets[("B", 2)] == pytest.approx(T0 + 0.5 - 20.0)
        ordered, off2 = align_clocks(recs)
        assert off2 == offsets
        norm = [normalized_time(r, offsets) for r in ordered]
        assert norm == sorted(norm)
        # merged order is the strict A/B interleave of the true timeline
        assert [r["event"] for r in ordered] == ["tick", "tock"] * 9
        # each B record sits exactly its true 0.5s after its A sibling,
        # stepped or not
        for i, r in enumerate(r for r in ordered if r["host"] == "B"):
            assert normalized_time(r, offsets) == pytest.approx(
                T0 + 0.5 + i
            )

    def test_wall_sort_would_have_misordered(self):
        """The counterfactual that motivates alignment: raw wall-clock
        ordering shuffles the stepped records to the end."""
        recs = []
        for i in range(6):
            recs.append({"event": "a", "host": "A", "pid": 1,
                         "t_wall": T0 + i, "t_mono": 10.0 + i})
        # B's LAST-but-one record stepped: wall says it happened after
        # everything, mono knows better
        for i in range(6):
            step = 100.0 if i == 4 else 0.0
            recs.append({"event": "b", "host": "B", "pid": 2,
                         "t_wall": T0 + 0.25 + i + step,
                         "t_mono": 50.0 + i})
        by_wall = sorted(recs, key=lambda r: r["t_wall"])
        assert by_wall[-1]["t_mono"] == pytest.approx(54.0)  # the stepped one
        ordered, _ = align_clocks(recs)
        bs = [r["t_mono"] for r in ordered if r["host"] == "B"]
        assert bs == sorted(bs)
        assert ordered[-1]["t_mono"] == pytest.approx(55.0)  # true last

    def test_records_without_twin_stamps_fall_back_to_wall(self):
        recs = [
            {"event": "x", "host": "A", "pid": 1, "t_wall": T0 + 2.0},
            {"event": "y", "host": "A", "pid": 1, "t_wall": T0 + 1.0,
             "t_mono": 1.0},
        ]
        offsets = clock_offsets(recs)
        assert normalized_time(recs[0], offsets) == T0 + 2.0
        ordered, _ = align_clocks(recs)
        assert [r["event"] for r in ordered] == ["y", "x"]


class TestSpanApi:
    def test_phase_span_and_mark_reject_unknown_phases(self):
        with pytest.raises(ValueError, match="unknown phase"):
            phase_span("x", "not_a_phase")
        with pytest.raises(ValueError, match="unknown phase"):
            mark("x", "warmup")

    def test_recorder_captures_phase_spans_with_identity(self):
        rec = TimelineRecorder(static_fields={"host": "h0", "pid": 7})
        with rec:
            with phase_span("sweep_planning", ADMISSION, seq=1):
                pass
            mark("promoted", PROMOTION, bracket=2)
        rows = rec.records
        assert [r["event"] for r in rows] == ["sweep_planning", "promoted"]
        assert rows[0]["phase"] == ADMISSION
        assert rows[0]["duration_s"] >= 0.0
        assert rows[0]["host"] == "h0" and rows[0]["pid"] == 7
        assert rows[1]["phase"] == PROMOTION and rows[1]["bracket"] == 2
        # detached: further emission is not recorded
        mark("late", PROMOTION)
        assert len(rec.records) == 2

    def test_inactive_emission_constructs_no_event(self):
        """The byte-identical-off guarantee at the API layer: with no
        sink attached, the span API returns None from emission — no
        Event exists to observe."""
        assert not obs.get_bus().active
        assert mark("probe", RUNG_COMPUTE) is None

    def test_inactive_phase_span_reads_no_clock(self, monkeypatch):
        """The host-pool tiers' spans (``obs.span``, ``phase_span``) keep
        the inactive path they had: no sink, no clock read. The fused
        driver's ``sweep_span`` is the one that always measures."""
        from hpbandster_tpu.obs import events

        def no_clock():
            raise AssertionError("an inactive span read the clock")

        assert not obs.get_bus().active
        monkeypatch.setattr(events.time, "monotonic", no_clock)
        with phase_span("probe", ADMISSION):
            pass
        with obs.span("probe"):
            pass

    def test_obs_imports_no_jax(self):
        """``sweep_span`` imports jax when it is entered, never before:
        the host-pool tiers import ``obs`` and stay jax-free."""
        import subprocess
        import sys

        code = ("import sys; import hpbandster_tpu.obs; "
                "from hpbandster_tpu.obs.timeline import sweep_span; "
                "sys.exit('jax' in sys.modules)")
        repo = str(Path(__file__).parent.parent)
        assert subprocess.run([sys.executable, "-c", code], cwd=repo).returncode == 0

    def test_sweep_span_always_measures_and_journals_with_a_sink(self):
        with pytest.raises(ValueError, match="unknown phase"):
            sweep_span("x", "not_a_phase")
        # no sink: the seconds are kept all the same, and accumulate
        totals = {}
        assert not obs.get_bus().active
        for _ in range(2):
            with sweep_span("fetch", RUNG_COMPUTE, totals):
                pass
        assert set(totals) == {"fetch"} and totals["fetch"] >= 0.0
        # re-pointed before exit: the seconds land where it points then
        row = {}
        with sweep_span("run", ADMISSION, totals) as run_span:
            run_span.totals = row
        assert set(row) == {"run"} and set(totals) == {"fetch"}
        # with a sink: phase_span's event, under the trace it was given
        trace = obs.new_trace("sweep")
        rec = TimelineRecorder(static_fields={"host": "h0", "pid": 7})
        with rec:
            with sweep_span("dispatch", PROMOTION, totals, trace=trace, seq=3):
                mark("inside", PROMOTION)
            with pytest.raises(KeyError):
                with sweep_span("result", PROMOTION, totals):
                    raise KeyError("boom")
        inside, dispatch, result = rec.records
        assert dispatch["event"] == "dispatch" and dispatch["seq"] == 3
        assert dispatch["phase"] == PROMOTION and dispatch["duration_s"] >= 0
        assert dispatch["trace_id"] == trace.trace_id
        assert "trace_id" not in inside and "trace_id" not in result
        assert result["error"] == "KeyError" and "result" in totals


class TestCli:
    def _journal_two_hop(self, tmp_path) -> str:
        path = str(tmp_path / "journal.jsonl")
        with open(path, "w", encoding="utf-8") as fh:
            for r in two_hop_records():
                fh.write(json.dumps(r) + "\n")
        return path

    def test_timeline_writes_perfetto_loadable_json(self, tmp_path, capsys):
        journal = self._journal_two_hop(tmp_path)
        out = str(tmp_path / "trace.json")
        assert obs_main(["timeline", journal, "--out", out]) == 0
        err = capsys.readouterr().err
        assert "perfetto" in err.lower()
        with open(out, encoding="utf-8") as fh:
            doc = json.load(fh)
        assert doc["traceEvents"]
        assert doc["displayTimeUnit"] == "ms"

    def test_timeline_stdout_mode(self, tmp_path, capsys):
        journal = self._journal_two_hop(tmp_path)
        assert obs_main(["timeline", journal]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["otherData"]["processes"] == 2

    def test_critical_path_json_and_text(self, tmp_path, capsys):
        journal = self._journal_two_hop(tmp_path)
        assert obs_main(["critical-path", journal, "--json"]) == 0
        cp = json.loads(capsys.readouterr().out)
        assert cp["verdict"]["threshold"] == 0.95
        assert obs_main(
            ["critical-path", journal, "--threshold", "0.5"]
        ) == 0
        assert "verdict:" in capsys.readouterr().out

    def test_missing_journal_is_usage_error(self, capsys):
        assert obs_main(["timeline", "/nonexistent/journal.jsonl"]) == 2
        assert obs_main(["critical-path", "/nonexistent/j.jsonl"]) == 2
        capsys.readouterr()


class TestEndToEnd:
    @staticmethod
    def _journaled_sweep(seed, path, warm_seed=None):
        """Run a fused sweep (device metrics on) with a journal attached
        at ``path``; ``warm_seed`` first runs one unjournaled sweep so
        the journaled one is the steady state. Returns the journal."""
        from hpbandster_tpu.optimizers import FusedBOHB
        from hpbandster_tpu.workloads.toys import (
            branin_from_vector,
            branin_space,
        )

        def run_once(s):
            opt = FusedBOHB(
                configspace=branin_space(seed=s),
                eval_fn=branin_from_vector, run_id=f"tl-e2e-{s}",
                min_budget=1, max_budget=9, eta=3, seed=s,
            )
            opt.run(n_iterations=6, device_metrics=True)
            opt.shutdown()

        if warm_seed is not None:
            # first-in-process jax/XLA backend init is one-time, not sweep
            run_once(warm_seed)
        journal = obs.JsonlJournal(path, max_bytes=50_000_000, max_files=3)
        detach = obs.get_bus().subscribe(journal)
        try:
            run_once(seed)
        finally:
            detach()
            journal.close()
        return journal

    def test_journaled_fused_sweep_timeline_and_critical_path(
        self, tmp_path, capsys
    ):
        """ISSUE 19 acceptance, structural half: run a fused sweep (device
        metrics on, the 8-device CPU mesh) with a journal attached;
        ``obs timeline`` yields a Perfetto-loadable trace whose device
        rung slices are correctly ordered, and ``obs critical-path``
        attributes the sweep's wall-clock to named phases. HOW MUCH of
        the wall it attributes is a timing, checked in the slow lane
        (``test_critical_path_attributes_96pct_of_wall``)."""
        path = str(tmp_path / "journal.jsonl")
        journal = self._journaled_sweep(6, path, warm_seed=5)

        # ISSUE 20 satellite: the sink batches micro-span writes behind
        # chunk-close barriers — physical flushes stay far below the
        # record count (write-through would make them equal)
        with open(path, encoding="utf-8") as fh:
            n_records = sum(1 for _ in fh)
        assert 0 < journal.flushes < n_records, (
            f"{journal.flushes} flushes for {n_records} records"
        )

        out = str(tmp_path / "trace.json")
        assert obs_main(["timeline", path, "--out", out]) == 0
        capsys.readouterr()
        with open(out, encoding="utf-8") as fh:
            doc = json.load(fh)
        evs = doc["traceEvents"]
        assert doc["otherData"]["slices"] > 0
        # per-rung device slices, seq-ordered: budgets within one
        # bracket ascend (rung r0 -> r1 -> r2), slices lie back to back
        dev = [
            e for e in evs
            if e["ph"] == "X" and e["name"].startswith("rung b")
        ]
        assert dev, "no decoded device rung slices in the trace"
        for a, b in zip(dev, dev[1:]):
            assert b["ts"] >= a["ts"]
        by_bracket = {}
        for e in dev:
            b = e["name"].split()[1]
            by_bracket.setdefault(b, []).append(e)
        for b, slices in by_bracket.items():
            rungs = [s["name"].split()[2] for s in slices]
            assert rungs == sorted(rungs), (
                f"bracket {b} device slices out of rung order: {rungs}"
            )
        # flows stitched the sweep's trace_id across rows
        assert doc["otherData"]["flows"] >= 1

        assert obs_main(["critical-path", path, "--json"]) == 0
        cp = json.loads(capsys.readouterr().out)
        assert cp["end_to_end_s"] > 0
        assert 0.0 < cp["attributed_share"] <= 1.0
        assert cp["phases"]["rung_compute"]["s"] > 0

    @pytest.mark.slow
    def test_critical_path_attributes_96pct_of_wall(self, tmp_path, capsys):
        """>= 96% of the journaled wall attributed to named phases
        (tightened from 95% once the batched journal sink took fsync
        stalls off the span path — ISSUE 20 satellite). A wall-clock
        share of a millisecond-scale CPU sweep: a timing, so it lives
        in the slow lane, not tier-1. One retry with a fresh journal
        damps shared-host scheduling noise (a single descheduling blip
        between two spans can cost a percent) — the claim is about
        steady state."""
        path = str(tmp_path / "journal.jsonl")
        self._journaled_sweep(6, path, warm_seed=5)
        assert obs_main(["critical-path", path, "--json"]) == 0
        cp = json.loads(capsys.readouterr().out)
        if cp["attributed_share"] < 0.96:
            path2 = str(tmp_path / "journal2.jsonl")
            self._journaled_sweep(7, path2)
            assert obs_main(["critical-path", path2, "--json"]) == 0
            cp = json.loads(capsys.readouterr().out)
        assert cp["attributed_share"] >= 0.96, format_critical_path(cp)
        assert cp["verdict"]["ok"] is True


if __name__ == "__main__":
    # golden regeneration: python tests/test_timeline.py
    GOLDEN.parent.mkdir(parents=True, exist_ok=True)
    GOLDEN.write_text(_golden_payload())
    print(f"wrote {GOLDEN}")
