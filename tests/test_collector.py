"""Fleet observatory tests: collector, derived gauges, fleet anomaly
rules, `obs top`, multi-URI watch, and the master-collector e2e.

The resilience class runs over REAL sockets (the style of
``tests/test_trace.py``'s two-journal e2e): live health endpoints, a
dead port, and a deliberately HUNG socket that accepts and never
replies — the collector must record the gaps without stalling.
"""

import io
import json
import socket
import threading
import time

from hpbandster_tpu import obs
from hpbandster_tpu.obs.__main__ import main as obs_main
from hpbandster_tpu.obs.__main__ import run_top
from hpbandster_tpu.obs.anomaly import AnomalyDetector, AnomalyRules, scan_records
from hpbandster_tpu.obs.collector import (
    FleetCollector,
    derive_fleet,
    format_fleet_table,
    read_series,
)


def snap_of(component="worker", gauges=None, counters=None, devices=None,
            uptime=1.0, in_flight=None, alerts=None):
    """A minimal obs_snapshot-shaped dict for fake-fetch tests."""
    snap = {
        "component": component,
        "uptime_s": uptime,
        "in_flight": in_flight,
        "metrics": {
            "counters": dict(counters or {}),
            "gauges": dict(gauges or {}),
            "histograms": {},
        },
        "runtime": {
            "compile": {"total_compiles": 0, "functions": {}},
            "devices": {"devices": dict(devices or {})} if devices else None,
        },
    }
    if alerts is not None:
        snap["alerts"] = alerts
    return snap


class TestDeriveFleet:
    def rows(self, **overrides):
        rows = {
            "d": {"ok": True, "component": "dispatcher",
                  "workers_alive": 2.0, "queue_depth": 4.0,
                  "jobs_in_flight": 2.0, "compiles": 10.0, "devices": {}},
            "w": {"ok": True, "component": "worker", "compiles": 1.0,
                  "devices": {"0": {"bytes_in_use": 100, "bytes_limit": 400},
                              "1": {"bytes_in_use": 300, "bytes_limit": 400}}},
        }
        rows.update(overrides)
        return rows

    def test_sums_and_balance(self):
        fleet = derive_fleet(self.rows(), ok=2, stale=0, lost=0,
                             churn_events=0)
        assert fleet["workers_alive"] == 2.0
        assert fleet["queue_depth"] == 4.0
        assert fleet["compiles"] == 11.0
        # 400/800 in use fleet-wide; skew (300-100)/300
        assert fleet["device_mem_utilization"] == 0.5
        assert fleet["device_mem_skew"] == round(200 / 300, 4)

    def test_workers_alive_falls_back_to_endpoint_census(self):
        rows = self.rows()
        del rows["d"]["workers_alive"]
        rows["w2"] = {"ok": True, "component": "worker", "devices": {}}
        rows["w3"] = {"ok": False, "component": "worker", "devices": {}}
        fleet = derive_fleet(rows, ok=3, stale=0, lost=0, churn_events=0)
        # gauge absent -> count of OK worker-component endpoints
        assert fleet["workers_alive"] == 2.0

    def test_live_bytes_feed_skew_when_no_memory_stats(self):
        rows = {
            "a": {"ok": True, "devices": {"0": {"live_bytes": 50}}},
            "b": {"ok": True, "devices": {"0": {"live_bytes": 100}}},
        }
        fleet = derive_fleet(rows, ok=2, stale=0, lost=0, churn_events=0)
        assert fleet["device_mem_utilization"] is None  # no limits known
        assert fleet["device_mem_skew"] == 0.5

    def test_empty_rows(self):
        fleet = derive_fleet({}, ok=0, stale=0, lost=0, churn_events=0)
        assert fleet["endpoints"] == 0
        assert fleet["device_mem_skew"] is None
        assert fleet["device_compute_skew"] is None
        assert fleet["workers_alive"] is None

    def test_device_compute_skew_from_sweep_gauges(self):
        """The compute-balance sibling of the memory skew: worst
        PER-ENDPOINT (max-min)/max over per-device sharded-sweep config
        counts."""
        rows = self.rows()
        rows["w"]["sweep_devices"] = {
            "0": {"configs": 100.0, "pad_rows": 0.0},
            "1": {"configs": 50.0, "pad_rows": 0.0},  # uneven endpoint
        }
        rows["h2"] = {
            "ok": True, "component": "worker", "devices": {},
            "sweep_devices": {"2": {"configs": 7.0}, "3": {"configs": 7.0}},
        }
        fleet = derive_fleet(rows, ok=3, stale=0, lost=0, churn_events=0)
        assert fleet["device_compute_skew"] == 0.5
        # two BALANCED sweeps of very different sizes must read 0.0:
        # absolute counts are only comparable within one sweep, never
        # pooled across endpoints
        rows["w"]["sweep_devices"]["1"]["configs"] = 100.0
        fleet = derive_fleet(rows, ok=3, stale=0, lost=0, churn_events=0)
        assert fleet["device_compute_skew"] == 0.0

    def test_endpoint_row_distills_sweep_device_gauges(self):
        from hpbandster_tpu.obs.collector import _endpoint_row

        snap = snap_of(gauges={
            "sweep.device.0.configs": 186.0,
            "sweep.device.0.pad_rows": 1.0,
            "sweep.device.3.configs": 186.0,
            "sweep.balance_skew": 0.0,  # not a per-device gauge: ignored
            "dispatcher.queue_depth": 2.0,
        })
        row = _endpoint_row(snap)
        assert row["sweep_devices"] == {
            "0": {"configs": 186.0, "pad_rows": 1.0},
            "3": {"configs": 186.0},
        }


class FakeFetch:
    """Scriptable fetcher: per-endpoint snapshot or exception factory."""

    def __init__(self, snaps):
        self.snaps = dict(snaps)

    def __call__(self, uri, timeout):
        v = self.snaps[uri]
        if callable(v):
            v = v()
        if isinstance(v, Exception):
            raise v
        return v


class TestFleetCollector:
    def collector(self, snaps, tmp_path=None, **kw):
        kw.setdefault("interval_s", 0.1)
        kw.setdefault("registry", obs.MetricsRegistry())
        kw.setdefault("bus", obs.EventBus())
        return FleetCollector(
            endpoints=list(snaps), fetch=FakeFetch(snaps),
            series_path=str(tmp_path / "series.jsonl") if tmp_path else None,
            **kw,
        )

    def test_derived_gauges_published_to_registry(self):
        reg = obs.MetricsRegistry()
        c = self.collector(
            {"d": snap_of("dispatcher",
                          gauges={"dispatcher.queue_depth": 3.0,
                                  "dispatcher.workers_alive": 1.0})},
            registry=reg,
        )
        c.poll_once()
        g = reg.snapshot()["gauges"]
        assert g["fleet.endpoints"] == 1.0
        assert g["fleet.endpoints_ok"] == 1.0
        assert g["fleet.queue_depth"] == 3.0
        assert g["fleet.workers_alive"] == 1.0
        assert reg.snapshot()["counters"]["fleet.poll_rounds"] == 1

    def test_unmeasurable_gauges_cleared_not_frozen(self):
        """A derived gauge whose source dies must disappear from the
        registry, not keep serving its last value (a dead dispatcher
        would otherwise scrape as a live queue forever)."""
        reg = obs.MetricsRegistry()
        fetch = FakeFetch({"d": snap_of(
            "dispatcher", gauges={"dispatcher.queue_depth": 3.0})})
        c = FleetCollector(endpoints=["d"], fetch=fetch, interval_s=0.1,
                           registry=reg, bus=obs.EventBus())
        c.poll_once()
        assert reg.snapshot()["gauges"]["fleet.queue_depth"] == 3.0
        fetch.snaps["d"] = ConnectionRefusedError("dispatcher died")
        c.poll_once()
        g = reg.snapshot()["gauges"]
        assert "fleet.queue_depth" not in g
        assert g["fleet.endpoints"] == 1.0  # still counted, just not ok
        assert g["fleet.endpoints_ok"] == 0.0
        c.stop()

    def test_series_file_round_trips_and_is_key_sorted(self, tmp_path):
        c = self.collector({"w": snap_of()}, tmp_path=tmp_path)
        c.poll_once()
        c.poll_once()
        c.stop()
        path = str(tmp_path / "series.jsonl")
        recs = read_series(path)
        assert [r["seq"] for r in recs] == [0, 1]
        assert recs[0]["endpoints"]["w"]["ok"] is True
        # determinism: every line's key layout is content-ordered
        with open(path) as fh:
            for line in fh:
                rec = json.loads(line)
                assert list(rec) == sorted(rec)
                assert list(rec["fleet"]) == sorted(rec["fleet"])

    def test_fleet_sample_event_lands_on_bus_flattened(self):
        bus = obs.EventBus()
        events = []
        bus.subscribe(lambda ev: events.append(ev))
        c = self.collector({"w": snap_of()}, bus=bus)
        c.poll_once()
        assert len(events) == 1
        ev = events[0]
        assert ev.name == obs.FLEET_SAMPLE
        assert ev.fields["endpoints"] == 1
        assert ev.fields["ok"] == 1
        assert "worker_churn_per_min" in ev.fields
        assert ev.fields["endpoint_names"] == ["w"]

    def test_dead_endpoint_records_gap_and_counts_churn_after_streak(self):
        alive = {"state": True}

        def flappy():
            if alive["state"]:
                return snap_of()
            return ConnectionRefusedError("down")

        reg = obs.MetricsRegistry()
        c = self.collector({"w": flappy, "ok": snap_of("dispatcher")},
                           registry=reg, lost_after_failures=2)
        s = c.poll_once()
        assert s["fleet"]["ok"] == 2
        alive["state"] = False
        s = c.poll_once()  # first miss: a stall, not churn yet
        assert s["endpoints"]["w"]["ok"] is False
        assert s["endpoints"]["w"]["error"].startswith("ConnectionRefusedError")
        assert s["fleet"]["lost"] == 0
        s = c.poll_once()  # second consecutive miss: churn event
        assert s["fleet"]["lost"] == 1
        assert s["fleet"]["churn_events"] == 1
        assert s["endpoints"]["w"]["consecutive_failures"] == 3 - 1
        assert s["fleet"]["worker_churn_per_min"] > 0
        # the healthy endpoint kept being sampled throughout
        assert s["endpoints"]["ok"]["ok"] is True
        # staleness grows from the last success
        assert s["endpoints"]["w"]["stale_s"] >= 0

    def test_unlisted_endpoint_counts_as_churn(self):
        listing = {"value": {"a": "a", "b": "b"}}
        snaps = {"a": snap_of(), "b": snap_of()}
        c = FleetCollector(
            endpoints=lambda: listing["value"], fetch=FakeFetch(snaps),
            interval_s=0.1, registry=obs.MetricsRegistry(),
            bus=obs.EventBus(),
        )
        c.poll_once()
        listing["value"] = {"a": "a"}  # b left the fleet
        s = c.poll_once()
        assert s["fleet"]["endpoints"] == 1
        assert s["fleet"]["worker_churn_per_min"] > 0

    def test_dispatcher_drop_counter_delta_feeds_churn(self):
        dropped = {"n": 0}

        def disp():
            return snap_of(
                "dispatcher",
                counters={"dispatcher.workers_dropped": dropped["n"]},
            )

        c = self.collector({"d": disp})
        c.poll_once()
        dropped["n"] = 2
        s = c.poll_once()
        assert s["fleet"]["churn_events"] == 2
        assert s["fleet"]["worker_churn_per_min"] > 0

    def test_trends_from_window(self):
        q = {"depth": 10.0, "compiles": 0.0}

        def disp():
            return snap_of(
                "dispatcher",
                gauges={"dispatcher.queue_depth": q["depth"]},
                counters={"runtime.compiles": q["compiles"]},
            )

        c = self.collector({"d": disp})
        c.poll_once()
        q["depth"], q["compiles"] = 4.0, 6.0
        time.sleep(0.02)
        s = c.poll_once()
        assert s["fleet"]["queue_depth_trend_per_min"] < 0  # draining
        assert s["fleet"]["compile_rate_per_min"] > 0

    def test_compile_counter_reset_means_unmeasurable_not_negative(self):
        q = {"compiles": 50.0}

        def disp():
            return snap_of("dispatcher",
                           counters={"runtime.compiles": q["compiles"]})

        c = self.collector({"d": disp})
        c.poll_once()
        q["compiles"] = 1.0  # endpoint restarted
        time.sleep(0.02)
        s = c.poll_once()
        assert s["fleet"]["compile_rate_per_min"] is None

    def test_start_stop_thread_lifecycle(self, tmp_path):
        c = self.collector({"w": snap_of()}, tmp_path=tmp_path,
                           interval_s=0.05)
        c.start()
        c.start()  # idempotent
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and len(c.window()) < 2:
            time.sleep(0.01)
        c.stop()
        c.stop()  # idempotent
        assert len(c.window()) >= 2
        assert c.last_sample()["fleet"]["ok"] == 1

    def test_fetch_exception_inside_loop_never_propagates(self):
        c = self.collector({"w": RuntimeError("boom")})
        s = c.poll_once()  # must not raise
        assert s["fleet"]["ok"] == 0

    def test_malformed_snapshot_is_a_gap_not_a_crash(self):
        """A version-skewed peer answering with an unexpected structure
        (non-dict metrics/runtime fields) must record as a failed poll,
        never raise out of poll_once."""
        c = self.collector({
            "skewed": {"component": "worker", "metrics": ["not", "a", "dict"],
                       "runtime": 7},
            "ok": snap_of(),
        })
        s = c.poll_once()
        assert s["endpoints"]["skewed"]["ok"] is False
        assert s["endpoints"]["skewed"]["error"]
        assert s["endpoints"]["ok"]["ok"] is True
        assert s["fleet"]["ok"] == 1

    def test_uri_change_under_same_name_counts_as_churn(self):
        """A worker restarting on a new port under the same listing name
        is churn — the old endpoint died even though the name persists."""
        listing = {"value": {"w": "old-uri"}}
        snaps = {"old-uri": snap_of(), "new-uri": snap_of()}
        c = FleetCollector(
            endpoints=lambda: listing["value"], fetch=FakeFetch(snaps),
            interval_s=0.1, registry=obs.MetricsRegistry(),
            bus=obs.EventBus(),
        )
        c.poll_once()
        listing["value"] = {"w": "new-uri"}
        s = c.poll_once()
        assert s["fleet"]["lost"] == 1
        assert s["fleet"]["churn_events"] == 1
        assert s["fleet"]["worker_churn_per_min"] > 0
        # the replacement endpoint polls fresh (not inheriting streaks)
        assert s["endpoints"]["w"]["ok"] is True


class TestFleetAnomalyRules:
    def fs(self, t, **fleet):
        return {"event": "fleet_sample", "t_wall": t, "fleet": fleet}

    def test_imbalance_needs_consecutive_streak(self):
        rules = AnomalyRules(imbalance_skew=0.6, imbalance_consecutive=3,
                             cooldown_s=0.0)
        recs = [
            self.fs(1.0, device_mem_skew=0.9),
            self.fs(2.0, device_mem_skew=0.9),
            self.fs(3.0, device_mem_skew=0.1),  # streak broken
            self.fs(4.0, device_mem_skew=0.9),
            self.fs(5.0, device_mem_skew=0.9),
            self.fs(6.0, device_mem_skew=0.9),  # 3rd consecutive: fires
        ]
        alerts = scan_records(recs, rules)
        assert [a["rule"] for a in alerts] == ["fleet_imbalance"]
        assert alerts[0]["t_wall"] == 6.0
        assert alerts[0]["consecutive"] == 3

    def test_churn_rule_fires_on_rate(self):
        alerts = scan_records(
            [self.fs(1.0, worker_churn_per_min=2.5, lost=1, churn_events=3)],
            AnomalyRules(churn_per_min=1.0),
        )
        assert [a["rule"] for a in alerts] == ["worker_churn"]
        assert alerts[0]["churn_per_min"] == 2.5
        assert alerts[0]["lost_endpoints"] == 1

    def test_flattened_bus_shape_is_equivalent(self):
        nested = [self.fs(1.0, worker_churn_per_min=9.0)]
        flat = [{"event": "fleet_sample", "t_wall": 1.0,
                 "worker_churn_per_min": 9.0}]
        rules = AnomalyRules(churn_per_min=1.0)
        a, b = scan_records(nested, rules), scan_records(flat, rules)
        assert [x["rule"] for x in a] == [x["rule"] for x in b] == [
            "worker_churn"
        ]

    def test_zero_knobs_disable(self):
        recs = [self.fs(1.0, device_mem_skew=1.0, worker_churn_per_min=99.0)]
        assert scan_records(
            recs, AnomalyRules(imbalance_consecutive=0, churn_per_min=0.0)
        ) == []

    def test_live_detector_matches_offline_scan(self):
        recs = [self.fs(float(i), device_mem_skew=0.9) for i in range(5)]
        rules = AnomalyRules(imbalance_consecutive=3, cooldown_s=1000.0)
        det = AnomalyDetector(rules=rules)
        live = []
        for r in recs:
            live.extend(det.process(r))
        assert live == scan_records(recs, rules)


def _start_health_server(component="worker", registry=None):
    from hpbandster_tpu.parallel.rpc import RPCServer

    srv = RPCServer("127.0.0.1", 0)
    obs.HealthEndpoint(component=component, registry=registry).register(srv)
    srv.start()
    return srv


def _hung_socket():
    """A listener that accepts connections and never replies — the
    worst-case peer (reachable but wedged)."""
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    sock.listen(4)
    stop = threading.Event()
    conns = []

    def accept_loop():
        sock.settimeout(0.2)
        while not stop.is_set():
            try:
                conn, _ = sock.accept()
                conns.append(conn)  # hold open, never answer
            except socket.timeout:
                continue
            except OSError:
                return

    t = threading.Thread(target=accept_loop, daemon=True)
    t.start()

    def close():
        stop.set()
        for c in conns:
            c.close()
        sock.close()

    return f"127.0.0.1:{sock.getsockname()[1]}", close


class TestCollectorResilienceSockets:
    """ISSUE satellite: a dead or hung endpoint times out without
    stalling the poll loop, the series records the gap, and the
    worker_churn anomaly rule fires — over real sockets."""

    def test_dead_and_hung_endpoints_do_not_stall_and_churn_fires(
        self, tmp_path
    ):
        live = _start_health_server("worker")
        # a port nothing listens on (connect refused immediately)
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        dead_uri = f"127.0.0.1:{probe.getsockname()[1]}"
        probe.close()
        hung_uri, close_hung = _hung_socket()

        bus = obs.EventBus()
        events = []
        bus.subscribe(lambda ev: events.append(ev))
        det = AnomalyDetector(
            rules=AnomalyRules(churn_per_min=0.05, cooldown_s=0.0), bus=bus
        )
        bus.subscribe(det)
        series = str(tmp_path / "series.jsonl")
        c = FleetCollector(
            endpoints={"live": live.uri, "dead": dead_uri, "hung": hung_uri},
            interval_s=0.1, timeout_s=0.3, series_path=series,
            registry=obs.MetricsRegistry(), bus=bus,
            lost_after_failures=2,
        )
        try:
            t0 = time.monotonic()
            samples = [c.poll_once() for _ in range(3)]
            elapsed = time.monotonic() - t0
            # bounded: 3 rounds x 2 bad endpoints x 0.3 s timeout + slack.
            # a stalled loop would sit here forever
            assert elapsed < 6.0
            last = samples[-1]
            # the live endpoint was sampled every round
            assert all(s["endpoints"]["live"]["ok"] for s in samples)
            # the gaps are recorded, per endpoint
            assert last["endpoints"]["dead"]["ok"] is False
            assert last["endpoints"]["hung"]["ok"] is False
            assert last["endpoints"]["hung"]["consecutive_failures"] >= 2
            # hung (never-ok) endpoints are not churn — they never joined;
            # kill the live one to produce a real ok->lost transition
            live.shutdown()
            c.poll_once()
            final = c.poll_once()  # second consecutive miss: churn
            assert final["endpoints"]["live"]["ok"] is False
            assert final["fleet"]["worker_churn_per_min"] > 0
        finally:
            close_hung()
            c.stop()

        # the worker_churn rule fired on the live bus...
        alert_events = [e for e in events if e.name == obs.ALERT]
        assert any(e.fields["rule"] == "worker_churn" for e in alert_events)
        # ...and the offline scan of the series file reaches the same
        # verdict (scan_records parity)
        recs = read_series(series)
        assert len(recs) == 5
        offline = scan_records(
            recs, AnomalyRules(churn_per_min=0.05, cooldown_s=0.0)
        )
        assert any(a["rule"] == "worker_churn" for a in offline)


class TestTopCLI:
    def test_top_over_live_endpoints(self):
        srv = _start_health_server("dispatcher")
        try:
            out = io.StringIO()
            rc = run_top(uris=[srv.uri], interval=0.01, ticks=2,
                         clear=False, stream=out)
            assert rc == 0
            text = out.getvalue()
            assert "hpbandster fleet top" in text
            assert "dispatcher" in text
            assert "endpoints 1/1 ok" in text
        finally:
            srv.shutdown()

    def test_top_over_series_file(self, tmp_path):
        series = str(tmp_path / "s.jsonl")
        c = FleetCollector(
            endpoints=["x"], fetch=FakeFetch({"x": snap_of()}),
            series_path=series, registry=obs.MetricsRegistry(),
            bus=obs.EventBus(),
        )
        c.poll_once()
        c.stop()
        out = io.StringIO()
        assert run_top(uris=None, series=series, interval=0.01, ticks=1,
                       clear=False, stream=out) == 0
        assert "worker" in out.getvalue()

    def test_top_usage_errors(self, capsys):
        assert obs_main(["top"]) == 2
        assert "top needs" in capsys.readouterr().err
        assert obs_main(["top", "--snapshot", "nope"]) == 2
        assert "invalid --snapshot URI" in capsys.readouterr().err
        assert obs_main(
            ["top", "--series", "/nonexistent/series.jsonl", "--ticks", "1"]
        ) == 2

    def test_format_fleet_table_renders_recompilers_and_alerts(self):
        sample = {
            "fleet": {"endpoints": 1, "ok": 1, "stale": 0,
                      "device_mem_skew": 0.25,
                      "worker_churn_per_min": 0.0},
            "endpoints": {
                "w0": {
                    "ok": True, "component": "worker", "uptime_s": 12.0,
                    "stale_s": 0.1, "in_flight": [0, 0, 1],
                    "alerts_total": 2.0, "compiles": 7.0,
                    "top_recompilers": [{"fn": "fused_bracket",
                                         "compiles": 5}],
                },
            },
        }
        text = format_fleet_table(sample)
        assert "fused_bracketx5" in text
        assert "mem_skew=0.250" in text
        assert "w0" in text


class TestWatchMultiUri:
    def test_multi_uri_merges_one_row_per_endpoint(self):
        from hpbandster_tpu.obs.summarize import watch_snapshot

        a = _start_health_server("worker")
        b = _start_health_server("dispatcher")
        try:
            out = io.StringIO()
            assert watch_snapshot(
                [a.uri, b.uri, "127.0.0.1:1"],
                interval=0.01, ticks=2, stream=out,
            ) == 0
            text = out.getvalue()
            # 2 ticks x 3 endpoints = 6 rows, each prefixed by its uri
            rows = [l for l in text.splitlines() if l]
            assert len(rows) == 6
            assert sum(1 for r in rows if "worker" in r) >= 2
            assert sum(1 for r in rows if "dispatcher" in r) >= 2
            assert sum(
                1 for r in rows
                if "waiting for obs_snapshot at 127.0.0.1:1" in r
            ) == 2
        finally:
            a.shutdown()
            b.shutdown()

    def test_cli_accepts_repeated_snapshot_flags(self, capsys):
        a = _start_health_server("worker")
        b = _start_health_server("dispatcher")
        try:
            assert obs_main([
                "watch", "--snapshot", a.uri, "--snapshot", b.uri,
                "--ticks", "1", "--interval", "0.01",
            ]) == 0
            out = capsys.readouterr().out
            assert a.uri in out and b.uri in out
        finally:
            a.shutdown()
            b.shutdown()

    def test_any_malformed_uri_is_usage_error(self, capsys):
        from hpbandster_tpu.obs.summarize import watch_snapshot

        srv = _start_health_server("worker")
        try:
            assert watch_snapshot([srv.uri, "junk"], ticks=1) == 2
            assert "invalid --snapshot URI 'junk'" in capsys.readouterr().err
        finally:
            srv.shutdown()

    def test_viewer_clis_never_pollute_the_global_registry(self):
        """watch --snapshot and top are VIEWERS: polling a foreign fleet
        must not publish its fleet.* gauges into this process's global
        registry (which may itself be scraped)."""
        from hpbandster_tpu.obs.summarize import watch_snapshot

        srv = _start_health_server("worker")
        before = set(obs.get_metrics().snapshot()["gauges"])
        try:
            out = io.StringIO()
            assert watch_snapshot(srv.uri, interval=0.01, ticks=1,
                                  stream=out) == 0
            out = io.StringIO()
            assert run_top(uris=[srv.uri], interval=0.01, ticks=1,
                           clear=False, stream=out) == 0
        finally:
            srv.shutdown()
        after = set(obs.get_metrics().snapshot()["gauges"])
        assert not {g for g in after - before if g.startswith("fleet.")}


class TestMasterCollectorEndToEnd:
    def test_collector_over_master_dispatcher_worker(self, tmp_path, capsys):
        """Acceptance: a collector polling >= 3 live endpoints (master +
        dispatcher + worker) yields a series file, derived fleet gauges
        visible in a Prometheus scrape, and `obs top` renders it."""
        from hpbandster_tpu.core.nameserver import NameServer
        from hpbandster_tpu.core.worker import Worker
        from hpbandster_tpu.obs.export import (
            parse_prometheus_text,
            render_registry,
        )
        from hpbandster_tpu.optimizers import BOHB
        from tests.toys import branin_dict, branin_space

        class W(Worker):
            def compute(self, config_id, config, budget, working_directory):
                time.sleep(0.01)
                return {"loss": branin_dict(config, budget), "info": {}}

        series = str(tmp_path / "fleet.jsonl")
        ns = NameServer(run_id="fleet-e2e", host="127.0.0.1", port=0)
        host, port = ns.start()
        try:
            W(run_id="fleet-e2e", nameserver=host, nameserver_port=port,
              id=0).run(background=True)
            opt = BOHB(
                configspace=branin_space(seed=7), run_id="fleet-e2e",
                nameserver=host, nameserver_port=port,
                min_budget=1, max_budget=9, eta=3, seed=7,
                collector={"interval_s": 0.2, "series_path": series},
            )
            try:
                assert opt.fleet_collector is not None
                assert opt.health_server is not None
                opt.run(n_iterations=1, min_n_workers=1)
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    sample = opt.fleet_collector.last_sample()
                    if sample is not None and sample["fleet"]["ok"] >= 3:
                        break
                    time.sleep(0.05)
                sample = opt.fleet_collector.last_sample()
                eps = set(sample["endpoints"])
                assert {"master", "dispatcher"} <= eps
                assert any(e.startswith("hpbandster.") for e in eps), eps
                assert sample["fleet"]["ok"] >= 3
                assert sample["fleet"]["workers_alive"] >= 1
            finally:
                opt.shutdown(shutdown_workers=True)
        finally:
            ns.shutdown()

        # series file on disk, readable, sequential
        recs = read_series(series)
        assert len(recs) >= 1
        assert [r["seq"] for r in recs] == list(range(len(recs)))
        # derived gauges visible in a strict Prometheus scrape
        fams = parse_prometheus_text(render_registry())
        for fam in ("hpbandster_fleet_endpoints",
                    "hpbandster_fleet_endpoints_ok",
                    "hpbandster_fleet_worker_churn_per_min"):
            assert fam in fams, sorted(f for f in fams if "fleet" in f)
        # `obs top --series` renders the fleet table from the same file
        assert obs_main(["top", "--series", series, "--ticks", "1",
                         "--no-clear"]) == 0
        out = capsys.readouterr().out
        assert "hpbandster fleet top" in out
        assert "dispatcher" in out

    def test_poll_round_duty_cycle_under_two_percent(self):
        """Acceptance: collector overhead < 2% of a warm sweep. At the
        default 2 s interval the steady-state overhead reduces to the
        poll-round duty cycle (round cost / interval), measured here
        over 3 real health-endpoint sockets."""
        servers = [_start_health_server() for _ in range(3)]
        c = FleetCollector(
            endpoints=[s.uri for s in servers], interval_s=2.0,
            registry=obs.MetricsRegistry(), bus=obs.EventBus(),
        )
        try:
            c.poll_once()  # warm (connection setup, first derivation)
            times = []
            for _ in range(5):
                t0 = time.monotonic()
                c.poll_once()
                times.append(time.monotonic() - t0)
            times.sort()
            duty_pct = 100.0 * times[len(times) // 2] / c.interval_s
            assert duty_pct < 2.0, f"poll duty cycle {duty_pct:.2f}% >= 2%"
        finally:
            c.stop()
            for s in servers:
                s.shutdown()
