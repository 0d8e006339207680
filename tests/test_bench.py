"""Unit tests for bench.py's pipeline layer.

The bench measures the chip, so it must refuse to measure without one
(``JAX_PLATFORMS=cpu`` from the caller being the one explicit exception,
which is how these tests run it), keep a failing tier from taking the
other tiers' numbers with it, and still exit non-zero when any tier
raised. These tests cover that logic on stubbed tiers plus a few real
tiers at tiny sizes.
"""

import json
import sys

import pytest

sys.path.insert(0, ".")  # bench.py lives at the repo root, not in a package
import bench  # noqa: E402

from tests.record_suite import _parse_summary  # noqa: E402


class TestRequireBackend:
    """No TPU and no explicit CPU request -> fail BEFORE measuring."""

    def test_explicit_cpu_env_is_allowed(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert bench._require_backend() == "cpu"

    def test_cpu_backend_without_explicit_request_exits(self, monkeypatch):
        # the suite's backend IS the CPU; without the caller's explicit
        # JAX_PLATFORMS=cpu that is "jax found no TPU", not a fallback
        monkeypatch.setenv("JAX_PLATFORMS", "")
        with pytest.raises(SystemExit) as ei:
            bench._require_backend()
        assert ei.value.code not in (0, None)
        assert "no TPU" in str(ei.value.code)

    def test_main_exits_before_collect_without_a_tpu(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "")
        monkeypatch.setattr(
            bench, "collect",
            lambda **kw: pytest.fail("collect ran without a TPU"))
        with pytest.raises(SystemExit) as ei:
            bench.main([])
        assert ei.value.code not in (0, None)


class TestTierIsolation:
    def test_failing_tier_records_error_and_returns_none(self):
        errors = {}

        def boom():
            raise RuntimeError("chip vanished mid-tier")

        out = bench._run_tier(errors, "fused", boom)
        assert out is None
        assert "fused" in errors and "chip vanished" in errors["fused"]

    def test_passing_tier_returns_value_and_no_error(self):
        errors = {}
        assert bench._run_tier(errors, "ok", lambda: 42) == 42
        assert errors == {}


class TestBudgetGate:
    """The enforcement arm of the compile/transfer telemetry (ISSUE 6):
    a tier that exceeds its declared compile-count or transfer-byte
    budget must fail LOUDLY (error entry -> degraded artifact), never
    drift."""

    def test_exceeded_compile_budget_records_loud_error(self, monkeypatch):
        errors = {}
        monkeypatch.setitem(bench.COMPILE_BY_TIER, "fused", {
            "compiles": 99, "compile_s": 1.0, "h2d_bytes": 0, "d2h_bytes": 0,
        })
        v = bench._check_tier_budget("fused", errors)
        assert v is not None and not v["ok"]
        assert "budget:fused" in errors
        assert "EXCEEDED" in errors["budget:fused"]
        monkeypatch.delitem(bench.BUDGET_VERDICTS, "fused", raising=False)

    def test_exceeded_transfer_budget_records_loud_error(self, monkeypatch):
        errors = {}
        mb = bench.TIER_BUDGETS["fused"]["max_transfer_mb"]
        monkeypatch.setitem(bench.COMPILE_BY_TIER, "fused", {
            "compiles": 1, "compile_s": 0.0,
            "h2d_bytes": (mb + 1) * 10**6, "d2h_bytes": 0,
        })
        v = bench._check_tier_budget("fused", errors)
        assert not v["ok"] and "budget:fused" in errors
        monkeypatch.delitem(bench.BUDGET_VERDICTS, "fused", raising=False)

    def test_within_budget_is_ok_and_silent(self, monkeypatch):
        errors = {}
        monkeypatch.setitem(bench.COMPILE_BY_TIER, "fused", {
            "compiles": 1, "compile_s": 1.0,
            "h2d_bytes": 1000, "d2h_bytes": 1000,
        })
        v = bench._check_tier_budget("fused", errors)
        assert v["ok"] and errors == {}
        monkeypatch.delitem(bench.BUDGET_VERDICTS, "fused", raising=False)

    def test_unbudgeted_tier_is_ungated(self, monkeypatch):
        errors = {}
        monkeypatch.setitem(bench.COMPILE_BY_TIER, "cnn", {
            "compiles": 500, "compile_s": 0.0,
            "h2d_bytes": 0, "d2h_bytes": 0,
        })
        assert bench._check_tier_budget("cnn", errors) is None
        assert errors == {}

    def test_run_tier_lands_transfer_deltas(self):
        """_run_tier's ledger entries carry the byte counters the budget
        verdicts are computed from."""
        from hpbandster_tpu.obs.runtime import note_transfer

        errors = {}
        bench._run_tier(
            errors, "_budget_probe", lambda: note_transfer("h2d", 1234)
        )
        try:
            entry = bench.COMPILE_BY_TIER["_budget_probe"]
            assert entry["h2d_bytes"] >= 1234
            assert set(entry) >= {
                "compiles", "compile_s", "h2d_bytes", "d2h_bytes",
            }
        finally:
            bench.COMPILE_BY_TIER.pop("_budget_probe", None)
        assert errors == {}


class TestFusedShardedTier:
    """ISSUE 10 acceptance: the ``fused_100k`` smoke rung runs END TO END
    on the forced 8-device CPU mesh (conftest), budget-gated — per-shard
    on-device sampling, balanced per-device config counts, and an
    incumbent-only fetch whose transfer bill is bytes, not candidates."""

    def test_fused_100k_runs_on_8_device_mesh_budget_gated(self):
        import jax

        assert len(jax.devices()) == 8  # the conftest-forced CPU mesh
        errors = {}
        out = bench._run_tier(
            errors, "fused_100k", bench.bench_fused_sharded,
            n_configs=1 << 17, repeats=3,
        )
        try:
            assert errors == {}, errors
            assert out is not None
            assert out["n_devices"] == 8
            assert out["n_configs"] == 1 << 17
            assert out["median"] > 0
            # geometry-balanced: every device owns the same config count
            assert len(out["per_device_configs"]) == 8
            assert len(set(out["per_device_configs"])) == 1
            assert out["balance_skew"] == 0.0
            # the scaling claim is recorded as numbers (the >= 0.8 bar is
            # judged on real chips; virtual CPU devices share host cores)
            assert "scaling_efficiency" in out
            assert "single_chip_configs_per_s" in out
            # budget gate judged the tier and passed
            v = bench.BUDGET_VERDICTS["fused_100k"]
            assert v["ok"], v
            # structural transfer claim: candidates sampled on device, so
            # the host link carried seeds + incumbents — not arrays
            assert v["observed"]["transfer_mb"] < 1.0
            assert out["host_rss_delta_mb"] < 2048
            assert out["rss_note"].startswith("cpu backend")
        finally:
            bench.COMPILE_BY_TIER.pop("fused_100k", None)
            bench.BUDGET_VERDICTS.pop("fused_100k", None)


class TestResidentTier:
    """ISSUE 12 acceptance: the ``resident_100k`` tier runs END TO END on
    the forced 8-device CPU mesh, budget-gated, with the host-sync count
    per sweep CONSTANT in config count and the d2h bill flat — the
    resident outer loop's whole point, asserted from measured transfer
    deltas, not prose. The KDE-fit probe rides along."""

    def test_resident_tier_runs_budget_gated_flat_d2h(self):
        import jax

        assert len(jax.devices()) == 8  # the conftest-forced CPU mesh
        errors = {}
        out = bench._run_tier(
            errors, "resident_100k", bench.bench_resident_sharded,
            sizes=(1024, 4096), kde_fit_sizes=(1 << 12, 1 << 14),
        )
        try:
            assert errors == {}, errors
            assert out is not None
            assert out["d2h_flat"] is True
            sizes = [row["n_configs"] for row in out["per_size"]]
            assert sizes == [1024, 4096]
            bills = {
                (row["d2h_bytes"], row["h2d_bytes"], row["host_syncs"])
                for row in out["per_size"]
            }
            # host-sync count per sweep constant in config count, and the
            # whole schedule is ONE dispatch
            assert len(bills) == 1
            assert all(row["dispatches"] == 1 for row in out["per_size"])
            assert out["per_size"][0]["h2d_bytes"] == 4  # one uint32 seed
            # ISSUE 13: the flat bill above was measured WITH the device
            # metrics plane ON — the telemetry payload rides the same
            # final d2h and stays O(schedule)
            assert out["device_metrics_enabled"] is True
            assert out["device_telemetry"]["rounds_completed"] == 3
            assert (
                out["device_telemetry"]["evaluations"]
                == out["per_size"][-1]["evaluations"]
            )
            # the KDE-fit probe measured and reported
            assert set(out["kde_fit_s"]) == {"4096", "16384"}
            assert all(v >= 0 for v in out["kde_fit_s"].values())
            assert out["fit_is_wall"] in (True, False, None)
            v = bench.BUDGET_VERDICTS["resident_100k"]
            assert v["ok"], v
            assert v["observed"]["transfer_mb"] < 1.0
        finally:
            bench.COMPILE_BY_TIER.pop("resident_100k", None)
            bench.BUDGET_VERDICTS.pop("resident_100k", None)


class TestEnsembleTier:
    """ISSUE 17 acceptance: the ``ensemble_smoke`` tier trains REAL MLP
    ensembles (>= 256 configs per rung) end to end under both sweep
    modes, budget-gated; the roofline row classifies the training
    program, and the resident host-link bill stays flat with live model
    state in the carry. Small sizes/repeats keep the CPU wall low — the
    assertions inside the tier are size-independent."""

    @pytest.mark.slow
    def test_ensemble_tier_runs_budget_gated(self):
        import jax

        assert len(jax.devices()) == 8  # the conftest-forced CPU mesh
        errors = {}
        out = bench._run_tier(
            errors, "ensemble_smoke", bench.bench_ensemble_smoke,
            repeats=1, resident_sizes=(256, 512),
        )
        try:
            assert errors == {}, errors
            assert out is not None
            # the ISSUE 17 rung-size bar, in the artifact itself
            assert out["configs_per_rung"] >= 256
            assert out["unrolled"]["evaluations"] > 0
            # roofline classified the training program: intensity always;
            # bound OR the no-peak caveat (the honesty clause)
            roof = out["roofline"]
            assert roof["flops"] and roof["intensity_flops_per_byte"]
            assert roof["bound"] is not None or roof["caveats"]
            # flat host-link bill with live ensemble state (the tier
            # raises if not, but pin the artifact fields too)
            res = out["resident"]
            assert res["d2h_flat"] is True
            assert [r["n_configs"] for r in res["per_size"]] == [256, 512]
            assert res["per_size"][0]["h2d_bytes"] == 4  # one uint32 seed
            # memory-formula fields the docs point at
            assert out["lane_state_bytes"] > 0
            assert out["rung_state_mb"] > 0
            v = bench.BUDGET_VERDICTS["ensemble_smoke"]
            assert v["ok"], v
        finally:
            bench.COMPILE_BY_TIER.pop("ensemble_smoke", None)
            bench.BUDGET_VERDICTS.pop("ensemble_smoke", None)


class TestSloOverheadTier:
    """ISSUE 20 acceptance: the ``slo_overhead`` tier runs END TO END —
    a live AlertManager riding a real journaled ServePool churn — and
    lands under the <2% obs bar with the offline replay byte-identical
    and the machine-readable verdict riding the tier dict."""

    def test_slo_tier_runs_budget_gated_under_two_pct(self):
        errors = {}
        out = bench._run_tier(
            errors, "slo_overhead", bench.bench_slo_overhead,
            micro_records=2_000, n_tenants=2,
        )
        try:
            assert errors == {}, errors
            assert out is not None
            # the CI gate: evaluator cost projected onto the churn wall
            assert out["overhead_pct"] < 2.0, out
            assert out["process_ns"] > 0
            assert out["specs"] == 6  # the default pack
            # live == offline, byte-identical (the obs slo contract)
            assert out["replay"]["identical"] is True
            v = out["verdict"]
            assert set(v) == {"firing", "budget_remaining", "ok",
                              "replay_identical"}
            assert v["replay_identical"] is True
            # budget gate judged the tier and passed: pure host math,
            # no device work beyond the serve pool's own programs
            bv = bench.BUDGET_VERDICTS["slo_overhead"]
            assert bv["ok"], bv
        finally:
            bench.COMPILE_BY_TIER.pop("slo_overhead", None)
            bench.BUDGET_VERDICTS.pop("slo_overhead", None)


class TestServeContinuousTier:
    """ISSUE 15 acceptance: the ``serve_continuous`` tier runs END TO END
    (small lane count, 8-device CPU mesh conftest), budget-gated, with
    the compile ledger pinned <= len(bucket_set) across the churning
    workload and the fairness bar holding under continuous allocation."""

    def test_serve_continuous_tier_runs_budget_gated(self):
        errors = {}
        out = bench._run_tier(
            errors, "serve_continuous", bench.bench_serve_continuous,
            n_tenants=3, lane_count=2, repeats=3,
        )
        try:
            assert errors == {}, errors
            assert out is not None
            # one resident program per bucket family, however many
            # tenants came and went (the continuous-batching contract)
            led = out["compile_ledger"]
            assert led["pinned"] is True
            assert (
                led["continuous_bracket_compiles"]
                <= led["bucket_programs"]
            )
            # both arms measured and comparable
            assert out["median"] > 0 and out["one_shot"]["median"] > 0
            lat = out["p95_admission_to_first_result_s"]
            assert lat["continuous"] is not None
            assert lat["one_shot"] is not None
            # lanes: fully packed rounds, nobody starved
            assert out["lanes_starved"] == 0
            assert 0 < out["lane_occupancy"] <= 1.0
            assert out["chunks"] >= 1
            # the fairness bar (no tenant below 80% fair share)
            assert out["fairness"]["ok"] is True, out["fairness"]
            v = bench.BUDGET_VERDICTS["serve_continuous"]
            assert v["ok"], v
        finally:
            bench.COMPILE_BY_TIER.pop("serve_continuous", None)
            bench.BUDGET_VERDICTS.pop("serve_continuous", None)


def _modern_result():
    tier = {"median": 100.0, "iqr": [90.0, 110.0],
            "runs_configs_per_s": [90.0, 100.0, 110.0]}
    return {
        "value": 100.0,
        "vs_baseline": 10.0,
        "detail": {
            "chip": "TPU v5 lite", "platform": "tpu", "n_chips": 1,
            "tiers": {
                "rpc_pool_1worker": tier,
                "batched_parallel_brackets3": tier,
                "fused_27_brackets": tier,
                "fused_10k_scale_36_brackets_1_729": tier,
            },
            "cnn_workload_budget_sgd_steps": {
                "evaluations": 10, "device_execute_s": 1.0,
                "achieved_flops_per_s": 1e12, "mfu": 0.5,
                "incumbent_val_accuracy": 0.75, "target_val_accuracy": 0.7,
                "target_met": True, "crashed_configs_masked": 0,
            },
            "cnn_wide_mxu_saturation": {
                "evaluations": 5, "device_execute_s": 2.0,
                "achieved_flops_per_s": 2e12, "mfu": 0.6,
            },
            "resnet_workload_budget_sgd_steps": {
                "evaluations": 3, "device_execute_s": 3.0,
                "incumbent_found": True,
            },
            "transformer_workload_budget_sgd_steps": {
                "evaluations": 12, "device_execute_s": 2.5,
                "achieved_flops_per_s": 3e12, "mfu": 0.4,
                "incumbent_val_accuracy": 0.91, "target_val_accuracy": 0.8,
                "target_met": True,
            },
            "teacher_workload_budget_epochs": {
                "target_val_accuracy": 0.9, "best_val_accuracy": 0.92,
                "evaluations": 60, "seconds_to_target_incl_compile": 3.5,
            },
            "pallas_scorer_vs_xla": {
                "shape": "128x64x256 d=6", "pallas_speedup": 4.0,
                "pallas_median_s": 0.001, "xla_median_s": 0.004,
            },
            "chunked_compile_static_vs_dynamic": {
                "schedule": "9 brackets, chunk 3, budgets 1..9",
                "static": {"first_run_wall_s": 32.4, "chunks": 3,
                           "fresh_compiles": 3, "compile_s_total": 32.4},
                "dynamic": {"first_run_wall_s": 12.7, "chunks": 3,
                            "fresh_compiles": 1, "compile_s_total": 12.5},
                "fresh_compiles_static_vs_dynamic": [3, 1],
                "first_run_wall_speedup": 2.56,
            },
            "chunked10k_at_scale_36_brackets_1_729": {
                "schedule": "36 brackets, chunk 6, budgets 1..729",
                "static": {"first_run_wall_s": 400.0, "chunks": 6,
                           "fresh_compiles": 6, "compile_s_total": 360.0},
                "dynamic": {"first_run_wall_s": 150.0, "chunks": 6,
                            "fresh_compiles": 2, "compile_s_total": 110.0},
                "fresh_compiles_static_vs_dynamic": [6, 2],
                "first_run_wall_speedup": 2.67,
            },
        },
    }


class TestRecordSuiteParsing:
    @pytest.mark.parametrize("line,expect", [
        ("190 passed, 22 deselected in 177.11s (0:02:57)",
         {"passed": 190, "deselected": 22}),
        ("1 failed, 21 passed, 3 warnings in 10.0s",
         {"failed": 1, "passed": 21, "warning": 3}),
        ("2 errors in 1.5s", {"error": 2}),
        ("5 passed, 1 xfailed, 2 skipped in 3.3s",
         {"passed": 5, "xfailed": 1, "skipped": 2}),
    ])
    def test_summary_token_parse(self, line, expect):
        counts, secs = _parse_summary("junk\n" + line)
        assert secs is not None
        for k, v in expect.items():
            assert counts[k] == v, (line, counts)

    def test_no_summary_line_returns_none(self):
        counts, secs = _parse_summary("nothing matching here\nat all")
        assert counts is None and secs is None


def _stub_tiers(monkeypatch, calls):
    def fused(brackets, repeats=5, max_budget=81, seed=0):
        calls.setdefault("fused", []).append(
            {"brackets": brackets, "max_budget": max_budget,
             "repeats": repeats}
        )
        return [100.0, 110.0, 120.0], 50, [{"wall_s": 1.0}], {"dominant": "x"}
    monkeypatch.setattr(bench, "bench_fused", fused)
    monkeypatch.setattr(
        bench, "bench_rpc_baseline",
        lambda repeats=5, **kw: [10.0, 11.0, 12.0])
    monkeypatch.setattr(
        bench, "bench_batched",
        lambda **kw: calls.setdefault("batched", True)
        and [1.0, 2.0, 3.0])
    monkeypatch.setattr(bench, "bench_cnn",
                        lambda **kw: calls.setdefault("cnn", True) and {})
    def fused_sharded(n_configs, repeats=3, **kw):
        calls.setdefault("fused_sharded", []).append(
            {"n_configs": n_configs, "repeats": repeats}
        )
        return {"median": 5000.0, "iqr": [4800.0, 5200.0], "n_configs":
                n_configs, "balance_skew": 0.0, "scaling_efficiency": 0.9,
                "near_linear": True, "per_device_configs": [10, 10]}
    monkeypatch.setattr(bench, "bench_fused_sharded", fused_sharded)

    def resident_sharded(sizes=None, **kw):
        calls.setdefault("resident_sharded", []).append({"sizes": sizes})
        return {"d2h_flat": True, "host_syncs_per_sweep": 5,
                "per_size": [{"n_configs": s, "d2h_bytes": 32,
                              "h2d_bytes": 4, "host_syncs": 5}
                             for s in (sizes or (1 << 13, 1 << 17))],
                "kde_fit_s": {"16384": 0.01}, "fit_is_wall": False}
    monkeypatch.setattr(bench, "bench_resident_sharded", resident_sharded)
    monkeypatch.setattr(bench, "bench_cnn_wide", lambda **kw: {})
    monkeypatch.setattr(bench, "bench_resnet", lambda **kw: {})
    monkeypatch.setattr(bench, "bench_transformer", lambda **kw: {})
    monkeypatch.setattr(bench, "bench_teacher", lambda **kw: {"t": 1})
    monkeypatch.setattr(bench, "bench_pallas_scorer",
                        lambda **kw: {"pallas_speedup": 2.0})
    monkeypatch.setattr(bench, "bench_chunked_compile",
                        lambda **kw: {"fresh_compiles_static_vs_dynamic":
                                      [3, 1]})
    monkeypatch.setattr(
        bench, "bench_obs_overhead",
        lambda **kw: calls.setdefault("obs_overhead", True)
        and {"overhead_pct": 0.1})
    monkeypatch.setattr(
        bench, "bench_runtime_overhead",
        lambda **kw: calls.setdefault("runtime_overhead", True)
        and {"overhead_pct": 0.01, "tracked_overhead_ns": 900.0})
    monkeypatch.setattr(
        bench, "bench_collector_overhead",
        lambda **kw: calls.setdefault("collector_overhead", True)
        and {"overhead_pct": 0.6, "poll_round_s": 0.012, "n_endpoints": 3,
             "interval_s": 2.0, "duty_cycle_pct": 0.6})
    monkeypatch.setattr(
        bench, "bench_slo_overhead",
        lambda **kw: calls.setdefault("slo_overhead", True)
        and {"overhead_pct": 0.14, "process_ns": 20000.0, "specs": 6,
             "slo_records_per_churn": 120, "warm_churn_s": 1.2,
             "replay": {"live_transitions": 2, "identical": True},
             "verdict": {"firing": 0, "budget_remaining": 0.9,
                         "ok": True, "replay_identical": True}})
    monkeypatch.setattr(
        bench, "bench_report_100k",
        lambda **kw: calls.setdefault("report_100k", True)
        and {"n_events": 100000, "events_per_s": 1, "deterministic": True})
    monkeypatch.setattr(
        bench, "bench_multitenant",
        lambda **kw: calls.setdefault("multitenant", True)
        and {"n_tenants": 16, "median": 100.0, "iqr": [90.0, 110.0],
             "packing_efficiency": 1.2, "p95_queue_wait_s": 0.05})
    monkeypatch.setattr(
        bench, "bench_serve_continuous",
        lambda **kw: calls.setdefault("serve_continuous", True)
        and {"n_tenants": 8, "lane_count": 4, "median": 120.0,
             "iqr": [110.0, 130.0], "continuous_vs_one_shot": 1.1,
             "p95_admission_to_first_result_s": {"continuous": 0.03,
                                                 "one_shot": 0.05},
             "lane_occupancy": 1.0, "lanes_starved": 0,
             "compile_ledger": {"continuous_bracket_compiles": 1,
                                "bucket_programs": 1, "pinned": True},
             "fairness": {"min_share_ratio": 1.0, "ok": True}})
    monkeypatch.setattr(
        bench, "bench_chaos",
        lambda **kw: calls.setdefault("chaos", True)
        and {"n_workers": 4, "median": 50.0, "iqr": [45.0, 55.0],
             "throughput_retention": 0.8, "trajectory_consistent": True,
             "recovery": {"requeues": 3}})
    monkeypatch.setattr(
        bench, "bench_async_straggler",
        lambda **kw: calls.setdefault("async_straggler", True)
        and {"n_workers": 3, "median": 60.0, "iqr": [55.0, 65.0],
             "throughput_ratio": 1.4,
             "barrier_stall_s": {"sync_median": 0.35, "asha_median": 0.0},
             "utilization_delta": 0.2, "straggler_markers": 2})


class TestFullSchedule:
    def test_run_keeps_full_schedule(self, monkeypatch):
        calls = {}
        _stub_tiers(monkeypatch, calls)
        r = bench.collect()
        # TIER_ORDER: the 10k tier runs BEFORE the headline fused tier
        assert calls["fused"][0]["brackets"] == 36
        assert calls["fused"][1]["brackets"] == bench.HEADLINE_BRACKETS
        assert calls["fused"][1]["max_budget"] == 81
        # the sharded tiers run at their real scales
        assert calls["fused_sharded"] == [
            {"n_configs": 1 << 20, "repeats": 5},
            {"n_configs": 1 << 17, "repeats": 5},
        ]
        # the resident tier picks its own ladder from the backend
        assert calls["resident_sharded"] == [{"sizes": None}]
        d = r["detail"]
        assert d["fused_1M_mesh_sharded"]["near_linear"] is True
        # every measured tier dict is stamped with the platform it
        # actually ran on (the stale-budget self-description)
        assert d["teacher_workload_budget_epochs"]["platform"] == "cpu"
        assert "cpu_fallback" not in d["teacher_workload_budget_epochs"]
        assert d["tiers"]["fused_27_brackets"]["iqr_attribution"] == {
            "dominant": "x"}
        assert "batched" in calls and "cnn" in calls
        assert "error" not in r


class TestTierSelection:
    """--tiers runs a subset; everything else is marked, never run."""

    def test_only_selected_tiers_run(self, monkeypatch):
        calls = {}
        _stub_tiers(monkeypatch, calls)
        r = bench.collect(tiers={"cnn", "pallas"})
        assert "cnn" in calls
        assert "fused" not in calls and "batched" not in calls
        assert "fused_sharded" not in calls
        d = r["detail"]
        assert "skipped" in d["tiers"]["fused_27_brackets"]
        assert "skipped" in d["tiers"]["rpc_pool_1worker"]
        assert "skipped" in d["fused_1M_mesh_sharded"]
        assert "skipped" in d["fused_100k_mesh_sharded"]
        assert "skipped" in d["resident_100k_scan_fused"]
        assert "resident_sharded" not in calls
        # deselected tiers are never stamped (they did not run anywhere)
        assert "platform" not in d["fused_100k_mesh_sharded"]
        assert d["cnn_workload_budget_sgd_steps"]["platform"] == "cpu"
        assert d["pallas_scorer_vs_xla"]["pallas_speedup"] == 2.0
        # no fused/rpc -> no headline, but the artifact still exists
        assert r["value"] is None and r["vs_baseline"] is None

    def test_unknown_tier_name_rejected(self, capsys):
        with pytest.raises(SystemExit):
            bench._parse_args(["--tiers", "cnn,warpdrive"])
        assert "warpdrive" in capsys.readouterr().err

    def test_empty_tiers_rejected_not_recorded_as_all(self, capsys):
        # `--tiers ""` must not silently run nothing while the _meta line
        # claims a full run was requested
        with pytest.raises(SystemExit):
            bench._parse_args(["--tiers", ""])
        assert "no tier names" in capsys.readouterr().err

    def test_unknown_flag_is_ignored_not_fatal(self, capsys):
        # the final JSON line must ALWAYS print: a stranger flag from the
        # archiving driver cannot be allowed to SystemExit before collect()
        args = bench._parse_args(["--some-future-flag", "--smoke"])
        assert args.smoke is True
        assert "ignoring unrecognized" in capsys.readouterr().err

    def test_ambiguous_prefix_is_ignored_not_fatal(self, capsys):
        # allow_abbrev=False: '--detail' (a prefix of --detail-out) must
        # fall into the ignored-leftovers path, not SystemExit(2) inside
        # argparse pre-collect
        args = bench._parse_args(["--detail"])
        assert args.detail_out == "BENCH_DETAIL.json"
        assert "ignoring unrecognized" in capsys.readouterr().err

    def test_smoke_ignores_tiers_with_warning(self, capsys):
        args = bench._parse_args(["--smoke", "--tiers", "pallas"])
        assert args.tiers is None
        assert "ignored under --smoke" in capsys.readouterr().err

    def test_crashed_fused_tier_is_recorded_without_a_headline(
            self, monkeypatch):
        calls = {}
        _stub_tiers(monkeypatch, calls)

        def boom(*a, **k):
            raise RuntimeError("device OOM")

        monkeypatch.setattr(bench, "bench_fused", boom)
        r = bench.collect()
        assert "device OOM" in r["error"]["fused"]
        assert r["value"] is None and r["vs_baseline"] is None
        # the other tiers still ran and kept their numbers
        assert r["detail"]["tiers"]["rpc_pool_1worker"]["median"] == 11.0

    def test_tier_order_covers_all_tier_names(self):
        # the --tiers vocabulary and the execution order are one constant
        assert set(bench.TIER_ORDER) == {
            "cnn", "cnn_wide", "pallas", "resnet", "transformer",
            "fused_1M", "fused_100k", "resident_100k", "ensemble_smoke",
            "fused10k",
            "chunked10k", "chunked_compile", "fused", "rpc", "batched",
            "teacher", "multitenant", "serve_continuous", "chaos",
            "async_straggler", "obs_overhead", "timeline_overhead",
            "runtime_overhead", "collector_overhead", "slo_overhead",
            "report_100k",
        }


class TestPartialWrites:
    def test_each_tier_lands_on_disk_as_it_completes(
            self, monkeypatch, tmp_path):
        calls = {}
        _stub_tiers(monkeypatch, calls)
        p = tmp_path / "partial.jsonl"
        bench.collect(tiers={"cnn", "rpc"}, partial_path=str(p))
        lines = [json.loads(l) for l in p.read_text().splitlines()]
        assert lines[0]["tier"] == "_meta"
        assert lines[0]["tiers_requested"] == ["cnn", "rpc"]
        tiers_written = [l["tier"] for l in lines[1:]]
        assert tiers_written == ["cnn", "rpc"]  # evidence order, only selected
        assert all("elapsed_total_s" in l for l in lines[1:])

    def test_meta_line_truncates_stale_file(self, monkeypatch, tmp_path):
        p = tmp_path / "partial.jsonl"
        p.write_text('{"tier": "stale-from-last-run"}\n')
        calls = {}
        _stub_tiers(monkeypatch, calls)
        bench.collect(tiers=set(), partial_path=str(p))
        lines = p.read_text().splitlines()
        assert "stale-from-last-run" not in lines[0]
        assert json.loads(lines[0])["tier"] == "_meta"

    def test_chunked10k_subruns_land_on_disk_individually(
            self, monkeypatch, tmp_path):
        # the dynamic sub-run (tens of chip-minutes) must be on disk
        # BEFORE the static comparison starts: a death mid-static cannot
        # discard it
        calls = {}
        _stub_tiers(monkeypatch, calls)

        def fake_10k(seed=60, on_subresult=None):
            on_subresult("dynamic", {"fresh_compiles": 2})
            raise RuntimeError("chip lost during the static comparison")

        monkeypatch.setattr(bench, "bench_chunked_10k", fake_10k)
        p = tmp_path / "partial.jsonl"
        r = bench.collect(tiers={"chunked10k"}, partial_path=str(p))
        lines = [json.loads(l) for l in p.read_text().splitlines()]
        subs = [l for l in lines if l["tier"] == "chunked10k.dynamic"]
        assert subs and subs[0]["result"] == {"fresh_compiles": 2}
        assert "chip lost" in r["error"]["chunked10k"]

    def test_partial_write_failure_does_not_kill_the_run(
            self, monkeypatch, capsys):
        calls = {}
        _stub_tiers(monkeypatch, calls)
        r = bench.collect(tiers={"rpc"},
                          partial_path="/nonexistent-dir/partial.jsonl")
        assert r["detail"]["tiers"]["rpc_pool_1worker"]["median"] == 11.0
        assert "partial write" in capsys.readouterr().err


class TestCompactLineContract:
    """The driver captures a 2000-char tail and parses the LAST line; a
    monolithic result line once overran it and landed parsed: null
    despite rc=0. The compact line must fit WHATEVER the run did."""

    def test_worst_case_fits_and_parses(self):
        r = _modern_result()
        r["metric"] = ("configs evaluated/sec/chip (SMOKE: 4 brackets, "
                       "budgets 1..9)")
        r["unit"] = "configs/s/chip"
        r["smoke"] = True
        r["error"] = {
            t: "E" * 400 for t in list(bench.TIER_ORDER) + ["backend",
                                                            "collect"]
        }
        line = bench.compact_line(r, "BENCH_DETAIL.json")
        assert len(line) <= bench.COMPACT_LINE_MAX
        out = json.loads(line)
        assert out["value"] == 100.0 and out["vs_baseline"] == 10.0
        assert out["platform"] == "tpu"
        assert out["detail_file"] == "BENCH_DETAIL.json"
        assert out["smoke"] is True and "backend" in out["error"]

    def test_measured_tiers_listed_skipped_ones_not(self):
        r = _modern_result()
        r["detail"]["tiers"]["batched_parallel_brackets3"] = {
            "skipped": "not selected (--tiers)"}
        r["detail"]["cnn_wide_mxu_saturation"] = None
        line = json.loads(bench.compact_line(r, "D.json"))
        assert "fused_27_brackets" in line["tiers_measured"]
        assert "batched_parallel_brackets3" not in line["tiers_measured"]
        assert "cnn_wide_mxu_saturation" not in line["tiers_measured"]

    def test_collect_crash_result_still_emits(self):
        r = {"metric": "m", "value": None, "unit": "u", "vs_baseline": None,
             "error": {"collect": "BOOM " * 200}}
        out = json.loads(bench.compact_line(r, "D.json"))
        assert out["platform"] is None and out["tiers_measured"] == []
        assert len(json.dumps(out)) <= bench.COMPACT_LINE_MAX

    def test_oversized_line_drops_fields_never_truncates_bytes(self):
        # a sliced JSON string would land parsed: null — the line must
        # shrink by dropping whole fields, staying valid JSON, and the
        # honesty labels (metric banner, error, smoke) must outlive the
        # detail-ish fields that caused the overflow
        r = _modern_result()
        r["metric"] = "configs evaluated/sec/chip (SMOKE: reduced)"
        r["unit"] = "configs/s/chip"
        r["smoke"] = True
        r["error"] = {"fused": "chip lost"}
        line = bench.compact_line(r, "/very/long/path/" + "d" * 3000
                                  + ".json")
        assert len(line) <= bench.COMPACT_LINE_MAX
        out = json.loads(line)  # still parses
        assert out["value"] == 100.0 and out["vs_baseline"] == 10.0
        assert "detail_file" not in out  # the culprit went first
        assert "SMOKE" in out["metric"]  # honesty survived
        assert out["smoke"] is True and "chip lost" in out["error"]

    def test_failed_detail_write_drops_the_pointer(self, monkeypatch,
                                                   capsys):
        # a compact line must never point at a STALE detail file from a
        # previous run: when this run's write failed, the field goes away
        monkeypatch.setattr(bench, "_require_backend", lambda: "cpu")
        monkeypatch.setattr(
            bench, "collect",
            lambda **kw: dict(_modern_result(), metric="m", unit="u"))
        bench.main(["--detail-out", "/nonexistent-dir/D.json",
                    "--partial-out", ""])
        cap = capsys.readouterr()
        out = json.loads(cap.out.strip().splitlines()[-1])
        assert "detail_file" not in out
        assert "detail write" in cap.err

    def test_main_prints_compact_line_last(self, monkeypatch, tmp_path,
                                           capsys):
        monkeypatch.setattr(bench, "_require_backend", lambda: "cpu")
        monkeypatch.setattr(
            bench, "collect",
            lambda **kw: dict(_modern_result(), metric="m",
                              unit="configs/s/chip"))
        detail = tmp_path / "BENCH_DETAIL.json"
        bench.main(["--detail-out", str(detail), "--partial-out", ""])
        lines = capsys.readouterr().out.strip().splitlines()
        out = json.loads(lines[-1])
        assert len(lines[-1]) <= bench.COMPACT_LINE_MAX
        assert out["detail_file"] == str(detail)
        # the detail file holds the FULL result the line only points at
        full = json.loads(detail.read_text())
        assert full["detail"]["tiers"]["fused_27_brackets"]["median"] == 100.0


class TestExitCode:
    """A bench whose tier raised prints its line, keeps the finished
    tiers' numbers on disk — and FAILS."""

    def test_main_exits_nonzero_when_a_tier_raised(self, monkeypatch,
                                                   tmp_path, capsys):
        monkeypatch.setattr(bench, "_require_backend", lambda: "cpu")
        monkeypatch.setattr(
            bench, "collect",
            lambda **kw: dict(_modern_result(), metric="m", unit="u",
                              error={"cnn": "RuntimeError: boom"}))
        detail = tmp_path / "D.json"
        with pytest.raises(SystemExit) as ei:
            bench.main(["--detail-out", str(detail), "--partial-out", ""])
        assert ei.value.code == 1
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert "boom" in out["error"]
        assert json.loads(detail.read_text())["error"]["cnn"]

    def test_collect_exception_propagates(self, monkeypatch):
        monkeypatch.setattr(bench, "_require_backend", lambda: "cpu")

        def boom(**kw):
            raise RuntimeError("collect died")

        monkeypatch.setattr(bench, "collect", boom)
        with pytest.raises(RuntimeError, match="collect died"):
            bench.main(["--partial-out", ""])
