"""Resident outer-loop sweep tests (ISSUE 12).

The tentpole contract: the resident sweep (``ops/sweep.py``
``resident=True`` — one traced rotation round driven by an in-trace
``lax.scan``) is BIT-IDENTICAL to the unrolled dynamic tier on the same
seed and capacities: same sampled configs, same promotion decisions
(``idx_packed``), same losses, same incumbent — at 1k and 10k configs on
the conftest 8-device CPU mesh. On top of the kernel bar, the FusedBOHB
driver must replay identical Results AND identical promotion journals,
and the incumbent-only payload must be flat in config count (the d2h
claim measured, not asserted).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu import obs
from hpbandster_tpu.ops.bracket import (
    BracketPlan,
    hyperband_schedule,
    mesh_aligned_plan,
)
from hpbandster_tpu.ops.sweep import (
    ResidentSweepOutputs,
    build_space_codec,
    make_fused_sweep_fn,
    plan_additions,
    pow2_capacities,
    resident_rotation,
    unstack_resident_outputs,
)
from hpbandster_tpu.parallel.mesh import config_mesh
from hpbandster_tpu.parallel.multihost import run_sharded_fused_sweep
from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space


def _caps_for(plans):
    """The chunked/resident drivers' shared pow2-floor-256 capacity map
    (ONE definition: ops.sweep.pow2_capacities — the drivers use it)."""
    return pow2_capacities(plan_additions(plans))


def _empty_warm(caps, d):
    wv = {b: np.zeros((c, d), np.float32) for b, c in caps.items()}
    wl = {b: np.full(c, np.inf, np.float32) for b, c in caps.items()}
    wn = {b: np.int32(0) for b in caps}
    return wv, wl, wn


def _assert_outputs_bitwise(a, b):
    assert len(a) == len(b)
    for i, (oa, ob) in enumerate(zip(a, b)):
        for name, la, lb in zip(oa._fields, oa, ob):
            if la is None or lb is None:
                # an eval_fn without device counters leaves that field empty
                assert la is lb, f"bracket {i} leaf {name} diverged"
                continue
            assert np.array_equal(
                np.asarray(la), np.asarray(lb), equal_nan=True
            ), f"bracket {i} leaf {name} diverged"


class TestResidentRotation:
    def test_periodic_schedule(self):
        plans = hyperband_schedule(6, 1, 9, 3)
        period, n_rounds, n_tail = resident_rotation(plans)
        assert (period, n_rounds, n_tail) == (3, 2, 0)

    def test_partial_tail(self):
        plans = hyperband_schedule(7, 1, 9, 3)
        period, n_rounds, n_tail = resident_rotation(plans)
        assert (period, n_rounds, n_tail) == (3, 2, 1)
        assert period * n_rounds + n_tail == 7

    def test_aperiodic_falls_back_to_one_round(self):
        plans = [
            BracketPlan((9, 3), (1.0, 3.0)),
            BracketPlan((4, 2), (1.0, 3.0)),
            BracketPlan((5,), (3.0,)),
        ]
        period, n_rounds, n_tail = resident_rotation(plans)
        assert (period, n_rounds, n_tail) == (3, 1, 0)

    def test_single_bracket(self):
        plans = [BracketPlan((9, 3), (1.0, 3.0))]
        assert resident_rotation(plans) == (1, 1, 0)

    def test_empty_raises(self):
        with pytest.raises(ValueError):
            resident_rotation([])

    def test_requires_dynamic_counts(self):
        cs = branin_space(seed=0)
        codec = build_space_codec(cs)
        plans = hyperband_schedule(2, 1, 9, 3)
        with pytest.raises(ValueError, match="dynamic_counts"):
            make_fused_sweep_fn(
                branin_from_vector, plans, codec, resident=True
            )


class TestResidentBitParity:
    """resident == unrolled dynamic, leaf for leaf, on the same seed."""

    def _parity(self, n_configs, incumbent_only, model, seed=11,
                n_brackets=5, num_samples=8):
        cs = branin_space(seed=0)
        codec = build_space_codec(cs)
        d = int(codec.kind.shape[0])
        mesh = config_mesh(jax.devices())
        n_shards = int(np.asarray(mesh.devices).size)
        plan = mesh_aligned_plan(n_configs, 1, 9, 3, n_shards)
        plans = [plan] * n_brackets
        caps = _caps_for(plans)
        kwargs = dict(
            dynamic_counts=True,
            capacities=caps,
            mesh=mesh,
            shard_sampling=True,
            incumbent_only=incumbent_only,
            # model off = HyperBand mode (the honest 100k-1M mode); on =
            # the full in-trace KDE refit path (kept small: the parity
            # target is bitwise equality, not model throughput)
            min_points_in_model=None if model else 2**30,
            num_samples=num_samples,
        )
        fn_u = make_fused_sweep_fn(branin_from_vector, plans, codec, **kwargs)
        fn_r = make_fused_sweep_fn(
            branin_from_vector, plans, codec, resident=True, **kwargs
        )
        wv, wl, wn = _empty_warm(caps, d)
        out_u = jax.device_get(fn_u(np.uint32(seed), wv, wl, wn))
        wv, wl, wn = _empty_warm(caps, d)
        out_r = jax.device_get(fn_r(np.uint32(seed), wv, wl, wn))
        return out_u, out_r, plans

    def test_full_outputs_1k_mesh(self):
        """1k configs on the 8-device mesh: vectors, model-based mask,
        promotion indices and losses all bitwise across every bracket
        (HyperBand mode — the honest at-scale proposal path)."""
        out_u, out_r, plans = self._parity(
            1024, incumbent_only=False, model=False
        )
        assert isinstance(out_r, ResidentSweepOutputs)
        _, n_rounds, _ = resident_rotation(plans)
        flat_r = unstack_resident_outputs(out_r, n_rounds)
        _assert_outputs_bitwise(out_u, flat_r)

    def test_full_outputs_model_on_small(self):
        """The in-trace KDE refit path (dynamic_proposals) bit-matches
        across the scan/unrolled program shapes — small widths keep the
        CPU compile inside the tier-1 wall; the refit math is identical
        at any width."""
        out_u, out_r, plans = self._parity(
            128, incumbent_only=False, model=True, n_brackets=4
        )
        _, n_rounds, _ = resident_rotation(plans)
        flat_r = unstack_resident_outputs(out_r, n_rounds)
        _assert_outputs_bitwise(out_u, flat_r)
        # the parity must not be vacuous: the model gate actually opened
        assert any(np.asarray(o.model_based).any() for o in flat_r)

    @pytest.mark.slow
    def test_full_outputs_10k_mesh_model_on(self):
        out_u, out_r, plans = self._parity(
            10_240, incumbent_only=False, model=True, n_brackets=3
        )
        _, n_rounds, _ = resident_rotation(plans)
        _assert_outputs_bitwise(
            out_u, unstack_resident_outputs(out_r, n_rounds)
        )

    def test_incumbent_only_10k_mesh(self):
        """10k configs, incumbent-only: the whole payload is bitwise."""
        inc_u, inc_r, _ = self._parity(
            10_240, incumbent_only=True, model=False, n_brackets=3
        )
        for name, la, lb in zip(inc_u._fields, inc_u, inc_r):
            assert np.array_equal(
                np.asarray(la), np.asarray(lb), equal_nan=True
            ), f"incumbent leaf {name} diverged"

    def test_partial_tail_round_parity(self):
        """A schedule whose last round is partial (tail brackets run
        unrolled after the scan) still bit-matches the unrolled tier."""
        cs = branin_space(seed=0)
        codec = build_space_codec(cs)
        d = int(codec.kind.shape[0])
        plans = hyperband_schedule(5, 1, 9, 3)  # period 3 -> tail of 2
        assert resident_rotation(plans)[2] == 2
        caps = _caps_for(plans)
        kwargs = dict(dynamic_counts=True, capacities=caps)
        fn_u = make_fused_sweep_fn(branin_from_vector, plans, codec, **kwargs)
        fn_r = make_fused_sweep_fn(
            branin_from_vector, plans, codec, resident=True, **kwargs
        )
        wv, wl, wn = _empty_warm(caps, d)
        out_u = jax.device_get(fn_u(np.uint32(5), wv, wl, wn))
        wv, wl, wn = _empty_warm(caps, d)
        out_r = jax.device_get(fn_r(np.uint32(5), wv, wl, wn))
        _, n_rounds, _ = resident_rotation(plans)
        _assert_outputs_bitwise(
            out_u, unstack_resident_outputs(out_r, n_rounds)
        )


class TestResidentDriver:
    """FusedBOHB.run(resident=True): identical Result AND identical
    promotion journal to the unrolled dynamic tier."""

    def _journaled_run(self, seed, **run_kwargs):
        from hpbandster_tpu.optimizers import FusedBOHB

        records = []
        detach = obs.get_bus().subscribe(records.append)
        try:
            cs = branin_space(seed=0)
            opt = FusedBOHB(
                configspace=cs, eval_fn=branin_from_vector,
                run_id="resident-parity", min_budget=1, max_budget=9,
                eta=3, seed=seed,
            )
            res = opt.run(n_iterations=6, **run_kwargs)
        finally:
            detach()
        journal = [
            {
                # drop measured per-candidate wall costs: they are
                # timing, not decision content, and two identical runs
                # measure different nanoseconds
                k: v for k, v in e.fields.items() if k != "costs"
            } | {"event": e.name}
            for e in records
            if e.name in ("promotion_decision", "config_sampled")
        ]
        return res, journal

    def test_result_and_journal_parity(self):
        res_u, j_u = self._journaled_run(21, dynamic_counts=True)
        res_r, j_r = self._journaled_run(21, resident=True)
        runs_u = sorted(
            (r.config_id, r.budget, r.loss) for r in res_u.get_all_runs()
        )
        runs_r = sorted(
            (r.config_id, r.budget, r.loss) for r in res_r.get_all_runs()
        )
        assert runs_u == runs_r
        assert res_u.get_incumbent_id() == res_r.get_incumbent_id()
        assert json.dumps(j_u, sort_keys=True, default=str) == json.dumps(
            j_r, sort_keys=True, default=str
        )
        assert len(j_u) > 0, "parity vacuous: no audit records captured"

    def test_resident_rejects_chunking(self):
        from hpbandster_tpu.optimizers import FusedBOHB

        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="rej",
            min_budget=1, max_budget=9, eta=3, seed=0,
        )
        with pytest.raises(ValueError, match="chunk"):
            opt.run(n_iterations=3, resident=True, chunk_brackets=2)
        with pytest.raises(ValueError, match="dynamic"):
            opt.run(n_iterations=3, resident=True, dynamic_counts=False)

    def test_run_incumbent_flat_payload_and_audit(self):
        """The incumbent-only driver's d2h bill and host-sync count do
        not scale with the schedule, and the payload is journaled as a
        sweep_incumbent record with the byte accounting attached."""
        from hpbandster_tpu.optimizers import FusedBOHB

        records = []
        detach = obs.get_bus().subscribe(records.append)
        try:
            bills = {}
            for n_iter in (3, 6):
                cs = branin_space(seed=0)
                opt = FusedBOHB(
                    configspace=cs, eval_fn=branin_from_vector,
                    run_id=f"inc-{n_iter}", min_budget=1, max_budget=9,
                    eta=3, seed=13,
                )
                out = opt.run_incumbent(n_iterations=n_iter)
                t = out["transfers"]
                bills[n_iter] = (
                    t["transfers_h2d"] + t["transfers_d2h"],
                )
                assert out["incumbent"]["loss"] == out["incumbent"]["loss"]
        finally:
            detach()
        # host-sync count is constant in schedule length: one dispatch,
        # one fetch, whatever the bracket count
        assert bills[3] == bills[6]
        incs = [r for r in records if r.name == "sweep_incumbent"]
        assert len(incs) == 2
        for rec in incs:
            assert rec.fields["d2h_bytes"] > 0
            assert rec.fields["host_syncs"] == bills[3][0]
            assert len(rec.fields["per_bracket_loss"]) in (3, 6)
        # the gauges the exporter scrapes
        g = obs.get_metrics().snapshot()["gauges"]
        assert g["sweep.transfer_bytes.d2h"] > 0
        assert g["sweep.host_syncs"] == float(bills[6][0])


class TestResidentSharded:
    """run_sharded_fused_sweep(resident=True): flat d2h/h2d, constant
    host syncs, incumbent parity with the non-resident program."""

    def test_flat_d2h_and_h2d_across_config_counts(self):
        cs = branin_space(seed=0)
        mesh = config_mesh(jax.devices())
        bills = {}
        for n in (1024, 8192):
            r = run_sharded_fused_sweep(
                branin_from_vector, cs, n_configs=n, min_budget=1,
                max_budget=9, eta=3, mesh=mesh, seed=3, n_brackets=3,
                resident=True,
            )
            bills[n] = (r["d2h_bytes"], r["h2d_bytes"], r["host_syncs"])
            assert len(r["chunks"]) == 1  # one dispatch for the schedule
            assert r["resident"] is True
        assert bills[1024] == bills[8192], (
            "host-link bill scaled with config count: %r" % (bills,)
        )
        # the d2h payload is the incumbent alone: vector + loss +
        # bracket + per-bracket bests
        d = 2  # branin
        expect = d * 4 + 4 + 4 + 3 * 4
        assert bills[1024][0] == expect
        assert bills[1024][1] == 4  # one uint32 seed

    def test_incumbent_matches_unrolled_program(self):
        """HyperBand mode: the resident scan and the unrolled static
        program consume identical RNG, so the incumbent is bitwise
        equal across the two program shapes."""
        cs = branin_space(seed=0)
        mesh = config_mesh(jax.devices())
        kw = dict(
            n_configs=1024, min_budget=1, max_budget=9, eta=3,
            mesh=mesh, seed=9, n_brackets=4,
        )
        a = run_sharded_fused_sweep(branin_from_vector, cs, resident=True, **kw)
        b = run_sharded_fused_sweep(branin_from_vector, cs, **kw)
        assert a["incumbent"]["loss"] == b["incumbent"]["loss"]
        assert a["incumbent"]["vector"] == b["incumbent"]["vector"]
        assert a["incumbent"]["bracket"] == b["incumbent"]["bracket"]
        assert a["evaluations"] == b["evaluations"]

    def test_resident_rejects_chunking(self):
        cs = branin_space(seed=0)
        with pytest.raises(ValueError, match="chunk"):
            run_sharded_fused_sweep(
                branin_from_vector, cs, n_configs=64, mesh=config_mesh(
                    jax.devices()
                ), resident=True, chunk_brackets=2,
            )


class TestResidentReplayAndExport:
    def test_replay_incumbent_section(self):
        """`obs replay` re-scores a journal whose only decision payload
        is the resident incumbent record — deterministically."""
        from hpbandster_tpu.promote.replay import (
            format_replay,
            replay_records,
        )

        rec = {
            "event": "sweep_incumbent",
            "loss": 1.5,
            "bracket": 2,
            "per_bracket_loss": [2.0, None, 1.5, 3.0],
            "d2h_bytes": 28,
            "host_syncs": 5,
        }
        rep = replay_records([rec], "successive_halving")
        rep2 = replay_records([dict(rec)], "successive_halving")
        assert json.dumps(rep, sort_keys=True) == json.dumps(
            rep2, sort_keys=True
        )
        inc = rep["incumbent"]
        assert inc["inconsistent"] == 0
        row = inc["sweeps"][0]
        assert row["rank1_regret"] == 0.0
        assert row["best_bracket"] == 2
        assert row["consistent"] is True
        assert "resident incumbent payload" in format_replay(rep)

    def test_replay_flags_inconsistent_incumbent(self):
        from hpbandster_tpu.promote.replay import replay_records

        rec = {
            "event": "sweep_incumbent",
            "loss": 9.0,  # worse than the recorded bracket bests
            "bracket": 0,
            "per_bracket_loss": [2.0, 1.0],
        }
        rep = replay_records([rec], "asha")
        assert rep["incumbent"]["inconsistent"] == 1
        assert rep["incumbent"]["sweeps"][0]["rank1_regret"] == 8.0

    def test_transfer_gauge_export_round_trip(self):
        """sweep.transfer_bytes.{h2d,d2h} render as ONE labeled family
        and survive the strict parser."""
        from hpbandster_tpu.obs.export import (
            parse_prometheus_text,
            render_snapshot,
        )

        snap = {
            "counters": {},
            "gauges": {
                "sweep.transfer_bytes.h2d": 4.0,
                "sweep.transfer_bytes.d2h": 28.0,
                "sweep.host_syncs": 5.0,
            },
            "histograms": {},
        }
        text = render_snapshot(snap)
        fams = parse_prometheus_text(text)
        fam = fams["hpbandster_sweep_transfer_bytes"]
        got = {
            lab["direction"]: val for lab, val in fam["samples"]
        }
        assert got == {"h2d": 4.0, "d2h": 28.0}
        assert fams["hpbandster_sweep_host_syncs"]["samples"] == [({}, 5.0)]

    def test_summarize_host_link_section(self):
        from hpbandster_tpu.obs.summarize import (
            format_summary,
            summarize_records,
        )

        recs = [
            {"event": "sweep_chunk", "t_wall": 1.0, "duration_s": 0.5,
             "h2d_bytes": 100, "d2h_bytes": 50, "host_syncs": 3},
            {"event": "sweep_incumbent", "t_wall": 2.0,
             "h2d_bytes": 4, "d2h_bytes": 28, "host_syncs": 5},
            {"event": "job_finished", "t_wall": 3.0},
        ]
        s = summarize_records(recs)
        assert s["host_link"] == {
            "records": 2, "h2d_bytes": 104, "d2h_bytes": 78,
            "host_syncs": 8,
        }
        assert "host link:" in format_summary(s)

    def test_roofline_transfer_section(self):
        from hpbandster_tpu.obs.metrics import MetricsRegistry
        from hpbandster_tpu.obs.profile import (
            format_roofline,
            roofline_report,
            transfer_summary,
        )

        reg = MetricsRegistry()
        reg.counter("runtime.transfer_bytes_h2d").inc(100)
        reg.counter("runtime.transfers_h2d").inc(2)
        reg.gauge("sweep.transfer_bytes.d2h").set(28.0)
        reg.gauge("sweep.host_syncs").set(5.0)
        t = transfer_summary(reg)
        assert t["process_total"]["transfer_bytes_h2d"] == 100
        assert t["last_sweep"]["d2h_bytes"] == 28.0
        rep = roofline_report(transfers=t)
        assert rep["transfers"] is t
        text = format_roofline(rep)
        assert "host link (process)" in text
        assert "host link (last sweep)" in text
