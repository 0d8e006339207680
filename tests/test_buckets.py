"""Shape-bucketed fused brackets (ops/buckets.py) — ISSUE 6 tentpole.

Covers the three claims the bucket layer makes:

* **geometry**: a schedule's shapes collapse into a small geometric bucket
  set (the 36-bracket 1..729 rotation -> <= 6 programs, acceptance bar);
* **exactness**: the traced-count bucketed kernel reproduces the plain
  fused bracket's promotions and losses bit-for-bit, at any entry stage,
  crashes included — and the donated dynamic sweep matches the undonated
  one bit-for-bit (the donation contract);
* **ledger**: an end-to-end bucketed 27-bracket BOHB sweep compiles
  exactly ``len(bucket_set)`` fused programs (read back from the
  tracked_jit compile ledger), with the AOT precompile overlapped with
  sampling, and produces results identical to the unbucketed path.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.ops.bracket import BracketPlan, hyperband_schedule
from hpbandster_tpu.ops.buckets import (
    build_bucket_set,
    fused_sh_bracket_bucketed,
    make_bucketed_bracket_fn,
    precompile_buckets,
    slice_member_stages,
)
from hpbandster_tpu.ops.fused import fused_sh_bracket


def quad_eval(vec, budget):
    return jnp.sum(jnp.square(vec - 0.3)) / budget


@pytest.fixture
def rng():
    return np.random.default_rng(7)


# --------------------------------------------------------------- geometry
class TestBucketGeometry:
    def test_36_bracket_rotation_needs_at_most_6_programs(self):
        """Acceptance bar (ISSUE 6): the 10k-scale 36-bracket 1..729
        rotation — 6 distinct multi-stage shapes today, one compile each —
        buckets into <= 6 (actually 3) programs."""
        plans = hyperband_schedule(36, 1, 729, 3)
        bs = build_bucket_set(plans)
        distinct_shapes = {
            (p.num_configs, p.budgets) for p in plans if len(p.num_configs) >= 2
        }
        assert len(distinct_shapes) == 6
        assert len(bs.buckets) <= 6
        assert len(bs.buckets) == 3
        # every fusable shape is placed
        assert set(bs.assignment) == distinct_shapes

    def test_buckets_cover_members_and_align_at_tail(self):
        plans = hyperband_schedule(36, 1, 729, 3)
        bs = build_bucket_set(plans)
        for (num_configs, budgets), (bi, entry) in bs.assignment.items():
            bucket = bs.buckets[bi]
            assert budgets == bucket.budgets[entry:]
            for s, k in enumerate(num_configs):
                assert bucket.widths[entry + s] >= k
        # widths are non-increasing pow2 (floor 8) — the geometric claim
        for b in bs.buckets:
            assert all(
                w1 >= w2 for w1, w2 in zip(b.widths, b.widths[1:])
            )
            assert all(w >= 8 and (w & (w - 1)) == 0 for w in b.widths)

    def test_single_stage_plans_are_excluded(self):
        plans = [BracketPlan((5,), (9.0,)), BracketPlan((9, 3), (3.0, 9.0))]
        bs = build_bucket_set(plans)
        assert ((5,), (9.0,)) not in bs.assignment
        assert ((9, 3), (3.0, 9.0)) in bs.assignment

    def test_foreign_ladder_gets_singleton_bucket(self):
        """A shape whose budgets are NOT a suffix of its depth-group's
        deepest member must not mis-align — it gets its own program."""
        plans = [
            BracketPlan((9, 3, 1), (1.0, 3.0, 9.0)),
            BracketPlan((8, 2), (5.0, 25.0)),  # alien ladder
        ]
        bs = build_bucket_set(plans)
        bi, entry = bs.assignment[((8, 2), (5.0, 25.0))]
        assert entry == 0
        assert bs.buckets[bi].budgets == (5.0, 25.0)

    def test_mesh_pads_stage0_width(self):
        plans = [BracketPlan((9, 3, 1), (1.0, 3.0, 9.0))]
        bs = build_bucket_set(plans, mesh_size=24)
        assert bs.buckets[0].widths[0] % 24 == 0


# --------------------------------------------------------------- exactness
class TestBucketedKernelParity:
    def _reference(self, eval_fn, X, plan):
        fn = jax.jit(
            lambda v: [
                (s[0], s[1])
                for s in fused_sh_bracket(
                    eval_fn, v, plan.num_configs, plan.budgets
                )
            ]
        )
        return [(np.asarray(i), np.asarray(l)) for i, l in fn(X)]

    def _assert_stage_equal(self, member, ref):
        assert len(member) == len(ref)
        for (mi, ml), (ri, rl) in zip(member, ref):
            assert np.array_equal(np.asarray(mi), ri)
            assert np.array_equal(np.asarray(ml), rl, equal_nan=True)

    def test_entry0_member_matches_plain_fused_bracket(self, rng):
        plans = hyperband_schedule(27, 1, 9, 3)
        bs = build_bucket_set(plans)
        plan = plans[0]  # deepest shape
        bi, entry = bs.lookup(plan.num_configs, plan.budgets)
        assert entry == 0
        runner = make_bucketed_bracket_fn(quad_eval, bs.buckets[bi])
        X = rng.uniform(size=(plan.num_configs[0], 2)).astype(np.float32)
        self._assert_stage_equal(
            runner.run_member(X, plan, entry),
            self._reference(quad_eval, X, plan),
        )

    def test_later_entry_member_matches_plain_fused_bracket(self, rng):
        plans = hyperband_schedule(27, 1, 9, 3)
        bs = build_bucket_set(plans)
        plan = next(p for p in plans if len(p.budgets) == 2)
        bi, entry = bs.lookup(plan.num_configs, plan.budgets)
        assert entry > 0  # the shallower member enters mid-bucket
        runner = make_bucketed_bracket_fn(quad_eval, bs.buckets[bi])
        X = rng.uniform(size=(plan.num_configs[0], 2)).astype(np.float32)
        self._assert_stage_equal(
            runner.run_member(X, plan, entry),
            self._reference(quad_eval, X, plan),
        )

    def test_crashed_configs_rank_behind_clean_ahead_of_pad(self, rng):
        def crashy(vec, budget):
            val = jnp.sum(jnp.square(vec - 0.3))
            return jnp.where(vec[0] > 0.5, jnp.nan, val)

        plans = hyperband_schedule(27, 1, 9, 3)
        bs = build_bucket_set(plans)
        plan = plans[0]
        bi, entry = bs.lookup(plan.num_configs, plan.budgets)
        runner = make_bucketed_bracket_fn(crashy, bs.buckets[bi])
        X = np.linspace(0, 1, plan.num_configs[0])[:, None].repeat(2, 1)
        member = runner.run_member(X.astype(np.float32), plan, entry)
        self._assert_stage_equal(
            member, self._reference(crashy, X.astype(np.float32), plan)
        )
        # no pad row (index >= n0) ever surfaces in member results
        for idx, _ in member:
            assert (np.asarray(idx) < plan.num_configs[0]).all()

    def test_all_crashed_wave_still_promotes_real_rows_not_pads(self):
        """Worse than NaN: every REAL row crashed. Crash rank must still
        beat the pad rows' +inf — promotions pick (crashed) real configs,
        never padding."""
        def all_nan(vec, budget):
            return jnp.nan * jnp.sum(vec)

        plan = BracketPlan((9, 3, 1), (1.0, 3.0, 9.0))
        bs = build_bucket_set([plan])
        bi, entry = bs.lookup(plan.num_configs, plan.budgets)
        runner = make_bucketed_bracket_fn(all_nan, bs.buckets[bi])
        X = np.random.default_rng(0).uniform(size=(9, 2)).astype(np.float32)
        member = runner.run_member(X, plan, entry)
        for idx, losses in member:
            assert (np.asarray(idx) < 9).all()
            assert np.isnan(np.asarray(losses)).all()

    def test_kernel_under_jit_directly(self, rng):
        """fused_sh_bracket_bucketed is a plain traceable function —
        usable under jit without the runner plumbing."""
        plan = BracketPlan((5, 1), (1.0, 3.0))
        bs = build_bucket_set([plan])
        bucket = bs.buckets[0]
        X = np.zeros((bucket.widths[0], 2), np.float32)
        X[:5] = rng.uniform(size=(5, 2)).astype(np.float32)
        counts = np.array([5, 1], np.int32)
        stages = jax.jit(
            lambda v, c: [
                (s[0], s[1])
                for s in fused_sh_bracket_bucketed(quad_eval, v, c, bucket)
            ]
        )(X, counts)
        member = slice_member_stages(
            [(np.asarray(i), np.asarray(l)) for i, l in stages], plan, 0
        )
        self._assert_stage_equal(
            member, self._reference(quad_eval, X[:5], plan)
        )


# ------------------------------------------------------------- AOT + ledger
class TestAOTAndLedger:
    def test_precompile_then_dispatch_compiles_once_per_bucket(self):
        from hpbandster_tpu.obs.runtime import get_compile_tracker

        def eval_fn(vec, budget):  # fresh closure: unique cache identity
            return jnp.sum(jnp.square(vec - 0.25)) * budget

        plans = hyperband_schedule(9, 1, 9, 3)
        bs = build_bucket_set(plans)
        tracker = get_compile_tracker()
        tracker.reset()
        handle = precompile_buckets(eval_fn, bs, d=2, background=False)
        assert handle.errors == []
        led = tracker.snapshot()["functions"]
        assert led["fused_bucket"]["compiles"] == len(bs.buckets)
        # dispatches reuse the AOT executables: zero additional compiles
        rng = np.random.default_rng(1)
        for plan in plans:
            placed = bs.lookup(plan.num_configs, plan.budgets)
            if placed is None:
                continue
            bi, entry = placed
            runner = make_bucketed_bracket_fn(eval_fn, bs.buckets[bi])
            X = rng.uniform(size=(plan.num_configs[0], 2)).astype(np.float32)
            runner.run_member(X, plan, entry)
        led = tracker.snapshot()["functions"]
        assert led["fused_bucket"]["compiles"] == len(bs.buckets)

    def test_background_precompile_overlaps_and_serializes_with_dispatch(self):
        """The background thread and a racing dispatch must agree on one
        compile (the runner's lock), and wait() reports completion."""
        def eval_fn(vec, budget):
            return jnp.sum(vec) * budget

        plan = BracketPlan((9, 3), (1.0, 3.0))
        bs = build_bucket_set([plan])
        handle = precompile_buckets(eval_fn, bs, d=2, background=True)
        runner = make_bucketed_bracket_fn(eval_fn, bs.buckets[0])
        X = np.ones((9, 2), np.float32)
        member = runner.run_member(X, plan, 0)  # may race the thread
        assert handle.wait(timeout=60.0)
        assert handle.errors == []
        assert len(member) == 2
        # exactly one executable exists despite the race
        assert runner._compiled is not None

    def test_dim_mismatch_is_loud(self):
        def eval_fn(vec, budget):
            return jnp.sum(vec)

        plan = BracketPlan((5, 1), (1.0, 3.0))
        bs = build_bucket_set([plan])
        runner = make_bucketed_bracket_fn(eval_fn, bs.buckets[0])
        runner.ensure_compiled(3)
        with pytest.raises(ValueError, match="compiled for d="):
            runner.ensure_compiled(4)


# ----------------------------------------------------------------- end2end
class TestBucketedExecutorE2E:
    def _run_sweep(self, bucket_brackets, eval_fn=None, n_iterations=27,
                   seed=0):
        from hpbandster_tpu.optimizers import BOHB
        from hpbandster_tpu.parallel import BatchedExecutor, VmapBackend
        from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

        cs = branin_space(seed=seed)
        ex = BatchedExecutor(
            VmapBackend(eval_fn or branin_from_vector), cs,
            bucket_brackets=bucket_brackets,
        )
        opt = BOHB(
            configspace=cs, run_id=f"bkt{bucket_brackets}", executor=ex,
            min_budget=1, max_budget=9, eta=3, seed=seed,
        )
        res = opt.run(n_iterations=n_iterations)
        opt.shutdown()
        runs = sorted(
            (r.config_id, r.budget,
             None if r.loss is None else round(float(r.loss), 6))
            for r in res.get_all_runs()
        )
        return runs, ex

    def test_27_bracket_sweep_compiles_exactly_bucket_set_programs(self):
        """Satellite (ISSUE 6): the bucketed 27-bracket fused sweep
        compiles exactly ``len(bucket_set)`` fused programs — ledger-based
        — and its results are identical to the unbucketed path."""
        from hpbandster_tpu.obs.runtime import get_compile_tracker
        from hpbandster_tpu.workloads.toys import branin_from_vector

        # fresh closure: the process-wide bucket cache keys on eval_fn
        # identity, and earlier suite tests sweep branin through the same
        # bucket shapes — a shared fn would satisfy every lookup and show
        # zero compiles here
        def eval_fn(v, b):
            return branin_from_vector(v, b)

        tracker = get_compile_tracker()
        tracker.reset()
        runs_b, ex_b = self._run_sweep(bucket_brackets=True, eval_fn=eval_fn)
        led = tracker.snapshot()["functions"]
        assert ex_b._bucket_set is not None
        n_buckets = len(ex_b._bucket_set.buckets)
        assert led["fused_bucket"]["compiles"] == n_buckets
        # the per-shape program never compiled: bucketing replaced it
        assert "fused_bracket" not in led
        assert ex_b.bucketed_brackets_run > 0
        assert ex_b.bucketed_brackets_run == ex_b.fused_brackets_run

        runs_u, _ = self._run_sweep(bucket_brackets=False)
        assert runs_b == runs_u

    def test_prepare_schedule_is_optional(self):
        """An executor that never hears the schedule still works — every
        bracket falls back to the per-shape fused program."""
        from hpbandster_tpu.parallel import BatchedExecutor, VmapBackend
        from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

        cs = branin_space(seed=1)
        ex = BatchedExecutor(VmapBackend(branin_from_vector), cs)
        assert ex._bucket_runner_for(
            {"num_configs": (9, 3, 1), "budgets": (1.0, 3.0, 9.0)}
        ) is None


# ----------------------------------------------------------------- donation
class TestDonationContract:
    def _sweep_pair(self, caps_n=64, donate_env=None, monkeypatch=None):
        from hpbandster_tpu.ops.sweep import (
            build_space_codec,
            make_fused_sweep_fn,
            plan_additions,
        )
        from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

        if donate_env is not None:
            monkeypatch.setenv("HPB_SWEEP_DONATE", donate_env)
        cs = branin_space(seed=3)
        codec = build_space_codec(cs)
        plans = hyperband_schedule(3, 1, 9, 3)
        caps = {float(b): caps_n for b in plan_additions(plans)}
        d = int(codec.kind.shape[0])

        def mkargs():
            warm_v = {b: np.zeros((caps[b], d), np.float32) for b in caps}
            warm_l = {b: np.full((caps[b],), np.inf, np.float32) for b in caps}
            warm_n = {b: np.int32(0) for b in caps}
            return warm_v, warm_l, warm_n

        def eval_fn(v, b):  # fresh closure: no executable-cache bleed
            return branin_from_vector(v, b)

        plain = make_fused_sweep_fn(
            eval_fn, plans, codec, dynamic_counts=True, capacities=caps,
        )
        state_fn = make_fused_sweep_fn(
            eval_fn, plans, codec, dynamic_counts=True, capacities=caps,
            return_state=True,
        )
        return plans, plain, state_fn, mkargs

    def _assert_outputs_equal(self, out_a, out_b):
        for a, b in zip(out_a, out_b):
            assert np.array_equal(
                np.asarray(a.vectors), np.asarray(b.vectors), equal_nan=True
            )
            assert np.array_equal(
                np.asarray(a.idx_packed), np.asarray(b.idx_packed)
            )
            assert np.array_equal(
                np.asarray(a.loss_packed), np.asarray(b.loss_packed),
                equal_nan=True,
            )

    def test_state_thread_matches_plain_sweep_bit_for_bit(self):
        """Satellite (ISSUE 6): the state-threading executable must never
        change results — same seed, bitwise-identical bracket outputs,
        and the returned state continues the sweep."""
        plans, plain, state_fn, mkargs = self._sweep_pair()
        out_u = plain(11, *mkargs())
        out_d, state = state_fn(11, *mkargs())
        self._assert_outputs_equal(out_u, out_d)
        out_2, state_2 = state_fn(12, *state)
        assert len(out_2) == len(plans)

    def test_forced_donation_matches_and_consumes(self, monkeypatch):
        """With donation forced on (the accelerator default;
        ops/sweep.py sweep_donation_safe keeps it off on CPU), results
        stay bit-identical and the donated
        inputs are CONSUMED (aliased in place, not copied)."""
        plans, plain, state_fn, mkargs = self._sweep_pair(
            caps_n=32, donate_env="1", monkeypatch=monkeypatch
        )
        out_u = plain(11, *mkargs())
        out_d, state = state_fn(11, *mkargs())
        self._assert_outputs_equal(out_u, out_d)
        obs_v, obs_l, counts = state
        out_2, _ = state_fn(12, obs_v, obs_l, counts)
        assert len(out_2) == len(plans)
        with pytest.raises(RuntimeError):
            np.asarray(list(obs_l.values())[0])

    def test_donation_gated_off_on_cpu_by_default(self, monkeypatch):
        from hpbandster_tpu.ops.sweep import _sweep_donation_safe

        monkeypatch.delenv("HPB_SWEEP_DONATE", raising=False)
        assert _sweep_donation_safe() is False  # suite runs on CPU
        monkeypatch.setenv("HPB_SWEEP_DONATE", "1")
        assert _sweep_donation_safe() is True
        monkeypatch.setenv("HPB_SWEEP_DONATE", "0")
        assert _sweep_donation_safe() is False

    def test_return_state_requires_dynamic_counts(self):
        from hpbandster_tpu.ops.sweep import (
            build_space_codec,
            make_fused_sweep_fn,
        )
        from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

        cs = branin_space(seed=3)
        codec = build_space_codec(cs)
        plans = hyperband_schedule(1, 1, 9, 3)
        with pytest.raises(ValueError, match="return_state"):
            make_fused_sweep_fn(
                branin_from_vector, plans, codec, return_state=True
            )

    def test_fused_bohb_chunked_threads_state_without_reupload(self):
        """The chunked FusedBOHB driver uploads warm state once (chunk 0)
        and threads it on-device afterward: warm_upload_bytes must drop
        to ~seed-size for every later same-capacity chunk."""
        from hpbandster_tpu.optimizers import FusedBOHB
        from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

        def eval_fn(v, b):  # fresh closure: no executable-cache bleed
            return branin_from_vector(v, b)

        opt = FusedBOHB(
            configspace=branin_space(seed=5), eval_fn=eval_fn,
            run_id="thread", min_budget=1, max_budget=9, eta=3, seed=5,
        )
        # chunk == rotation period (max_SH_iter=3): consecutive chunks run
        # the same shapes, so the dynamic executable is reused and the
        # device state can thread across the boundary
        opt.run(n_iterations=9, chunk_brackets=3)
        opt.shutdown()
        stats = opt.run_stats
        assert len(stats) == 3
        assert stats[0]["warm_upload_bytes"] > 0
        same_cap = [
            s for s in stats[1:]
            if s["compile_cache_hit"]  # same executable = same capacities
        ]
        assert same_cap, "no chunk reused the executable; cannot test thread"
        for s in same_cap:
            # only the seed scalar crosses the link
            assert s["warm_upload_bytes"] <= 16
