"""Mesh-sharded fused sweep tests (ISSUE 10).

Parity bars: the sharded kernels on a 1-DEVICE mesh are bit-identical to
the unsharded kernels (promotions, crash-NaN rank order, entry>0 members,
sampled configs), and a multi-device CPU mesh (the conftest-forced
8-device host platform) preserves results under uneven ``_mesh_pad``
padding. The driver (``parallel/multihost.py``) is exercised end to end:
incumbent-only fetch, chunked state threading, per-device balance gauges.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.obs.metrics import get_metrics
from hpbandster_tpu.ops.bracket import (
    BracketPlan,
    hyperband_schedule,
    mesh_aligned_plan,
)
from hpbandster_tpu.ops.buckets import (
    build_bucket_set,
    make_bucketed_bracket_fn,
)
from hpbandster_tpu.ops.fused import fused_sh_bracket, shard_rows
from hpbandster_tpu.ops.sweep import (
    build_space_codec,
    make_fused_sweep_fn,
    random_unit,
    random_unit_sharded,
)
from hpbandster_tpu.parallel.mesh import (
    config_mesh,
    pad_to_shards,
    shard_count,
)
from hpbandster_tpu.parallel.multihost import (
    publish_device_balance,
    run_sharded_fused_sweep,
)
from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space


def quad_eval(vec, budget):
    return jnp.sum(jnp.square(vec - 0.3)) * budget


def crashy_eval(vec, budget):
    val = jnp.sum(jnp.square(vec - 0.3)) * budget
    return jnp.where(vec[0] > 0.6, jnp.nan, val)


@pytest.fixture
def rng():
    return np.random.default_rng(11)


def _stages_equal(a, b):
    assert len(a) == len(b)
    for (ia, la), (ib, lb) in zip(a, b):
        assert np.array_equal(np.asarray(ia), np.asarray(ib))
        assert np.array_equal(np.asarray(la), np.asarray(lb), equal_nan=True)


# ----------------------------------------------------------- mesh helpers
class TestMeshHelpers:
    def test_shard_count_and_pad(self):
        mesh = config_mesh(jax.devices())
        assert shard_count(mesh, "config") == 8
        assert shard_count(None) == 1
        assert shard_count(mesh, "nonexistent") == 1
        assert pad_to_shards(9, mesh) == 16
        assert pad_to_shards(16, mesh) == 16
        assert pad_to_shards(5, None) == 5

    def test_mesh_aligned_plan_geometry(self):
        plan = mesh_aligned_plan(1000, 1, 9, 3, mesh_size=8)
        # every stage shards evenly; profile non-increasing; full ladder
        assert all(n % 8 == 0 for n in plan.num_configs)
        assert list(plan.budgets) == [1.0, 3.0, 9.0]
        assert all(
            a >= b for a, b in zip(plan.num_configs, plan.num_configs[1:])
        )
        assert plan.num_configs[0] >= 1000
        # pow2 count on a pow2 mesh: zero padding
        assert mesh_aligned_plan(1024, 1, 9, 3, 8).num_configs[0] == 1024


# ------------------------------------------------- kernel parity (buckets)
class TestShardedKernelParity:
    """The satellite parity matrix: 1-device mesh bitwise-equals the
    unsharded kernel; multi-device meshes (even the uneven-padding case)
    preserve promotions, crash ranking and entry>0 members."""

    def _member_vs_unsharded(self, eval_fn, plans, mesh, mesh_size, rng):
        bs_ref = build_bucket_set(plans)
        bs_mesh = build_bucket_set(plans, mesh_size=mesh_size)
        for plan in plans:
            if len(plan.num_configs) < 2:
                continue
            bi, entry = bs_ref.lookup(plan.num_configs, plan.budgets)
            bj, entry_m = bs_mesh.lookup(plan.num_configs, plan.budgets)
            X = rng.uniform(size=(plan.num_configs[0], 2)).astype(np.float32)
            ref = make_bucketed_bracket_fn(
                eval_fn, bs_ref.buckets[bi]
            ).run_member(X, plan, entry)
            got = make_bucketed_bracket_fn(
                eval_fn, bs_mesh.buckets[bj], mesh=mesh
            ).run_member(X, plan, entry_m)
            _stages_equal(got, ref)

    def test_one_device_mesh_bitwise_equals_unsharded(self, rng):
        mesh1 = config_mesh(jax.devices()[:1])
        plans = hyperband_schedule(27, 1, 9, 3)
        self._member_vs_unsharded(quad_eval, plans, mesh1, 1, rng)

    def test_one_device_mesh_crash_rank_order(self, rng):
        mesh1 = config_mesh(jax.devices()[:1])
        plans = [BracketPlan((9, 3, 1), (1.0, 3.0, 9.0))]
        self._member_vs_unsharded(crashy_eval, plans, mesh1, 1, rng)

    def test_uneven_mesh_pad_preserves_results(self, rng):
        """3 devices: pow2 bucket widths are NOT multiples of 3, so
        _mesh_pad pads every stage unevenly vs the pow2 profile — results
        must still match the unsharded kernel bitwise (incl. an entry>0
        member and crashed rows)."""
        mesh3 = config_mesh(jax.devices()[:3])
        plans = hyperband_schedule(27, 1, 9, 3)
        self._member_vs_unsharded(crashy_eval, plans, mesh3, 3, rng)

    def test_full_mesh_parity(self, rng):
        mesh8 = config_mesh(jax.devices())
        plans = hyperband_schedule(9, 1, 9, 3)
        self._member_vs_unsharded(quad_eval, plans, mesh8, 8, rng)

    def test_mesh_pad_pads_every_stage(self):
        plans = [BracketPlan((9, 3, 1), (1.0, 3.0, 9.0))]
        bs = build_bucket_set(plans, mesh_size=3)
        assert all(w % 3 == 0 for w in bs.buckets[0].widths)

    def test_fused_bracket_mesh_kwarg_is_identity(self, rng):
        """fused_sh_bracket with a mesh produces bitwise the same stages
        as without (sharding constraints never change values)."""
        mesh8 = config_mesh(jax.devices())
        X = rng.uniform(size=(16, 2)).astype(np.float32)
        plain = jax.jit(
            lambda v: [
                (s[0], s[1])
                for s in fused_sh_bracket(
                    crashy_eval, v, (16, 8, 1), (1.0, 3.0, 9.0)
                )
            ]
        )(X)
        sharded = jax.jit(
            lambda v: [
                (s[0], s[1])
                for s in fused_sh_bracket(
                    crashy_eval, v, (16, 8, 1), (1.0, 3.0, 9.0),
                    mesh=mesh8, axis="config",
                )
            ]
        )(X)
        _stages_equal(
            [(np.asarray(i), np.asarray(l)) for i, l in sharded],
            [(np.asarray(i), np.asarray(l)) for i, l in plain],
        )


# ----------------------------------------------------- sharded PRNG / sweep
class TestShardedSampling:
    def test_one_shard_is_bitwise_random_unit(self):
        codec = build_space_codec(branin_space(seed=0))
        key = jax.random.key(123)
        a = np.asarray(random_unit(codec, key, 64))
        b = np.asarray(random_unit_sharded(codec, key, 64, 1))
        assert np.array_equal(a, b)

    def test_shards_are_folded_blocks(self):
        """Shard s's block equals random_unit under fold_in(key, s) — the
        per-shard derivation contract the docs promise."""
        codec = build_space_codec(branin_space(seed=0))
        key = jax.random.key(7)
        out = np.asarray(random_unit_sharded(codec, key, 32, 4))
        for s in range(4):
            block = np.asarray(
                random_unit(codec, jax.random.fold_in(key, s), 8)
            )
            assert np.array_equal(out[s * 8:(s + 1) * 8], block)

    def test_non_divisible_raises(self):
        codec = build_space_codec(branin_space(seed=0))
        with pytest.raises(ValueError, match="mesh multiple"):
            random_unit_sharded(codec, jax.random.key(0), 10, 4)

    def test_one_device_mesh_sweep_bitwise_equals_unsharded(self):
        """The acceptance bar: sampled configs, promotions and losses of
        the sharded sweep on a 1-device mesh are bit-identical to the
        plain unsharded sweep program."""
        cs = branin_space(seed=0)
        codec = build_space_codec(cs)
        plan = mesh_aligned_plan(16, 1, 9, 3, 1)
        plain = make_fused_sweep_fn(
            branin_from_vector, [plan], codec, min_points_in_model=2**30
        )
        sharded = make_fused_sweep_fn(
            branin_from_vector, [plan], codec, min_points_in_model=2**30,
            mesh=config_mesh(jax.devices()[:1]), shard_sampling=True,
        )
        o_plain = jax.device_get(plain(np.uint32(42)))
        o_shard = jax.device_get(sharded(np.uint32(42)))
        for a, b in zip(o_plain, o_shard):
            # the leaves: an output's empty lane_counters field is none
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                assert np.array_equal(
                    np.asarray(x), np.asarray(y), equal_nan=True
                )

    def test_two_axis_mesh_non_divisible_bracket_bitwise(self):
        """Regression: a (config, model) mesh with a bracket that does NOT
        divide the config axis (9 rows over 4 shards) must match the
        unsharded sweep bitwise. The raw with_sharding_constraint the
        kernel used to apply here miscompiled under XLA CPU SPMD — every
        stage index came back scaled by the model-axis size (the
        __graft_entry__ dryrun crash), so the host-side observation fold
        indexed out of range."""
        from jax.sharding import Mesh

        cs = branin_space(seed=0)
        codec = build_space_codec(cs)
        plan = BracketPlan((9, 3, 1), (1.0, 3.0, 9.0))
        plain = make_fused_sweep_fn(
            branin_from_vector, [plan], codec, min_points_in_model=2**30
        )
        mesh2d = Mesh(
            np.array(jax.devices()).reshape(4, 2), ("config", "model")
        )
        sharded = make_fused_sweep_fn(
            branin_from_vector, [plan], codec, min_points_in_model=2**30,
            mesh=mesh2d,
        )
        o_plain = jax.device_get(plain(np.uint32(3)))[0]
        o_shard = jax.device_get(sharded(np.uint32(3)))[0]
        idx = np.asarray(o_shard.idx_packed)
        assert idx.min() >= 0 and idx.max() < plan.num_configs[0]
        assert np.array_equal(idx, np.asarray(o_plain.idx_packed))
        assert np.array_equal(
            np.asarray(o_shard.loss_packed),
            np.asarray(o_plain.loss_packed), equal_nan=True,
        )
        assert np.array_equal(
            np.asarray(o_shard.vectors), np.asarray(o_plain.vectors),
            equal_nan=True,
        )

    def test_incumbent_matches_full_outputs(self):
        cs = branin_space(seed=0)
        codec = build_space_codec(cs)
        mesh8 = config_mesh(jax.devices())
        plan = mesh_aligned_plan(512, 1, 9, 3, 8)
        kwargs = dict(
            min_points_in_model=2**30, mesh=mesh8, shard_sampling=True
        )
        full = make_fused_sweep_fn(branin_from_vector, [plan], codec,
                                   **kwargs)
        inc_fn = make_fused_sweep_fn(branin_from_vector, [plan], codec,
                                     incumbent_only=True, **kwargs)
        inc = jax.device_get(inc_fn(np.uint32(9)))
        outs = jax.device_get(full(np.uint32(9)))
        losses = np.asarray(outs[0].loss_packed)
        final = losses[-plan.num_configs[-1]:]
        assert np.isclose(float(np.asarray(inc.loss)), np.nanmin(final))
        assert int(np.asarray(inc.bracket)) == 0
        assert np.asarray(inc.per_bracket_loss).shape == (1,)

    def test_all_crashed_sweep_returns_nan_incumbent(self):
        cs = branin_space(seed=0)
        codec = build_space_codec(cs)
        mesh8 = config_mesh(jax.devices())
        plan = mesh_aligned_plan(64, 1, 9, 3, 8)

        def all_nan(vec, budget):
            return jnp.nan * jnp.sum(vec)

        fn = make_fused_sweep_fn(
            all_nan, [plan], codec, min_points_in_model=2**30,
            mesh=mesh8, shard_sampling=True, incumbent_only=True,
        )
        inc = jax.device_get(fn(np.uint32(1)))
        assert np.isnan(np.asarray(inc.loss))
        # still a real bracket's row, never garbage
        assert int(np.asarray(inc.bracket)) == 0


# ----------------------------------------------------------------- driver
class TestShardedDriver:
    def test_driver_end_to_end_with_gauges(self):
        mesh8 = config_mesh(jax.devices())
        r = run_sharded_fused_sweep(
            branin_from_vector, branin_space(seed=0), n_configs=1024,
            mesh=mesh8, seed=3,
        )
        assert r["n_shards"] == 8
        assert np.isfinite(r["incumbent"]["loss"])
        assert len(r["per_device_configs"]) == 8
        assert len(set(r["per_device_configs"])) == 1  # balanced
        assert r["balance_skew"] == 0.0
        g = get_metrics().snapshot()["gauges"]
        dev_ids = [d.id for d in jax.devices()]
        for i in dev_ids:
            assert g[f"sweep.device.{i}.configs"] == float(
                r["per_device_configs"][0]
            )
            assert f"sweep.device.{i}.pad_rows" in g
        assert g["sweep.balance_skew"] == 0.0

    def test_chunked_state_thread_with_model(self):
        """The PR-6 sweep state thread under sharding: a chunked run with
        the KDE on executes chunk to chunk with the observation state
        staying on device (one executable, incumbent improves or holds)."""
        mesh8 = config_mesh(jax.devices())
        r = run_sharded_fused_sweep(
            branin_from_vector, branin_space(seed=0), n_configs=64,
            n_brackets=4, chunk_brackets=2, model=True, mesh=mesh8, seed=5,
        )
        assert len(r["chunks"]) == 2
        assert np.isfinite(r["incumbent"]["loss"])

    def test_compile_count_within_bucket_set_bound(self):
        """Acceptance: compile count <= len(bucket_set) — one program per
        chunk shape, reused across repeats (process-wide cache)."""
        from hpbandster_tpu.obs.runtime import get_compile_tracker

        def fresh_eval(vec, budget):  # unique identity: no stale cache hits
            return jnp.sum(jnp.square(vec - 0.25)) * budget

        mesh8 = config_mesh(jax.devices())
        tracker = get_compile_tracker()
        led0 = tracker.snapshot()["total_compiles"]
        for s in (0, 1, 2):
            run_sharded_fused_sweep(
                fresh_eval, branin_space(seed=0), n_configs=256,
                mesh=mesh8, seed=s,
            )
        led1 = tracker.snapshot()["total_compiles"]
        # one chunk shape -> one program, repeats ride the cache
        assert led1 - led0 <= 1

    def test_publish_device_balance_validates_and_reports_skew(self):
        mesh = config_mesh(jax.devices()[:4])
        skew = publish_device_balance(mesh, "config", [10, 10, 10, 5],
                                      [0, 0, 0, 5])
        assert skew == pytest.approx(0.5)
        g = get_metrics().snapshot()["gauges"]
        assert g["sweep.balance_skew"] == pytest.approx(0.5)
        with pytest.raises(ValueError, match="shard"):
            publish_device_balance(mesh, "config", [1, 2], [0, 0])

    def test_multiprocess_executor_seam(self):
        """MultiHostBatchedExecutor.run_sharded_sweep drives the same
        driver over the (single-process) pod mesh."""
        from hpbandster_tpu.parallel import VmapBackend
        from hpbandster_tpu.parallel.multihost import (
            MultiHostBatchedExecutor,
        )

        cs = branin_space(seed=0)
        ex = MultiHostBatchedExecutor(
            VmapBackend(branin_from_vector), cs
        )
        r = ex.run_sharded_sweep(
            n_configs=256, mesh=config_mesh(jax.devices()), seed=2
        )
        assert np.isfinite(r["incumbent"]["loss"])
        assert ex.primary is True


# ------------------------------------------------ FusedBOHB streamed warm
class TestStreamedWarmUpload:
    def test_mesh_chunked_matches_unmeshed_and_threads_state(self):
        """The chunked driver on a mesh streams warm buffers per shard
        slice; results are identical to the no-mesh run (the dynamic tier
        samples mesh-independently) and the state thread still zeroes the
        warm upload after chunk 0."""
        from hpbandster_tpu.optimizers import FusedBOHB

        cs = branin_space(seed=0)

        def run(mesh):
            opt = FusedBOHB(
                configspace=cs, eval_fn=branin_from_vector,
                run_id=f"st-{mesh is not None}", min_budget=1, max_budget=9,
                eta=3, seed=1, mesh=mesh,
            )
            res = opt.run(n_iterations=4, chunk_brackets=2)
            return opt, res

        opt_m, res_m = run(config_mesh(jax.devices()))
        opt_p, res_p = run(None)
        lm = sorted(r.loss for r in res_m.get_all_runs() if r.loss is not None)
        lp = sorted(r.loss for r in res_p.get_all_runs() if r.loss is not None)
        assert np.allclose(lm, lp)
        # chunk 0 streams the (empty) warm buffers; chunk 1 hands the
        # device state straight back — upload shrinks to the seed
        uploads = [s["warm_upload_bytes"] for s in opt_m.run_stats]
        assert len(uploads) == 2
        assert uploads[1] <= 16
        assert uploads[0] > uploads[1]

    def test_stream_slices_never_materialize_full_buffers(self):
        """The streaming satellite's RSS contract, asserted structurally:
        every callback allocation is one shard slice (cap / n_shards
        rows), never the full capacity buffer."""
        from hpbandster_tpu.optimizers import FusedBOHB

        cs = branin_space(seed=0)
        mesh = config_mesh(jax.devices())
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="slice",
            min_budget=1, max_budget=9, eta=3, seed=2, mesh=mesh,
        )
        # seed some warm data so slices carry real content
        opt._warm_v[9.0] = np.arange(20, dtype=np.float32).reshape(10, 2)
        opt._warm_l[9.0] = np.linspace(0, 1, 10).astype(np.float32)
        caps = {1.0: 256, 9.0: 256}
        from hpbandster_tpu.ops.sweep_driver import stream_warm_buffers

        (warm_v, warm_l, warm_n), bytes_up = stream_warm_buffers(
            opt._warm_v, opt._warm_l, caps, 2, mesh, "config")
        assert bytes_up == sum(c * 2 * 4 + c * 4 + 4 for c in caps.values())
        for b, cap in caps.items():
            assert warm_v[b].shape == (cap, 2)
            # sharded over the 8-device config axis: each addressable
            # shard holds cap/8 rows — the bounded-RSS allocation unit
            shards = warm_v[b].addressable_shards
            assert len(shards) == 8
            assert all(s.data.shape[0] == cap // 8 for s in shards)
        # warm content survived the slice-wise construction bitwise
        v9 = np.asarray(warm_v[9.0])
        assert np.array_equal(v9[:10], opt._warm_v[9.0])
        assert np.all(v9[10:] == 0)
        l9 = np.asarray(warm_l[9.0])
        assert np.array_equal(l9[:10], opt._warm_l[9.0])
        assert np.all(np.isinf(l9[10:]))
        assert int(warm_n[9.0]) == 10
