"""Fused whole-sweep path: ops/sweep.py + optimizers/fused_bohb.py.

Parity targets: the device codec must agree with the host to_vector/
from_vector round-trip, the device KDE fit with the host BOHBKDE fit, and
the replayed bookkeeping must satisfy the same SH arithmetic the reference's
Result checks rely on (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpbandster_tpu.ops.bracket import hyperband_schedule
from hpbandster_tpu.ops.sweep import (
    build_space_codec,
    make_fused_sweep_fn,
    quantize_unit,
    random_unit,
)
from hpbandster_tpu.optimizers import FusedBOHB, RandomSearch
from hpbandster_tpu.space import (
    CategoricalHyperparameter,
    ConfigurationSpace,
    Constant,
    EqualsCondition,
    OrdinalHyperparameter,
    UniformFloatHyperparameter,
    UniformIntegerHyperparameter,
)

from tests.toys import branin_from_vector, branin_space


def mixed_space(seed=0) -> ConfigurationSpace:
    cs = ConfigurationSpace(seed=seed)
    cs.add_hyperparameters(
        [
            UniformFloatHyperparameter("lr", 1e-5, 1e-1, log=True),
            UniformFloatHyperparameter("mom", 0.0, 0.99),
            UniformFloatHyperparameter("drop", 0.0, 0.8, q=0.1),
            UniformIntegerHyperparameter("width", 16, 1024, log=True),
            UniformIntegerHyperparameter("layers", 1, 8),
            CategoricalHyperparameter("act", ["relu", "tanh", "gelu"]),
            OrdinalHyperparameter("bs", [32, 64, 128, 256]),
            Constant("algo", "sgd"),
        ]
    )
    return cs


class TestSpaceCodec:
    def test_quantize_matches_host_roundtrip(self):
        cs = mixed_space()
        codec = build_space_codec(cs)
        rng = np.random.default_rng(0)
        # raw unit vectors, with categorical dims holding raw indices
        n = 256
        u = rng.random((n, cs.dim)).astype(np.float32)
        cards = codec.cards
        for j in range(cs.dim):
            if codec.kind[j] == 2:
                u[:, j] = rng.integers(0, max(cards[j], 1), size=n)
            if codec.kind[j] == 3:
                u[:, j] = 0.0
        q_dev = np.asarray(quantize_unit(codec, jnp.asarray(u)))
        for i in range(n):
            host = cs.to_vector(dict(cs.from_vector(q_dev[i].astype(np.float64))))
            np.testing.assert_allclose(q_dev[i], host, atol=2e-6, err_msg=f"row {i}")

    def test_quantize_is_idempotent(self):
        cs = mixed_space()
        codec = build_space_codec(cs)
        u = np.random.default_rng(1).random((64, cs.dim)).astype(np.float32)
        q1 = np.asarray(quantize_unit(codec, jnp.asarray(u)))
        q2 = np.asarray(quantize_unit(codec, jnp.asarray(q1)))
        np.testing.assert_allclose(q1, q2, atol=2e-6)

    def test_random_unit_respects_kinds(self):
        cs = mixed_space()
        codec = build_space_codec(cs)
        v = np.asarray(random_unit(codec, jax.random.key(0), 512))
        for j in range(cs.dim):
            if codec.kind[j] in (0, 1):
                assert (0 <= v[:, j]).all() and (v[:, j] <= 1).all()
            elif codec.kind[j] == 2:
                assert set(np.unique(v[:, j])) <= set(
                    float(x) for x in range(codec.cards[j])
                )
            else:
                assert (v[:, j] == 0).all()

    def test_conditional_space_compiles(self):
        # conditions are supported on-device via compile_active_mask
        # (VERDICT r1: this used to assert rejection — stale)
        from hpbandster_tpu.ops.sweep import compile_active_mask

        cs = ConfigurationSpace(seed=0)
        a = CategoricalHyperparameter("a", ["x", "y"])
        b = UniformFloatHyperparameter("b", 0, 1)
        cs.add_hyperparameters([a, b])
        cs.add_condition(EqualsCondition(b, a, "x"))
        codec = build_space_codec(cs)
        mask_fn = compile_active_mask(cs, codec)
        q = quantize_unit(codec, random_unit(codec, jax.random.key(0), 16))
        act = np.asarray(jax.vmap(mask_fn)(q))
        assert act.shape == (16, 2)
        assert act[:, 0].all()  # unconditional parent always active
        # child active exactly when parent decodes to choice "x" (index 0)
        ai = cs.get_hyperparameter_names().index("a")
        assert (act[:, 1] == (np.asarray(q)[:, ai] == 0)).all()

    def test_forbidden_mask_matches_host_is_forbidden(self):
        from hpbandster_tpu.ops.sweep import compile_forbidden_mask
        from hpbandster_tpu.space import (
            ForbiddenAndConjunction,
            ForbiddenEqualsClause,
            ForbiddenInClause,
        )

        cs = ConfigurationSpace(seed=0)
        a = CategoricalHyperparameter("a", ["x", "y", "z"])
        b = UniformIntegerHyperparameter("b", 1, 4)
        c = UniformFloatHyperparameter("c", 0.0, 1.0)
        cs.add_hyperparameters([a, b, c])
        cs.add_forbidden_clause(
            ForbiddenAndConjunction(
                ForbiddenEqualsClause(a, "x"), ForbiddenEqualsClause(b, 2)
            )
        )
        cs.add_forbidden_clause(ForbiddenInClause(b, [4]))
        codec = build_space_codec(cs)
        fb_fn = compile_forbidden_mask(cs, codec)

        q = np.asarray(
            quantize_unit(codec, random_unit(codec, jax.random.key(3), 256))
        )
        act = jnp.ones(q.shape, bool)
        dev = np.asarray(
            jax.vmap(lambda v, a: fb_fn(v, a))(jnp.asarray(q), act)
        )
        host = np.array(
            [cs.is_forbidden(dict(cs.from_vector(v))) for v in q]
        )
        np.testing.assert_array_equal(dev, host)
        assert host.any() and not host.all()  # fixture exercises both sides

    @pytest.mark.slow
    def test_fused_run_on_forbidden_space(self):
        from hpbandster_tpu.space import ForbiddenEqualsClause

        cs = ConfigurationSpace(seed=0)
        cs.add_hyperparameters(
            [
                UniformFloatHyperparameter("x", -5.0, 10.0),
                UniformFloatHyperparameter("y", 0.0, 15.0),
                CategoricalHyperparameter("arm", ["p", "q", "r"]),
            ]
        )
        cs.add_forbidden_clause(
            ForbiddenEqualsClause(cs.get_hyperparameter("arm"), "q")
        )

        def eval_fn(vec, budget):
            return branin_from_vector(vec[:2], budget) + vec[2]

        opt = FusedBOHB(
            configspace=cs, eval_fn=eval_fn, run_id="forbidden",
            min_budget=1, max_budget=9, eta=3, seed=0,
            min_points_in_model=5,
        )
        res = opt.run(n_iterations=3)
        opt.shutdown()
        runs = res.get_all_runs()
        assert len(runs) > 0
        id2c = res.get_id2config_mapping()
        # every evaluated config respects the forbidden clause (the device
        # resampler replicates host rejection-sampling semantics)
        for cid, entry in id2c.items():
            assert not cs.is_forbidden(entry["config"]), entry["config"]
            assert entry["config"]["arm"] in ("p", "r")

    def test_fused_run_on_conditional_space_matches_host_semantics(self):
        # VERDICT r2 #2: the fused tier's conditional support, end to end —
        # EqualsCondition on a categorical parent PLUS an order condition on
        # a numeric ordinal parent, through KDE-model-based brackets (the
        # conditional imputation path), with host-parity assertions on every
        # produced config's activity pattern.
        from hpbandster_tpu.ops.sweep import compile_active_mask
        from hpbandster_tpu.space import GreaterThanCondition

        cs = ConfigurationSpace(seed=0)
        x = UniformFloatHyperparameter("x", -5.0, 10.0)
        y = UniformFloatHyperparameter("y", 0.0, 15.0)
        opt_hp = CategoricalHyperparameter("opt", ["sgd", "adam"])
        mom = UniformFloatHyperparameter("momentum", 0.0, 0.99)
        depth = OrdinalHyperparameter("depth", [1, 2, 4, 8])
        extra = UniformFloatHyperparameter("extra", 0.0, 1.0)
        cs.add_hyperparameters([x, y, opt_hp, mom, depth, extra])
        cs.add_condition(EqualsCondition(mom, opt_hp, "sgd"))
        cs.add_condition(GreaterThanCondition(extra, depth, 2))

        names = cs.get_hyperparameter_names()
        i_mom, i_extra = names.index("momentum"), names.index("extra")

        def eval_fn(vec, budget):
            # inactive dims reach evaluation as 0.0 (host parity)
            return (
                branin_from_vector(vec[:2], budget)
                + 0.1 * vec[i_mom]
                + 0.05 * vec[i_extra]
            )

        opt = FusedBOHB(
            configspace=cs, eval_fn=eval_fn, run_id="conditional",
            min_budget=1, max_budget=9, eta=3, seed=3,
            min_points_in_model=6,
        )
        res = opt.run(n_iterations=3)
        opt.shutdown()

        runs = res.get_all_runs()
        assert len(runs) == 13 + 6 + 3  # SH arithmetic intact (eta=3, 1..9)
        id2c = res.get_id2config_mapping()
        mask_fn = compile_active_mask(cs, opt.codec)
        for cid, entry in id2c.items():
            cfg = entry["config"]
            # host activity semantics hold exactly: round-tripping through
            # the host codec neither prunes nor resurrects any key
            host_vec = cs.to_vector(cfg)
            assert dict(cs.from_vector(host_vec)) == cfg, cfg
            assert ("momentum" in cfg) == (cfg["opt"] == "sgd"), cfg
            assert ("extra" in cfg) == (cfg["depth"] > 2), cfg
            # device activity mask agrees with the host NaN pattern
            q = jnp.asarray(np.nan_to_num(host_vec, nan=0.0), jnp.float32)
            dev_active = np.asarray(mask_fn(q))
            np.testing.assert_array_equal(
                dev_active, ~np.isnan(host_vec), err_msg=str(cfg)
            )
        # the KDE engaged on the conditional space (imputation path traced
        # AND executed): later brackets carry model-based picks
        assert any(
            e["config_info"].get("model_based_pick") for e in id2c.values()
        )

    def test_order_condition_on_categorical_parent_rejected(self):
        # a categorical's decoded number is its choice index; comparing a
        # raw value against an index would be silently wrong on device
        from hpbandster_tpu.ops.sweep import compile_active_mask
        from hpbandster_tpu.space import GreaterThanCondition

        cs = ConfigurationSpace(seed=0)
        a = CategoricalHyperparameter("a", [4, 2, 8])
        b = UniformFloatHyperparameter("b", 0, 1)
        cs.add_hyperparameters([a, b])
        cs.add_condition(GreaterThanCondition(b, a, 4))
        codec = build_space_codec(cs)
        with pytest.raises(ValueError, match="categorical"):
            compile_active_mask(cs, codec)


class TestDeviceKDEFit:
    def test_fit_matches_host_bohbkde(self):
        from hpbandster_tpu.models.bohb_kde import BOHBKDE
        from hpbandster_tpu.ops.sweep import _fit_kde_pair_device

        cs = branin_space(seed=0)
        gen = BOHBKDE(configspace=cs, seed=0)
        rng = np.random.default_rng(2)
        n = 40
        vecs = rng.random((n, cs.dim))
        losses = rng.normal(size=n)

        # host fit
        budget = 9.0
        gen.configs[budget] = [v for v in vecs]
        gen.losses[budget] = list(losses)
        gen._fit_kde_pair(budget)
        host_good, host_bad = gen.kde_models[budget]

        n_good = max(gen.min_points_in_model, (gen.top_n_percent * n) // 100)
        n_bad = max(
            gen.min_points_in_model, ((100 - gen.top_n_percent) * n) // 100
        )
        dev_good, dev_bad = _fit_kde_pair_device(
            jnp.asarray(vecs, jnp.float32),
            jnp.asarray(losses, jnp.float32),
            n_good,
            n_bad,
            jnp.asarray(cs.cardinalities()),
            gen.min_bandwidth,
        )
        # same observation rows (host pads to capacity; compare masked rows)
        hg = host_good.data[host_good.mask > 0]
        np.testing.assert_allclose(
            np.sort(np.asarray(dev_good.data), axis=0),
            np.sort(hg, axis=0),
            atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(dev_good.bw),
            host_good.bw,
            rtol=2e-4,
        )
        hb = host_bad.data[host_bad.mask > 0]
        np.testing.assert_allclose(
            np.sort(np.asarray(dev_bad.data), axis=0), np.sort(hb, axis=0), atol=1e-5
        )
        np.testing.assert_allclose(np.asarray(dev_bad.bw), host_bad.bw, rtol=2e-4)


class TestFusedSweep:
    def test_structure_matches_sh_arithmetic(self):
        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="t",
            min_budget=1, max_budget=9, eta=3, seed=0,
        )
        res = opt.run(n_iterations=4)
        plans = hyperband_schedule(4, 1, 9, 3)
        runs = res.get_all_runs()
        assert len(runs) == sum(p.total_evaluations for p in plans)
        # per-bracket, per-budget counts match the plan
        for b_i, plan in enumerate(plans):
            for k, budget in zip(plan.num_configs, plan.budgets):
                got = [
                    r for r in runs if r.config_id[0] == b_i and r.budget == budget
                ]
                assert len(got) == k, (b_i, budget)
        assert res.get_incumbent_id() is not None

    def test_promotions_follow_losses(self):
        """Each promoted set must be the top-k of the previous stage."""
        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="t2",
            min_budget=1, max_budget=9, eta=3, seed=3,
        )
        res = opt.run(n_iterations=2)
        runs = res.get_all_runs()
        plans = hyperband_schedule(2, 1, 9, 3)
        for b_i, plan in enumerate(plans):
            for s in range(len(plan.num_configs) - 1):
                cur = sorted(
                    (r for r in runs
                     if r.config_id[0] == b_i and r.budget == plan.budgets[s]),
                    key=lambda r: r.loss,
                )
                nxt = {
                    r.config_id
                    for r in runs
                    if r.config_id[0] == b_i and r.budget == plan.budgets[s + 1]
                }
                k = plan.num_configs[s + 1]
                top_k_losses = {r.config_id for r in cur[:k]}
                # identical loss ties can permute; compare by loss values
                assert len(nxt) == k
                assert max(r.loss for r in cur if r.config_id in nxt) <= (
                    cur[k].loss if len(cur) > k else np.inf
                ) or nxt == top_k_losses

    def test_crashed_configs_masked_not_promoted(self):
        def crashy(vec, budget):
            loss = branin_from_vector(vec, budget)
            return jnp.where(vec[0] < 0.3, jnp.nan, loss)

        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=crashy, run_id="t3",
            min_budget=1, max_budget=9, eta=3, seed=4,
        )
        res = opt.run(n_iterations=3)
        runs = res.get_all_runs()
        assert len(runs) > 0
        crashed = [r for r in runs if r.loss is None]
        clean = [r for r in runs if r.loss is not None]
        assert clean, "all configs crashed — test space wrong"
        # a crashed stage-0 config must never appear at a later budget unless
        # the stage had no finite alternatives
        plans = hyperband_schedule(3, 1, 9, 3)
        for r in crashed:
            b_i = r.config_id[0]
            plan = plans[b_i]
            s = plan.budgets.index(r.budget)
            if s + 1 < len(plan.budgets):
                n_finite = sum(
                    1 for x in runs
                    if x.config_id[0] == b_i and x.budget == r.budget
                    and x.loss is not None
                )
                promoted_ids = {
                    x.config_id for x in runs
                    if x.config_id[0] == b_i and x.budget == plan.budgets[s + 1]
                }
                if n_finite >= plan.num_configs[s + 1]:
                    assert r.config_id not in promoted_ids

    def test_model_based_picks_appear_after_enough_observations(self):
        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="t4",
            min_budget=1, max_budget=27, eta=3, seed=5,
        )
        res = opt.run(n_iterations=4)
        id2conf = res.get_id2config_mapping()
        mb = [
            cid for cid, c in id2conf.items()
            if c["config_info"].get("model_based_pick")
        ]
        assert len(mb) > 0, "no model-based proposals in 4 brackets"
        # bracket 0 samples before any observations exist: all random
        assert all(cid[0] > 0 for cid in mb)

    @pytest.mark.slow
    def test_beats_random_search(self):
        """Sample-efficiency sanity: fused BOHB's best should not lose badly
        to random search with the same total evaluation count."""
        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="t5",
            min_budget=1, max_budget=27, eta=3, seed=6,
        )
        res = opt.run(n_iterations=6)
        best_bohb = min(r.loss for r in res.get_all_runs() if r.loss is not None)
        rng = np.random.default_rng(6)
        n_total = len(res.get_all_runs())
        rand_vecs = cs.sample_vectors(n_total, rng=rng)
        rand_losses = [
            float(branin_from_vector(jnp.asarray(v, jnp.float32), 27.0))
            for v in rand_vecs
        ]
        assert best_bohb <= min(rand_losses) * 3 + 1.0

    def test_mesh_sharded_sweep(self):
        from hpbandster_tpu.parallel import config_mesh

        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="t6",
            min_budget=1, max_budget=9, eta=3, seed=7,
            mesh=config_mesh(jax.devices()),
        )
        res = opt.run(n_iterations=2)
        assert len(res.get_all_runs()) > 0
        assert all(np.isfinite(r.loss) for r in res.get_all_runs())

    @pytest.mark.slow
    def test_fused_sweep_on_cnn_training_workload(self):
        """Real training workload on the fused path: budget (= SGD steps)
        arrives as a concrete Python float inside the trace; the CNN's
        while_loop-based trainer consumes it unchanged."""
        from hpbandster_tpu.workloads import CNNConfig, cnn_space, make_cnn_eval_fn

        cfg = CNNConfig(
            image_size=8, channels=3, width=8, n_classes=4,
            n_train=64, n_val=32, batch_size=32,
        )
        cs = cnn_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=make_cnn_eval_fn(cfg), run_id="cnn-f",
            min_budget=1, max_budget=4, eta=2, seed=14,
        )
        res = opt.run(n_iterations=2)
        runs = res.get_all_runs()
        assert len(runs) > 0
        # extreme sampled hyperparameters may legitimately diverge to NaN
        # (-> crashed, loss None); the healthy majority must be finite
        finite = [r for r in runs if r.loss is not None]
        assert len(finite) >= len(runs) // 2
        assert all(np.isfinite(r.loss) for r in finite)

    def test_pallas_scorer_inside_sweep_interpreted(self):
        """The Pallas acquisition scorer traces INSIDE the sweep program
        (interpreter mode on CPU); structure and convergence unchanged."""
        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="pl-s",
            min_budget=1, max_budget=9, eta=3, seed=23, use_pallas=True,
        )
        # off-TPU, use_pallas=True auto-selects the interpreter
        assert opt.pallas_interpret
        res = opt.run(n_iterations=3)
        runs = res.get_all_runs()
        assert len(runs) > 0
        assert all(np.isfinite(r.loss) for r in runs if r.loss is not None)
        id2conf = res.get_id2config_mapping()
        assert any(
            c["config_info"].get("model_based_pick") for c in id2conf.values()
        ), "pallas-scored sweep produced no model-based picks"

    def test_pallas_scorer_on_mesh_matches_single_device(self):
        """On a mesh the scorer runs under a shard_map (each device its
        own candidate rows); without shard_sampling the sweep draws the
        same random stream either way, so mesh and single-device sweeps
        make the same proposals."""
        import jax

        from hpbandster_tpu.parallel import config_mesh

        def sweep(mesh):
            opt = FusedBOHB(
                configspace=branin_space(seed=0), eval_fn=branin_from_vector,
                run_id="pl-mesh", min_budget=1, max_budget=9, eta=3, seed=23,
                use_pallas=True, mesh=mesh,
            )
            res = opt.run(n_iterations=3)
            id2conf = res.get_id2config_mapping()
            return {
                cid: (c["config"]["x"], c["config"]["y"])
                for cid, c in id2conf.items()
            }

        single = sweep(None)
        meshed = sweep(config_mesh(jax.devices()))
        assert single.keys() == meshed.keys()
        for cid, xy in single.items():
            np.testing.assert_allclose(meshed[cid], xy, rtol=1e-4, atol=1e-4)

    @pytest.mark.slow
    def test_hartmann6_fused_sweep_converges(self):
        """BASELINE rung 2: 6-D Hartmann on the fused path."""
        from hpbandster_tpu.workloads.toys import (
            HARTMANN6_OPT,
            hartmann6_from_vector,
            hartmann6_space,
        )

        cs = hartmann6_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=hartmann6_from_vector, run_id="h6",
            min_budget=1, max_budget=27, eta=3, seed=18,
        )
        res = opt.run(n_iterations=6)
        best = min(r.loss for r in res.get_all_runs() if r.loss is not None)
        # optimum is ~-3.32; any decent sweep lands well below -1
        assert best < -1.0, f"poor convergence: best {best} vs {HARTMANN6_OPT}"

    def test_profile_dir_writes_trace(self, tmp_path):
        import os

        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="prof",
            min_budget=1, max_budget=9, eta=3, seed=19,
        )
        opt.run(n_iterations=1, profile_dir=str(tmp_path))
        found = []
        for root, _, files in os.walk(tmp_path):
            found.extend(files)
        assert found, "no profiler trace files written"

    @pytest.mark.slow
    def test_fused_sweep_on_resnet_workload(self):
        """BASELINE rung 5 on the fused path (tiny shapes)."""
        from hpbandster_tpu.workloads import (
            ResNetConfig,
            make_resnet_eval_fn,
            resnet_space,
        )

        cfg = ResNetConfig(
            image_size=8, channels=3, width=8, n_classes=4,
            n_train=64, n_val=32, batch_size=32, groups=4,
        )
        cs = resnet_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=make_resnet_eval_fn(cfg), run_id="rn-f",
            min_budget=1, max_budget=4, eta=2, seed=16,
        )
        res = opt.run(n_iterations=1)
        runs = res.get_all_runs()
        assert len(runs) > 0
        finite = [r for r in runs if r.loss is not None]
        assert len(finite) >= len(runs) // 2
        assert all(np.isfinite(r.loss) for r in finite)

    def test_viz_surface_accepts_fused_result(self):
        """The matplotlib analysis surface consumes fused Results unchanged."""
        import matplotlib

        matplotlib.use("Agg")
        from hpbandster_tpu.viz import (
            correlation_across_budgets,
            losses_over_time,
        )

        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="viz-f",
            min_budget=1, max_budget=9, eta=3, seed=17,
        )
        res = opt.run(n_iterations=2)
        fig, ax = losses_over_time(res.get_all_runs())
        assert ax.lines or ax.collections
        correlation_across_budgets(res)
        # data exports work on fused results too
        X, y, _ = res.get_fANOVA_data(cs)
        assert len(X) == len(y) > 0

    def test_result_logger_compatible(self, tmp_path):
        from hpbandster_tpu.core.result import (
            json_result_logger,
            logged_results_to_HBS_result,
        )

        cs = branin_space(seed=0)
        logger = json_result_logger(str(tmp_path), overwrite=True)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="t7",
            min_budget=1, max_budget=9, eta=3, seed=8, result_logger=logger,
        )
        res = opt.run(n_iterations=2)
        reloaded = logged_results_to_HBS_result(str(tmp_path))
        assert len(reloaded.get_all_runs()) == len(res.get_all_runs())

    def test_repeated_run_continues_bracket_rotation(self):
        """Master.run resume semantics: n_iterations is the TOTAL count;
        a second call runs only the remaining brackets with fresh ids."""
        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="t9",
            min_budget=1, max_budget=9, eta=3, seed=9,
        )
        opt.run(n_iterations=1)
        res = opt.run(n_iterations=2)
        assert len(opt.iterations) == 2
        assert {it.HPB_iter for it in opt.iterations} == {0, 1}
        plans = hyperband_schedule(2, 1, 9, 3)
        assert len(res.get_all_runs()) == sum(p.total_evaluations for p in plans)
        # brackets rotate: the second bracket has a different shape
        assert opt.iterations[0].num_configs != opt.iterations[1].num_configs

    def test_inf_loss_is_valid_not_crashed(self):
        """+inf = diverged-but-valid (maximally bad); only NaN crashes —
        matching register_result on the host path."""

        def diverging(vec, budget):
            loss = branin_from_vector(vec, budget)
            return jnp.where(vec[0] < 0.5, jnp.inf, loss)

        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=diverging, run_id="t10",
            min_budget=1, max_budget=9, eta=3, seed=10,
        )
        res = opt.run(n_iterations=2)
        runs = res.get_all_runs()
        inf_runs = [r for r in runs if r.loss is not None and np.isinf(r.loss)]
        assert inf_runs, "expected some diverged (+inf) runs"
        assert all(r.loss is not None for r in runs)

    @pytest.mark.slow
    def test_chunked_run_matches_structure_and_carries_model(self):
        """chunk_brackets=K: same SH arithmetic as the monolithic program,
        and later chunks' proposals are model-based (obs threaded through
        as warm data)."""
        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="chunk",
            min_budget=1, max_budget=27, eta=3, seed=24,
        )
        res = opt.run(n_iterations=4, chunk_brackets=2)
        plans = hyperband_schedule(4, 1, 27, 3)
        runs = res.get_all_runs()
        assert len(runs) == sum(p.total_evaluations for p in plans)
        id2conf = res.get_id2config_mapping()
        # chunk 2 (brackets 2-3) must see chunk 1's observations
        mb_late = [
            cid for cid, c in id2conf.items()
            if cid[0] >= 2 and c["config_info"].get("model_based_pick")
        ]
        assert mb_late, "second chunk made no model-based picks"

    def test_second_run_call_is_model_warm(self):
        """Master-parity: a later run() call's proposals see all earlier
        results from this instance."""
        cs = branin_space(seed=0)
        opt = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="rr",
            min_budget=1, max_budget=27, eta=3, seed=25,
        )
        opt.run(n_iterations=2)
        res = opt.run(n_iterations=3)
        id2conf = res.get_id2config_mapping()
        mb_third = [
            cid for cid, c in id2conf.items()
            if cid[0] == 2 and c["config_info"].get("model_based_pick")
        ]
        assert mb_third, "third bracket ignored earlier results"

    @pytest.mark.slow
    def test_warmstart_from_previous_result(self):
        """previous_result= seeds the device observation buffers: bracket 0
        of the warm run can already make model-based picks, and the old data
        rides into the Result under negative iteration ids."""
        cs = branin_space(seed=0)
        cold = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="w0",
            min_budget=1, max_budget=27, eta=3, seed=11,
        )
        prev = cold.run(n_iterations=3)
        warm = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="w1",
            min_budget=1, max_budget=27, eta=3, seed=12,
            previous_result=prev,
        )
        res = warm.run(n_iterations=1)
        id2conf = res.get_id2config_mapping()
        # old data present under negative iteration ids
        assert any(cid[0] < 0 for cid in id2conf)
        # bracket 0 already has model-based picks (cold run: impossible)
        mb0 = [
            cid for cid, c in id2conf.items()
            if cid[0] == 0 and c["config_info"].get("model_based_pick")
        ]
        assert mb0, "warm start did not enable model-based picks in bracket 0"

    @pytest.mark.slow
    def test_chained_warmstart_no_id_collision(self):
        """Warm-starting from an already-warm-started Result must never remap
        old ids onto live bracket ids."""
        cs = branin_space(seed=0)
        r1 = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="c0",
            min_budget=1, max_budget=9, eta=3, seed=20,
        ).run(n_iterations=1)
        r2 = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="c1",
            min_budget=1, max_budget=9, eta=3, seed=21, previous_result=r1,
        ).run(n_iterations=1)
        opt3 = FusedBOHB(
            configspace=cs, eval_fn=branin_from_vector, run_id="c2",
            min_budget=1, max_budget=9, eta=3, seed=22, previous_result=r2,
        )
        r3 = opt3.run(n_iterations=1)
        id2conf = r3.get_id2config_mapping()
        live = [cid for cid in id2conf if cid[0] >= 0]
        warm = [cid for cid in id2conf if cid[0] < 0]
        # 3 generations: live bracket-0 plus two warm generations, no overlap
        assert {cid[0] for cid in live} == {0}
        assert len({cid[0] for cid in warm}) == 2
        # live bracket data intact: 13 configs for the (9,3,1) bracket
        assert len(live) == 9
        assert len(r3.get_all_runs()) == 13 * 3

    def test_fused_hyperband_all_random(self):
        from hpbandster_tpu.optimizers import FusedHyperBand

        cs = branin_space(seed=0)
        opt = FusedHyperBand(
            configspace=cs, eval_fn=branin_from_vector, run_id="hb",
            min_budget=1, max_budget=27, eta=3, seed=13,
        )
        res = opt.run(n_iterations=4)
        id2conf = res.get_id2config_mapping()
        assert len(res.get_all_runs()) > 0
        assert not any(
            c["config_info"].get("model_based_pick") for c in id2conf.values()
        )

    def test_power_law_extrapolate_matches_host_model(self):
        from hpbandster_tpu.models.learning_curves import PowerLawModel
        from hpbandster_tpu.ops.bracket import power_law_extrapolate

        rng = np.random.default_rng(7)
        budgets = np.array([1.0, 3.0, 9.0], np.float32)
        host = PowerLawModel()
        # mix of decaying power-law curves and degenerate/increasing curves
        curves = []
        for _ in range(40):
            kind = rng.integers(3)
            if kind == 0:  # clean power law
                a, k, c = rng.uniform(0.5, 5), rng.uniform(0.2, 2), rng.uniform(0, 1)
                curves.append(a * budgets ** (-k) + c)
            elif kind == 1:  # increasing (diverging) curve
                curves.append(np.sort(rng.uniform(0, 5, size=3)))
            else:  # noisy arbitrary
                curves.append(rng.uniform(0, 5, size=3))
        losses = np.stack(curves).astype(np.float32)
        dev = np.asarray(power_law_extrapolate(budgets, losses, 27.0))
        for i in range(len(curves)):
            expect = host.predict(list(zip(budgets, losses[i])), 27.0)
            # f32 device fit vs f64 host fit: a few percent of slack
            np.testing.assert_allclose(
                dev[i], expect, rtol=5e-2, atol=2e-2, err_msg=f"curve {i}"
            )

    def test_power_law_short_history_falls_back_to_last(self):
        from hpbandster_tpu.ops.bracket import power_law_extrapolate

        budgets = np.array([1.0, 3.0], np.float32)
        losses = np.array([[5.0, 2.0], [1.0, 4.0]], np.float32)
        out = np.asarray(power_law_extrapolate(budgets, losses, 9.0))
        np.testing.assert_allclose(out, [2.0, 4.0])

    def test_fused_h2bo_promotes_by_extrapolation(self):
        """On an objective where curves cross, FusedH2BO's promotions
        differ from raw top-k while the structure stays intact."""
        from hpbandster_tpu.optimizers import FusedH2BO
        import jax.numpy as jnp

        def crossing(vec, budget):
            # a = initial level, k = decay speed: fast decayers start worse
            # but win at high budget
            a = 1.0 + vec[0] * 10.0
            k = 0.1 + vec[1] * 2.0
            return a * budget ** (-k)

        cs = branin_space(seed=0)
        # seed choice matters: the assertion needs the random stage-0 draw
        # to contain at least one actual curve crossing inside the top-k
        # boundary. Seed 30's draw happens to promote identically under
        # both rankers (extrapolation reorders only within the survivor
        # set); seed 0 has a boundary crossing.
        kwargs = dict(
            configspace=cs, eval_fn=crossing,
            min_budget=1, max_budget=81, eta=3, seed=0,
        )
        res_h2 = FusedH2BO(run_id="h2", **kwargs).run(n_iterations=1)
        res_sh = FusedBOHB(run_id="sh", **kwargs).run(n_iterations=1)

        def promoted_at(res, budget):
            return {r.config_id for r in res.get_all_runs() if r.budget == budget}

        # same stage-0 proposals (identical seed/rng stream) ...
        assert promoted_at(res_h2, 1.0) == promoted_at(res_sh, 1.0)
        # ... but the bracket structure holds for both
        plans = hyperband_schedule(1, 1, 81, 3)
        assert len(res_h2.get_all_runs()) == plans[0].total_evaluations
        assert len(res_sh.get_all_runs()) == plans[0].total_evaluations
        # and at least one later-stage promotion set differs (curves cross)
        later = [b for b in plans[0].budgets[2:]]
        assert any(
            promoted_at(res_h2, b) != promoted_at(res_sh, b) for b in later
        ), "LC extrapolation never changed a promotion on a crossing objective"

    def test_fused_h2bo_recovers_from_earlier_stage_crash(self):
        """A config whose stage-0 eval crashed but was promoted anyway (not
        enough clean survivors) must be ranked by merit at later stages,
        not crash-ranked forever (host H2BO parity)."""
        from hpbandster_tpu.optimizers import FusedH2BO
        import jax.numpy as jnp

        def flaky_at_1(vec, budget):
            # everything crashes at budget 1; later budgets give clean,
            # config-dependent losses
            return jnp.where(budget < 2.0, jnp.nan, vec[0] / budget)

        cs = branin_space(seed=0)
        opt = FusedH2BO(
            configspace=cs, eval_fn=flaky_at_1, run_id="h2-crash",
            min_budget=1, max_budget=9, eta=3, seed=31,
        )
        res = opt.run(n_iterations=1)  # bracket (9,3,1)@(1,3,9)
        runs = res.get_all_runs()
        at9 = [r for r in runs if r.budget == 9.0]
        assert len(at9) == 1
        # the final promotion ranked the clean budget-3 losses by merit:
        # the winner's loss must be the minimum of the stage-3 losses
        at3 = {r.config_id: r.loss for r in runs if r.budget == 3.0}
        assert all(v is not None for v in at3.values())
        winner = at9[0].config_id
        assert at3[winner] == min(at3.values())

    def test_fused_randomsearch_single_stage_at_max_budget(self):
        from hpbandster_tpu.optimizers import FusedRandomSearch

        cs = branin_space(seed=0)
        opt = FusedRandomSearch(
            configspace=cs, eval_fn=branin_from_vector, run_id="rs",
            min_budget=1, max_budget=27, eta=3, seed=15,
        )
        res = opt.run(n_iterations=3)
        runs = res.get_all_runs()
        assert len(runs) > 0
        assert all(r.budget == 27.0 for r in runs)
        # sized like the matching HyperBand brackets' stage 0
        plans = hyperband_schedule(3, 1, 27, 3)
        assert len(runs) == sum(p.num_configs[0] for p in plans)

    def test_non_scalar_eval_fn_rejected_at_construction(self):
        # without the construction-time eval_shape check this surfaced as
        # an opaque XLA broadcasting error from deep inside the sweep trace
        cs = branin_space(seed=0)
        with pytest.raises(ValueError, match="SCALAR loss"):
            FusedBOHB(
                configspace=cs, eval_fn=lambda vec, budget: vec,
                run_id="bad", min_budget=1, max_budget=9, eta=3, seed=0,
            )

    def test_pytree_eval_fn_rejected_at_construction(self):
        # the (loss, aux) pattern returns a TUPLE from eval_shape — the
        # check must see through pytrees, not just array shapes
        cs = branin_space(seed=0)
        with pytest.raises(ValueError, match="SCALAR loss"):
            FusedBOHB(
                configspace=cs,
                eval_fn=lambda vec, budget: (vec.sum(), {"aux": vec}),
                run_id="bad3", min_budget=1, max_budget=9, eta=3, seed=0,
            )

    def test_untraceable_eval_fn_rejected_at_construction(self):
        cs = branin_space(seed=0)

        def bad(vec, budget):
            return float(vec[0])  # concretizes a tracer

        # the banner names the attempt (abstract evaluation), not a
        # diagnosis — eval_shape also surfaces plain bugs inside eval_fn,
        # and "not traceable" would mislabel those (ADVICE r4)
        with pytest.raises(ValueError, match="failed under abstract"):
            FusedBOHB(
                configspace=cs, eval_fn=bad, run_id="bad2",
                min_budget=1, max_budget=9, eta=3, seed=0,
            )

    def test_deterministic_given_seed(self):
        cs = branin_space(seed=0)

        def best(seed):
            opt = FusedBOHB(
                configspace=cs, eval_fn=branin_from_vector, run_id="t8",
                min_budget=1, max_budget=9, eta=3, seed=seed,
            )
            res = opt.run(n_iterations=2)
            return sorted(
                (r.config_id, r.budget, r.loss) for r in res.get_all_runs()
            )

        assert best(42) == best(42)
        assert best(42) != best(43)


class TestDynamicCountSweep:
    """The dynamic-count fused tier (ops.sweep dynamic_counts=True): chunked
    runs reuse one executable until a capacity bucket doubles, where the
    static tier burns every chunk's observation counts into a fresh trace
    and pays one compile per chunk."""

    def _mk(self, seed=11, **kw):
        from hpbandster_tpu.optimizers import FusedBOHB

        return FusedBOHB(
            configspace=branin_space(seed=3), eval_fn=branin_from_vector,
            run_id="dyn", min_budget=1, max_budget=9, eta=3, seed=seed, **kw
        )

    def test_chunked_run_compiles_log_many_not_per_chunk(self):
        opt = self._mk()
        res = opt.run(n_iterations=9, chunk_brackets=3)
        opt.shutdown()
        assert len(opt.run_stats) == 3
        assert all(s["dynamic_counts"] for s in opt.run_stats)
        fresh = [s for s in opt.run_stats if not s["compile_cache_hit"]]
        # 3 chunks: chunk 2 grows the budget-1.0 bucket past chunk 1's, so
        # at most 2 fresh compiles are acceptable — the static tier pays 3
        assert len(fresh) <= 2
        # the sweep itself is a full, well-formed BOHB run
        plans = hyperband_schedule(9, 1, 9, 3)
        assert len(res.get_all_runs()) == sum(sum(p.num_configs) for p in plans)
        assert res.get_incumbent_id() is not None

    def test_later_chunk_failure_keeps_previous_chunk_replayed(
            self, monkeypatch):
        # the deferred replay must land even when the NEXT chunk dies
        # before dispatch (e.g. a bucket-doubling recompile failing):
        # otherwise a retry would re-execute a chunk whose observations
        # are already folded into the warm data
        from hpbandster_tpu.ops.sweep_driver import SweepDriver

        opt = self._mk(seed=53)
        orig = SweepDriver._compiled
        calls = {"n": 0}

        def failing(*a, **k):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("recompile OOM")
            return orig(*a, **k)

        monkeypatch.setattr(SweepDriver, "_compiled", failing)
        with pytest.raises(RuntimeError, match="recompile OOM"):
            opt.run(n_iterations=9, chunk_brackets=3)
        # chunk 1's brackets were replayed before the error propagated
        assert len(opt.iterations) == 3
        # and a retry continues from bracket 3 with no duplicates
        monkeypatch.setattr(SweepDriver, "_compiled", orig)
        res = opt.run(n_iterations=9, chunk_brackets=3)
        opt.shutdown()
        plans = hyperband_schedule(9, 1, 9, 3)
        assert len(res.get_all_runs()) == sum(
            sum(p.num_configs) for p in plans
        )
        assert len(opt.iterations) == 9

    def test_pipelined_replay_matches_sequential_and_records_overlap(
            self, tmp_path):
        # chunk k's host replay runs inside chunk k+1's device window
        # (replay_overlap_s) UNLESS a checkpoint_path forces sequential
        # replay; either way the replayed results are identical — replay
        # content never depends on when it runs
        def run_once(ckpt):
            opt = self._mk(seed=47)
            res = opt.run(n_iterations=9, chunk_brackets=3,
                          checkpoint_path=ckpt)
            opt.shutdown()
            rows = sorted(
                (r.config_id, r.budget, r.loss) for r in res.get_all_runs()
            )
            return rows, opt.run_stats

        piped, piped_stats = run_once(None)
        seq, seq_stats = run_once(str(tmp_path / "ck.pkl"))
        assert piped == seq
        # pipelined: every chunk but the first hides its predecessor's
        # replay; sequential: no chunk does
        assert [("replay_overlap_s" in s) for s in piped_stats] == [
            False, True, True]
        assert all("replay_overlap_s" not in s for s in seq_stats)

    def test_oversized_capacities_default_missing_budgets_to_empty(self):
        # ADVICE r4: a budget present in `capacities` but absent from the
        # warm inputs must trace as an empty count-0 buffer, not raise a
        # bare KeyError — exported-API callers may oversize the capacity
        # map for a later chunk's budgets
        from hpbandster_tpu.ops.sweep import plan_additions

        cs = branin_space(seed=3)
        codec = build_space_codec(cs)
        plans = hyperband_schedule(1, 1, 9, 3)
        adds = {float(b): int(n) for b, n in plan_additions(plans).items()}
        caps = dict(adds)
        caps[27.0] = 8  # extra budget: capacity, but no warm data for it
        fn = make_fused_sweep_fn(
            branin_from_vector, plans, codec, dynamic_counts=True,
            capacities=caps,
        )
        d = int(codec.kind.shape[0])
        warm_v = {b: jnp.zeros((caps[b], d), jnp.float32) for b in adds}
        warm_l = {b: jnp.full((caps[b],), jnp.inf, jnp.float32) for b in adds}
        warm_n = {b: jnp.zeros((), jnp.int32) for b in adds}
        outs = fn(0, warm_v, warm_l, warm_n)
        assert len(outs) == len(plans)
        assert np.isfinite(np.asarray(outs[0].loss_packed)).any()

    def test_partially_missing_warm_budget_is_named_not_keyerror(self):
        # a budget in SOME of the three warm dicts is a caller bug; the
        # trace must name it instead of raising a bare KeyError from
        # warm_v[b] (or silently dropping data when only warm_v has it)
        from hpbandster_tpu.ops.sweep import plan_additions

        cs = branin_space(seed=3)
        codec = build_space_codec(cs)
        plans = hyperband_schedule(1, 1, 9, 3)
        adds = {float(b): int(n) for b, n in plan_additions(plans).items()}
        fn = make_fused_sweep_fn(
            branin_from_vector, plans, codec, dynamic_counts=True,
            capacities=adds,
        )
        d = int(codec.kind.shape[0])
        warm_v = {b: jnp.zeros((adds[b], d), jnp.float32) for b in adds}
        warm_l = {b: jnp.full((adds[b],), jnp.inf, jnp.float32) for b in adds}
        warm_n = {b: jnp.zeros((), jnp.int32) for b in adds}
        victim = sorted(adds)[0]
        del warm_v[victim]  # in warm_n/warm_l but not warm_v
        with pytest.raises(ValueError, match="inconsistent warm inputs"):
            fn(0, warm_v, warm_l, warm_n)

    def test_forced_dynamic_matches_sh_arithmetic_and_is_deterministic(self):
        def run_once():
            opt = self._mk(seed=21)
            res = opt.run(n_iterations=4, dynamic_counts=True)
            opt.shutdown()
            return sorted(
                (r.config_id, r.budget, r.loss) for r in res.get_all_runs()
            )

        a, b = run_once(), run_once()
        assert a == b
        plans = hyperband_schedule(4, 1, 9, 3)
        assert len(a) == sum(sum(p.num_configs) for p in plans)

    def test_dynamic_model_gate_opens_like_static(self):
        # same observation-count gate arithmetic as the static tier and the
        # host model: with enough observations, later brackets must contain
        # model-based picks on BOTH tiers
        def model_picks(dynamic):
            opt = self._mk(seed=31, min_points_in_model=5)
            res = opt.run(n_iterations=6, dynamic_counts=dynamic)
            opt.shutdown()
            id2c = res.get_id2config_mapping()
            return sum(
                1 for e in id2c.values()
                if e["config_info"].get("model_based_pick")
            )

        n_dyn, n_static = model_picks(True), model_picks(False)
        assert n_dyn > 0 and n_static > 0

    def test_dynamic_never_model_shortcut_for_pure_random(self):
        # FusedHyperBand's unreachable gate must keep the dynamic tier
        # all-random (and not trace dead model math into the program)
        from hpbandster_tpu.optimizers import FusedHyperBand

        opt = FusedHyperBand(
            configspace=branin_space(seed=3), eval_fn=branin_from_vector,
            run_id="dyn-hb", min_budget=1, max_budget=9, eta=3, seed=41,
        )
        res = opt.run(n_iterations=4, chunk_brackets=2)
        opt.shutdown()
        assert all(s["dynamic_counts"] for s in opt.run_stats)
        id2c = res.get_id2config_mapping()
        assert not any(
            e["config_info"].get("model_based_pick") for e in id2c.values()
        )

    @pytest.mark.slow
    def test_dynamic_composes_warmstart_conditions_forbiddens(self):
        # the newest paths COMPOSED: a conditional space with a forbidden
        # clause, run chunked (dynamic tier), warm-started from a previous
        # Result — warm NaN-carrying vectors ride the capacity buffers into
        # the rank-masked imputing fit, forbiddens keep resampling in-trace,
        # and the old data still lands under negative iteration ids
        from hpbandster_tpu.space.forbidden import ForbiddenEqualsClause

        cs = ConfigurationSpace(seed=0)
        x = UniformFloatHyperparameter("x", -5.0, 10.0)
        arm = CategoricalHyperparameter("arm", ["p", "q", "r"])
        mom = UniformFloatHyperparameter("momentum", 0.0, 0.99)
        cs.add_hyperparameters([x, arm, mom])
        cs.add_condition(EqualsCondition(mom, arm, "p"))
        cs.add_forbidden_clause(ForbiddenEqualsClause(arm, "q"))

        def eval_fn(vec, budget):
            return vec[0] * vec[0] + 0.1 * vec[2] + 0.0 * budget

        def mk(seed, prev=None):
            return FusedBOHB(
                configspace=cs, eval_fn=eval_fn, run_id=f"dyn-mix-{seed}",
                min_budget=1, max_budget=9, eta=3, seed=seed,
                min_points_in_model=5, previous_result=prev,
            )

        cold = mk(71)
        prev = cold.run(n_iterations=3, chunk_brackets=2)
        cold.shutdown()
        warm = mk(72, prev=prev)
        res = warm.run(n_iterations=3, chunk_brackets=2)
        warm.shutdown()
        assert all(s["dynamic_counts"] for s in warm.run_stats)
        id2c = res.get_id2config_mapping()
        assert any(cid[0] < 0 for cid in id2c)  # warm data rode along
        live = {cid: e for cid, e in id2c.items() if cid[0] >= 0}
        assert any(
            e["config_info"].get("model_based_pick") for e in live.values()
        ), "warm start did not open the model gate on the dynamic tier"
        for entry in live.values():
            cfg = entry["config"]
            assert cfg["arm"] in ("p", "r")  # forbidden clause held
            assert ("momentum" in cfg) == (cfg["arm"] == "p"), cfg
            assert not cs.is_forbidden(cfg)

    def test_dynamic_warm_continuation_reuses_executable(self):
        # iterative continuation (run -> inspect -> run more) on the forced
        # dynamic tier: the second run() call's brackets cycle through the
        # same plan shapes within the same capacity bucket, so the warm
        # continuation REUSES the first call's executable — the static
        # trace would recompile at the new warm-observation counts
        opt = self._mk(seed=81, min_points_in_model=5)
        opt.run(n_iterations=3, dynamic_counts=True)
        res = opt.run(n_iterations=6, dynamic_counts=True)
        opt.shutdown()
        # the claim is ONLY that run 2 reuses run 1's executable — run 1
        # itself may hit the process-global cache if an earlier test built
        # the same sweep, so don't require it to have compiled fresh
        assert len(opt.run_stats) == 2
        assert opt.run_stats[1]["compile_cache_hit"]
        id2c = res.get_id2config_mapping()
        # restrict to the CONTINUATION's brackets (>=3) — the first call's
        # brackets already contain model picks, which would mask a
        # regression where run 2 drops the accumulated observations
        assert any(
            e["config_info"].get("model_based_pick")
            for cid, e in id2c.items() if cid[0] >= 3
        ), "continuation did not see the first call's observations"

    def test_dynamic_with_pallas_scorer_interpreted(self):
        # on a real TPU chunked FusedBOHB runs dynamic counts WITH the
        # Pallas scorer (default-on there) — trace that combination via the
        # interpreter: the kernel is mask-weighted, so capacity-padded KDEs
        # must score like exact ones
        opt = self._mk(seed=61, use_pallas=True, min_points_in_model=5)
        assert opt.pallas_interpret
        res = opt.run(n_iterations=4, chunk_brackets=2)
        opt.shutdown()
        assert all(s["dynamic_counts"] for s in opt.run_stats)
        runs = res.get_all_runs()
        assert len(runs) > 0
        assert all(np.isfinite(r.loss) for r in runs if r.loss is not None)
        id2c = res.get_id2config_mapping()
        assert any(
            e["config_info"].get("model_based_pick") for e in id2c.values()
        ), "dynamic pallas-scored sweep produced no model-based picks"

    def test_dynamic_conditional_space_respects_activity(self):
        # conditional spaces ride the dynamic tier too: the rank-masked fit
        # imputes inactive dims (the masked donor path) and every decoded
        # config still carries exactly the host activity pattern
        from hpbandster_tpu.optimizers import FusedBOHB

        cs = ConfigurationSpace(seed=0)
        x = UniformFloatHyperparameter("x", -5.0, 10.0)
        opt_hp = CategoricalHyperparameter("opt", ["sgd", "adam"])
        mom = UniformFloatHyperparameter("momentum", 0.0, 0.99)
        cs.add_hyperparameters([x, opt_hp, mom])
        cs.add_condition(EqualsCondition(mom, opt_hp, "sgd"))

        def eval_fn(vec, budget):
            return vec[0] * vec[0] + 0.1 * vec[2] + 0.0 * budget

        opt = FusedBOHB(
            configspace=cs, eval_fn=eval_fn, run_id="dyn-cond",
            min_budget=1, max_budget=9, eta=3, seed=51,
            min_points_in_model=5,
        )
        res = opt.run(n_iterations=4, chunk_brackets=2)
        opt.shutdown()
        assert all(s["dynamic_counts"] for s in opt.run_stats)
        id2c = res.get_id2config_mapping()
        assert len(id2c) > 0
        for entry in id2c.values():
            cfg = entry["config"]
            # host activity semantics hold exactly: momentum present iff
            # the sgd arm is active, and the host codec round-trips
            assert ("momentum" in cfg) == (cfg["opt"] == "sgd"), cfg
            assert dict(cs.from_vector(cs.to_vector(cfg))) == cfg
