"""Child process for the DCN-tier integration test (not collected by pytest).

Each of the two processes joins a jax.distributed pod on the CPU backend,
builds a mesh over ALL pod devices, and runs the identical deterministic
BOHB sweep through MultiHostBatchedExecutor — the SPMD-driver pattern from
parallel/multihost.py. Promotion decisions are dumped per-process so the
parent can assert they are bit-identical across hosts; only process 0
attaches a result logger.

Usage: python multihost_child.py <coordinator> <num_procs> <proc_id> <outdir>
"""

import json
import os
import sys


def main() -> None:
    coordinator, num_procs, proc_id, outdir = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
    )

    import jax

    from hpbandster_tpu.parallel.multihost import (
        MultiHostBatchedExecutor,
        initialize_multihost,
        is_primary_host,
    )

    got_id = initialize_multihost(
        coordinator_address=coordinator,
        num_processes=num_procs,
        process_id=proc_id,
    )
    assert got_id == proc_id, (got_id, proc_id)
    devices = jax.devices()
    assert len(devices) == 2 * num_procs, devices  # 2 local CPU devs each

    import numpy as np
    from jax.sharding import Mesh

    from hpbandster_tpu.core.result import json_result_logger
    from hpbandster_tpu.optimizers import BOHB
    from hpbandster_tpu.parallel import VmapBackend
    from tests.toys import branin_from_vector, branin_space

    mesh = Mesh(np.asarray(devices), axis_names=("config",))
    cs = branin_space(seed=0)
    backend = VmapBackend(branin_from_vector, mesh=mesh)
    assert backend._multiprocess
    executor = MultiHostBatchedExecutor(backend, cs)
    assert executor.primary == (proc_id == 0)
    assert is_primary_host() == (proc_id == 0)

    logger = None
    if executor.primary:
        logger = json_result_logger(
            os.path.join(outdir, "logged"), overwrite=True
        )
    opt = BOHB(
        configspace=cs,
        run_id="dcn-test",
        executor=executor,
        min_budget=1,
        max_budget=9,
        eta=3,
        seed=0,
        min_points_in_model=4,
        result_logger=logger,
    )
    res = opt.run(n_iterations=3)
    opt.shutdown()

    # promotion decisions == the full (config_id, budget, loss) record
    runs = sorted(
        (list(r.config_id), float(r.budget), float(r.loss))
        for r in res.get_all_runs()
        if r.loss is not None
    )
    with open(os.path.join(outdir, f"runs_{proc_id}.json"), "w") as f:
        json.dump(runs, f)
    print(f"proc {proc_id}: OK ({len(runs)} runs)")

    # ---- phase 2 (VERDICT r3 #6): the FLAGSHIP fused whole-sweep tier
    # end-to-end across the pod — every rank compiles the same sweep over
    # the pod-wide mesh (replicated in/out shardings, config-axis-sharded
    # evaluation), and the replayed promotion records must be bit-identical.
    # The space carries a CONDITION so the device activity-predicate +
    # KDE-imputation path is exercised under multi-process SPMD too.
    from hpbandster_tpu.optimizers import FusedBOHB
    from hpbandster_tpu.space import (
        CategoricalHyperparameter,
        ConfigurationSpace,
        EqualsCondition,
        UniformFloatHyperparameter,
    )

    ccs = ConfigurationSpace(seed=1)
    cx = UniformFloatHyperparameter("x", -5.0, 10.0)
    cy = UniformFloatHyperparameter("y", 0.0, 15.0)
    c_arm = CategoricalHyperparameter("arm", ["a", "b"])
    c_extra = UniformFloatHyperparameter("extra", 0.0, 1.0)
    ccs.add_hyperparameters([cx, cy, c_arm, c_extra])
    ccs.add_condition(EqualsCondition(c_extra, c_arm, "a"))

    def cond_eval(vec, budget):
        return branin_from_vector(vec[:2], budget) + 0.05 * vec[3]

    fopt = FusedBOHB(
        configspace=ccs,
        eval_fn=cond_eval,
        run_id="dcn-fused",
        min_budget=1,
        max_budget=9,
        eta=3,
        seed=1,
        mesh=mesh,
        min_points_in_model=5,
        result_logger=None,  # side effects would need the primary gate
    )
    # three run() calls cover every fused argument signature under DCN:
    # call 1 — static warm-free (seed,); call 2 — static warm 3-arg
    # ((seed, warm_v, warm_l): ragged per-budget host-numpy pytrees to
    # global replicated arrays on every rank); call 3 — chunked, the
    # DYNAMIC-count tier's 4-arg signature (full-capacity warm buffers +
    # traced i32 counts through the same to_global conversion)
    fopt.run(n_iterations=1)
    fopt.run(n_iterations=2)
    fres = fopt.run(n_iterations=3, chunk_brackets=1)
    assert not fopt.run_stats[1]["dynamic_counts"], \
        "unchunked warm continuation must stay on the static tier"
    assert fopt.run_stats[-1]["dynamic_counts"], \
        "chunked continuation must take the dynamic tier"
    fruns = sorted(
        (list(r.config_id), float(r.budget), float(r.loss))
        for r in fres.get_all_runs()
        if r.loss is not None
    )
    assert len(fruns) > 0
    # conditional activity pattern holds on every rank's replayed configs
    for entry in fres.get_id2config_mapping().values():
        cfg = entry["config"]
        assert ("extra" in cfg) == (cfg["arm"] == "a"), cfg
    with open(os.path.join(outdir, f"fused_runs_{proc_id}.json"), "w") as f:
        json.dump(fruns, f)
    print(f"proc {proc_id}: fused OK ({len(fruns)} runs)")

    # ---- phase 3 (ISSUE 10): the mesh-SHARDED incumbent-only sweep over
    # the pod — per-shard sampling over the pod-wide config axis, rung
    # reductions over ICI/DCN, and ONLY the final incumbent (replicated)
    # leaving the device loop. Every rank must fetch the identical
    # incumbent, and each rank publishes balance gauges for its own
    # local devices only.
    from hpbandster_tpu.obs.metrics import get_metrics
    from hpbandster_tpu.parallel.mesh import is_multiprocess_mesh

    assert is_multiprocess_mesh(mesh)
    sharded = executor.run_sharded_sweep(
        n_configs=64, eval_fn=branin_from_vector, mesh=mesh, seed=4,
        max_budget=9.0,
    )
    assert sharded["n_shards"] == len(devices)
    gauges = get_metrics().snapshot()["gauges"]
    local_ids = {
        d.id for d in devices if d.process_index == jax.process_index()
    }
    published = {
        int(k.split(".")[2]) for k in gauges
        if k.startswith("sweep.device.") and k.endswith(".configs")
    }
    assert published == local_ids, (published, local_ids)
    with open(os.path.join(outdir, f"sharded_{proc_id}.json"), "w") as f:
        json.dump(sharded["incumbent"], f)
    print(f"proc {proc_id}: sharded OK (loss {sharded['incumbent']['loss']})")


if __name__ == "__main__":
    main()
