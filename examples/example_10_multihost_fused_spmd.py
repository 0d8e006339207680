"""Example 10: the fused whole-sweep tier across a multi-host pod (SPMD).

The flagship ``FusedBOHB`` path (example 8) compiles an entire multi-bracket
sweep into one XLA program. This example scales that program over a
``jax.distributed`` pod: every host runs the IDENTICAL script, the mesh
spans all pod devices, each wave's evaluations shard over the 'config'
axis (ICI within a slice, DCN between hosts), and the tiny stage records
replicate back to every rank — so each host's driver replays bit-identical
promotion decisions with no coordination protocol beyond XLA's collectives.

Contrast with example 9 (elastic batched workers over RPC): this tier is
static-membership SPMD — maximum throughput, no elasticity. Pick it when
the pod is yours for the whole sweep; pick example 9's pool when hosts
come and go.

In production, launch one copy per host:

    python example_10_multihost_fused_spmd.py \
        --coordinator <host0>:1234 --num_processes 4 --process_id <rank>

Run without arguments to see the topology demonstrated locally: the script
self-launches 2 single-host processes (2 virtual CPU devices each) that
form a 4-device pod and verify cross-rank agreement.
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile


def run_rank(coordinator: str, num_processes: int, process_id: int,
             out_path: str = "") -> None:
    import jax

    from hpbandster_tpu.core.result import json_result_logger
    from hpbandster_tpu.optimizers import FusedBOHB
    from hpbandster_tpu.parallel.multihost import (
        initialize_multihost,
        is_primary_host,
    )
    from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space

    initialize_multihost(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()  # pod-wide after initialize
    mesh = Mesh(np.asarray(devices), axis_names=("config",))

    # side effects gate on the primary host; everything else is identical
    # on every rank (same seed -> same deterministic driver control flow)
    logger = (
        json_result_logger(".ex10_results", overwrite=True)
        if is_primary_host() and not out_path
        else None
    )
    opt = FusedBOHB(
        configspace=branin_space(seed=0),
        eval_fn=branin_from_vector,
        run_id="ex10",
        min_budget=1,
        max_budget=27,
        eta=3,
        seed=0,
        mesh=mesh,
        result_logger=logger,
    )
    res = opt.run(n_iterations=4)
    inc_id = res.get_incumbent_id()
    runs = sorted(
        (list(r.config_id), float(r.budget), float(r.loss))
        for r in res.get_all_runs()
        if r.loss is not None
    )
    print(
        f"rank {jax.process_index()}/{num_processes}: "
        f"{len(runs)} evaluations over {len(devices)} pod devices, "
        f"incumbent {inc_id}"
    )
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(runs, fh)


def self_launch_demo() -> None:
    """Spawn 2 local 'hosts' (2 virtual CPU devices each) forming one pod."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    with tempfile.TemporaryDirectory() as td:
        procs = [
            subprocess.Popen(
                [
                    sys.executable, os.path.abspath(__file__),
                    "--coordinator", coordinator,
                    "--num_processes", "2",
                    "--process_id", str(i),
                    "--dump", os.path.join(td, f"runs_{i}.json"),
                ],
                env=env,
            )
            for i in range(2)
        ]
        try:
            for p in procs:
                p.wait(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert all(p.returncode == 0 for p in procs), "a rank failed"
        with open(os.path.join(td, "runs_0.json")) as fh:
            r0 = json.load(fh)
        with open(os.path.join(td, "runs_1.json")) as fh:
            r1 = json.load(fh)
    assert r0 == r1, "ranks disagreed on the run record"
    print(f"demo: both ranks replayed {len(r0)} identical runs — SPMD OK")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--coordinator", default="")
    p.add_argument("--num_processes", type=int, default=0)
    p.add_argument("--process_id", type=int, default=-1)
    p.add_argument("--dump", default="", help=argparse.SUPPRESS)
    args = p.parse_args()
    if not args.coordinator:
        self_launch_demo()
        return
    run_rank(args.coordinator, args.num_processes, args.process_id, args.dump)


if __name__ == "__main__":
    main()
