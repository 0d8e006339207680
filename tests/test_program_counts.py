"""What each way to run a sweep may compile and move over the host link.

A count is a property of the program's structure and not of the backend: how
many executables a schedule builds, how many bytes its choke points carry and
what a repeated request builds (nothing). So the CPU mesh of the conftest is
entitled to every number here, and none of them is a time, a rate or a ratio
of times. ``CEILINGS`` holds, for each way, the most it may compile and move;
a case reads what the process-wide compile ledger and transfer counters
(``obs/runtime.py``) grew by while the program ran, never their totals: the
counters and the executable caches outlive a test, so a ceiling is asserted
and never an exact count that a warm cache would lower. What must miss every
cache runs a new objective (a fresh lambda, a fresh ensemble): the executable
keys hold the objective's identity.
"""

import collections
import os
import threading
import time

import pytest

import jax

from hpbandster_tpu import obs
from hpbandster_tpu.core.nameserver import NameServer
from hpbandster_tpu.core.worker import Worker
from hpbandster_tpu.obs.runtime import get_compile_tracker
from hpbandster_tpu.optimizers import BOHB, FusedBOHB
from hpbandster_tpu.ops.sweep import plan_additions, pow2_capacities
from hpbandster_tpu.parallel import BatchedExecutor, VmapBackend, config_mesh
from hpbandster_tpu.parallel.chaos import ChaosMonkey, ChaosProxy, ChaosSchedule
from hpbandster_tpu.parallel.dispatcher import Dispatcher
from hpbandster_tpu.parallel.multihost import run_sharded_fused_sweep
from hpbandster_tpu.serve import ServePool
from hpbandster_tpu.workloads.ensemble import (
    MLPConfig, ensemble_lane_bytes, make_mlp_ensemble,
)
from hpbandster_tpu.workloads.mlp import mlp_space
from hpbandster_tpu.workloads.toys import (
    branin_dict, branin_from_vector, branin_space,
)

#: way to run a sweep -> (most compiles, most megabytes up and down together).
#: Structural ceilings with headroom, not medians: a compile a shape, a chunk
#: or a tenant, or warm sweep state going through the host every rung, is past
#: them at once; honest variance is not.
CEILINGS = {
    # the whole static schedule is ONE program (measured: 1 compile, 0.16 MB
    # at 27 brackets 1..81; here 3 brackets 1..9)
    "fused": (4, 4),
    # dynamic counts: observation counts are traced inputs over pow2
    # capacities, so consecutive chunks reuse one executable until a capacity
    # doubles (measured: 5 compiles, 0.013 MB for 9 brackets in chunks of 3)
    "chunked": (8, 16),
    # ONE scanned program a configuration count (two counts here), and the
    # link is the point: a 4-byte seed up and one incumbent down a sweep,
    # whatever the configuration count; the megabytes are headroom
    "resident": (10, 8),
    # ONE program a mesh shape. Candidates are sampled ON the device, shard
    # by shard, so the link carries a uint32 seed up and an incumbent down:
    # bytes, not the candidate array (measured, 8 devices: 2 compiles,
    # < 0.01 MB at 2^17 configurations)
    "sharded": (4, 8),
    # a rung of live models: one unrolled and one resident program. The link
    # stays incumbent-only: the ensemble's parameters and momentum are
    # bracket-local device scratch and NEVER cross it
    "ensemble": (8, 8),
    # a bucket set and its stage kernels, not a program a stage shape
    "batched": (24, 64),
    # a python objective behind sockets: what compiles is the host model's
    # proposal kernels
    "rpc": (8, 16),
    # host sockets and a python objective: the recovery machinery must cost
    # (nearly) no device work; a compile here means plumbing leaked onto the
    # device path
    "chaos": (4, 8),
    # the same diet: promotion bookkeeping is host work, so a compile here
    # means a rule dragged device code into the master's loop
    "straggler": (4, 8),
    # megabatch programs are one a bucket (<= len(bucket_set)); with the solo
    # twins, the proposal kernels and the cross-tenant stage batches the
    # structure sits near 20. Ragged tenants must NOT compile a tenant or a
    # pack size: that is the regression a blown ceiling catches
    "serve": (32, 64),
    # ONE resident lane program a bucket family over a whole churning
    # workload (pinned below by the ledger itself); the ceiling covers the
    # proposal kernels beside it
    "continuous": (32, 64),
    # burn-rate windows are host record math riding a real pool: the
    # ceiling is the pool's own, and the evaluator adds nothing to it
    "slo": (32, 64),
}

#: compiles, bytes up, bytes down, and the compiles by the ledger's label
Bill = collections.namedtuple("Bill", "compiles h2d d2h built")


def counted(call):
    """``(call(), Bill)``: what the process compiled and moved over the host
    link while ``call`` ran. A bucket set's and a lane family's programs are
    compiled ahead on daemon threads: theirs finish inside the window of the
    call that started them, and nobody else's reaches into it."""

    def totals():
        for thread in threading.enumerate():
            if thread.name in ("bucket-precompile", "continuous-precompile"):
                thread.join()
        ledger = get_compile_tracker().snapshot()
        counter = obs.get_metrics().counter
        return (ledger["total_compiles"],
                int(counter("runtime.transfer_bytes_h2d").value),
                int(counter("runtime.transfer_bytes_d2h").value),
                collections.Counter({label: row["compiles"] for label, row
                                     in ledger["functions"].items()}))

    before = totals()
    out = call()
    return out, Bill(*(b - a for a, b in zip(before, totals())))


def megabytes(*bills):
    return sum(b.h2d + b.d2h for b in bills) / 1e6


def own_objective():
    """Branin under a new identity: its programs are in no cache."""
    return lambda v, b: branin_from_vector(v, b)  # noqa: E731


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8  # the conftest's CPU mesh
    return config_mesh(jax.devices())


def twice(call):
    """The same request two times over: ``{"out", "first", "again"}``."""
    out, first = counted(call)
    _, again = counted(call)
    return {"out": out, "first": first, "again": again}


# ----------------------------------------------------------- fused, static
def fused_run(eval_fn, mesh, n_iterations, chunk_brackets=None):
    opt = FusedBOHB(
        configspace=branin_space(seed=0), eval_fn=eval_fn, run_id="counts",
        min_budget=1, max_budget=9, eta=3, seed=0, mesh=mesh)
    opt.run(n_iterations=n_iterations, chunk_brackets=chunk_brackets)
    opt.shutdown()
    return opt.run_stats


@pytest.fixture(scope="module")
def fused(mesh):
    eval_fn = own_objective()
    return twice(lambda: fused_run(eval_fn, mesh, 3))


def test_fused_compiles(fused):
    assert 1 <= fused["first"].compiles <= CEILINGS["fused"][0]
    assert len(fused["out"]) == 1  # one chunk: the whole schedule


def test_fused_bytes(fused):
    assert 0 < megabytes(fused["first"]) <= CEILINGS["fused"][1]
    assert fused["first"].h2d == 4  # a cold sweep's upload is its seed


def test_fused_again_compiles_nothing(fused):
    assert fused["again"].compiles == 0
    assert fused["again"][1:3] == fused["first"][1:3]


# -------------------------------------------------- fused, chunked dynamic
@pytest.fixture(scope="module")
def chunked(mesh):
    eval_fn, longer = own_objective(), own_objective()
    runs = twice(lambda: fused_run(eval_fn, mesh, 9, chunk_brackets=3))
    rows, runs["longer"] = counted(
        lambda: fused_run(longer, mesh, 18, chunk_brackets=3))
    assert len(runs["out"]) == 3 and len(rows) == 6
    return runs


def test_chunked_compiles(chunked):
    assert 1 <= chunked["first"].compiles <= CEILINGS["chunked"][0]
    # fewer programs than chunks: the chunks share executables
    built = [not r["compile_cache_hit"] for r in chunked["out"]]
    assert sum(built) < len(built)


def test_chunked_bytes(chunked):
    assert 0 < megabytes(chunked["first"]) <= CEILINGS["chunked"][1]


def test_chunked_again_compiles_nothing(chunked):
    assert chunked["again"].compiles == 0


def test_chunked_counts_are_traced_and_capacities_key(chunked):
    """Twice the brackets under an objective of its own: no more programs,
    because what keys an executable is a capacity, which grows by doubling,
    and never an observation count."""
    assert 1 <= chunked["longer"].compiles <= chunked["first"].compiles + 1
    assert chunked["longer"].compiles <= CEILINGS["chunked"][0]


# ------------------------------------------------ resident, incumbent only
SIZES = (1024, 4096)
BRACKETS = 3


@pytest.fixture(scope="module")
def resident(mesh):
    """``run_sharded_fused_sweep(resident=True)`` at two configuration
    counts with the device's own telemetry ON: the bill below includes it."""
    eval_fn = own_objective()

    def sweep(n, device_metrics=True):
        return run_sharded_fused_sweep(
            eval_fn, branin_space(seed=0), n_configs=n, min_budget=1,
            max_budget=9, eta=3, mesh=mesh, seed=0, n_brackets=BRACKETS,
            resident=True, device_metrics=device_metrics)

    runs = {n: twice(lambda n=n: sweep(n)) for n in SIZES}
    runs["metrics_off"] = twice(lambda: sweep(SIZES[0], device_metrics=False))
    return runs


@pytest.mark.parametrize("n", SIZES)
def test_resident_compiles(resident, n):
    # one scanned program a configuration count
    assert resident[n]["first"].compiles == 1
    assert sum(resident[m]["first"].compiles for m in SIZES) + resident[
        "metrics_off"]["first"].compiles <= CEILINGS["resident"][0]


@pytest.mark.parametrize("n", SIZES)
def test_resident_bytes(resident, n):
    bill = resident[n]["first"]
    assert 0 < megabytes(bill) <= CEILINGS["resident"][1]
    # the counters agree with the sweep's own account of its link
    out = resident[n]["out"]
    assert (bill.h2d, bill.d2h) == (out["h2d_bytes"], out["d2h_bytes"])
    assert bill.d2h < 4096  # an incumbent and its telemetry, not candidates


def test_resident_again_compiles_nothing(resident):
    assert [resident[n]["again"].compiles for n in SIZES] == [0, 0]


def test_resident_link_is_flat_in_the_configuration_count(resident):
    bills = {(r["d2h_bytes"], r["h2d_bytes"], r["host_syncs"])
             for r in (resident[n]["out"] for n in SIZES)}
    assert len(bills) == 1, "the host link scaled with the count: %r" % bills


def test_resident_schedule_is_one_dispatch(resident):
    assert [len(resident[n]["out"]["chunks"]) for n in SIZES] == [1, 1]


def test_resident_upload_is_one_seed(resident):
    assert [resident[n]["out"]["h2d_bytes"] for n in SIZES] == [4, 4]


def test_resident_telemetry_rides_the_flat_link(resident):
    for n in SIZES:
        out = resident[n]["out"]
        assert out["device_telemetry"]["rounds_completed"] == BRACKETS
        assert out["device_telemetry"]["evaluations"] == out["evaluations"]


def test_resident_metrics_on_and_off_are_two_executables(resident):
    """Telemetry changes the traced program (more outputs): the request
    without it, made after the one with it, builds its own executable, and
    neither request, repeated, builds again."""
    off = resident["metrics_off"]
    assert off["first"].compiles == 1
    assert off["out"]["chunks"][0]["compile_cache_hit"] is False
    assert off["out"]["device_telemetry"] is None
    assert off["out"]["d2h_bytes"] < resident[SIZES[0]]["out"]["d2h_bytes"]
    assert off["again"].compiles == 0
    assert resident[SIZES[0]]["again"].compiles == 0


# ------------------------------------------------------------ mesh-sharded
@pytest.fixture(scope="module")
def sharded(mesh):
    eval_fn = own_objective()
    return twice(lambda: run_sharded_fused_sweep(
        eval_fn, branin_space(seed=0), n_configs=1 << 17, min_budget=1,
        max_budget=9, eta=3, mesh=mesh, seed=0))


def test_sharded_compiles(sharded):
    assert 1 <= sharded["first"].compiles <= CEILINGS["sharded"][0]


def test_sharded_bytes(sharded):
    assert megabytes(sharded["first"]) <= CEILINGS["sharded"][1]
    # 2^17 candidates of two float32 are 1 MB; what crossed is a seed and
    # an incumbent
    assert 0 < megabytes(sharded["first"]) < 1.0
    assert sharded["first"].h2d == 4


def test_sharded_again_compiles_nothing(sharded):
    assert sharded["again"].compiles == 0


def test_sharded_devices_hold_equal_counts(sharded):
    out = sharded["out"]
    assert out["n_devices"] == 8 and out["requested_configs"] == 1 << 17
    assert len(out["per_device_configs"]) == 8
    assert len(set(out["per_device_configs"])) == 1
    assert sum(out["per_device_configs"]) == out["evaluations"]
    assert out["balance_skew"] == 0.0


# ------------------------------------------------------- stateful ensemble
ENSEMBLE = MLPConfig(d_in=8, width=16, n_classes=4, n_train=128, n_val=64,
                     batch_size=32)


@pytest.fixture(scope="module")
def ensemble(mesh):
    """256 MLPs a first rung, trained in the sweep, two brackets."""
    stateful = make_mlp_ensemble(ENSEMBLE, data_seed=0)

    def sweep(resident, n=256):
        return run_sharded_fused_sweep(
            None, mlp_space(seed=0), n_configs=n, min_budget=1, max_budget=9,
            eta=3, mesh=mesh, seed=0, n_brackets=2, resident=resident,
            device_metrics=True, stateful_eval=stateful,
            program_name="ensemble_sweep")

    runs = {"unrolled": twice(lambda: sweep(False)),
            "resident": twice(lambda: sweep(True))}
    runs["resident_512"] = sweep(True, n=512)
    return runs


WAYS = ("unrolled", "resident")


@pytest.mark.parametrize("way", WAYS)
def test_ensemble_compiles(ensemble, way):
    assert ensemble[way]["out"]["aligned_stage_counts"][0] >= 256
    assert ensemble[way]["first"].compiles == 1
    assert sum(ensemble[w]["first"].compiles for w in WAYS) + 1 <= CEILINGS[
        "ensemble"][0]


@pytest.mark.parametrize("way", WAYS)
def test_ensemble_state_never_crosses_the_link(ensemble, way):
    bill = ensemble[way]["first"]
    assert 0 < megabytes(bill) <= CEILINGS["ensemble"][1]
    # a seed up; down, less than ONE lane's parameters and momentum, where
    # a rung holds 256 lanes
    assert bill.h2d == 4
    assert bill.d2h < ensemble_lane_bytes(ENSEMBLE)


@pytest.mark.parametrize("way", WAYS)
def test_ensemble_again_compiles_nothing(ensemble, way):
    assert ensemble[way]["again"].compiles == 0


def test_ensemble_link_is_flat_with_live_state_in_the_carry(ensemble):
    small, large = ensemble["resident"]["out"], ensemble["resident_512"]
    assert large["evaluations"] > small["evaluations"]
    assert len({(r["d2h_bytes"], r["h2d_bytes"], r["host_syncs"])
                for r in (small, large)}) == 1


# ------------------------------------------------------------ batched tier
@pytest.fixture(scope="module")
def batched(mesh):
    eval_fn = own_objective()

    def sweep():
        space = branin_space(seed=0)
        executor = BatchedExecutor(
            VmapBackend(eval_fn, mesh=mesh), space, parallel_brackets=3)
        opt = BOHB(configspace=space, run_id="counts-batched",
                   executor=executor, min_budget=1, max_budget=81, eta=3,
                   seed=0)
        result = opt.run(n_iterations=5)
        opt.shutdown()
        return len(result.get_all_runs())

    return twice(sweep)


def test_batched_compiles(batched):
    assert batched["out"] > 0
    assert 1 <= batched["first"].compiles <= CEILINGS["batched"][0]


def test_batched_bytes(batched):
    assert 0 < megabytes(batched["first"]) <= CEILINGS["batched"][1]


def test_batched_same_schedule_again_compiles_nothing(batched):
    assert batched["again"].compiles == 0


# -------------------------------------- host pools under a python objective
class PacedWorker(Worker):
    """Branin by RPC; ``pace`` seconds a unit of budget, ``late`` a call."""

    pace = late = 0.0

    def compute(self, config_id, config, budget, working_directory):
        if self.pace or self.late:
            time.sleep(self.pace * float(budget) + self.late)
        return {"loss": branin_dict(config, budget), "info": {}}


def host_pool_sweep(run_id, n_workers=1, max_budget=9, pace=0.0, late=0.0,
                    chaos=None, **bohb):
    """One bracket over a name server and ``n_workers`` socket workers (the
    first one ``late``), each behind a ``ChaosProxy`` of ``chaos`` if given:
    ``{(config_id, budget): loss}``."""
    ns = NameServer(run_id=run_id, host="127.0.0.1", port=0)
    host, port = ns.start()
    proxies, monkey, opt = {}, None, None
    try:
        for i in range(n_workers):
            w = PacedWorker(run_id=run_id, nameserver=host,
                            nameserver_port=port, id=i)
            w.pace, w.late = pace, late if i == 0 else 0.0
            w.result_delivery_backoff = 0.02
            w.result_delivery_backoff_cap = 0.2
            w.run(background=True)
            if chaos is not None:
                proxy = ChaosProxy(w._server.uri, chaos).start()
                proxy.interpose(host, port, w.worker_id)
                proxies[w.worker_id] = proxy
        executor = {}
        if n_workers > 1:
            executor["executor"] = Dispatcher(
                run_id=run_id, nameserver=host, nameserver_port=port,
                ping_interval=0.1, discover_interval=0.1,
                requeue_backoff=0.02, requeue_backoff_cap=0.2)
        else:
            executor.update(nameserver=host, nameserver_port=port)
        opt = BOHB(configspace=branin_space(seed=0), run_id=run_id,
                   min_budget=1, max_budget=max_budget, eta=3, seed=0,
                   **executor, **bohb)
        if chaos is not None:
            monkey = ChaosMonkey(
                proxies, seed=0, interval_s=0.25, kill_fraction=0.1,
                outage_s=0.25, max_dead=n_workers - 1).start()
        result = opt.run(n_iterations=1, min_n_workers=n_workers)
        return {(r.config_id, r.budget): r.loss
                for r in result.get_all_runs()}
    finally:
        if monkey is not None:
            monkey.stop()
        if opt is not None:
            opt.shutdown(shutdown_workers=True)
        for proxy in proxies.values():
            proxy.shutdown()
        ns.shutdown()


@pytest.fixture(scope="module")
def host_pools():
    """``{way: Bill}`` of the three host-socket ways."""
    bills = {}
    runs, bills["rpc"] = counted(
        lambda: host_pool_sweep("counts-rpc", max_budget=81))
    assert len(runs) == 81 + 27 + 9 + 3 + 1
    # seeded sampling alone (no model): the trajectory is the seed's
    seeded = dict(min_points_in_model=10_000)
    faults = obs.get_metrics().counter("chaos.faults")
    before = faults.value
    runs, bills["chaos"] = counted(lambda: host_pool_sweep(
        "counts-chaos", n_workers=2, pace=0.01, chaos=ChaosSchedule(
            seed=13, delay_rate=0.15, partition_rate=0.1,
            duplicate_rate=0.15, delay_s=0.02), **seeded))
    assert faults.value > before, "no fault landed: not a chaos run"
    assert len(runs) == 9 + 3 + 1
    runs, bills["straggler"] = counted(lambda: host_pool_sweep(
        "counts-straggler", n_workers=2, pace=0.004, late=0.05,
        promotion_rule="asha", **seeded))
    assert len(runs) >= 9 + 3 + 1
    return bills


HOST_WAYS = ("rpc", "chaos", "straggler")


@pytest.mark.parametrize("way", HOST_WAYS)
def test_host_pool_compiles(host_pools, way):
    assert host_pools[way].compiles <= CEILINGS[way][0]


@pytest.mark.parametrize("way", HOST_WAYS)
def test_host_pool_bytes(host_pools, way):
    assert megabytes(host_pools[way]) <= CEILINGS[way][1]


# ----------------------------------------------------------------- serving
def tenant_wave(pool, n_tenants, brackets, seed=0, apart_s=0.0, run_id="w"):
    """``n_tenants`` sweeps at once through one pool, tenant ``i`` running
    ``brackets(i)`` brackets and arriving ``apart_s`` after the one before:
    ``{i: [(config_id, budget)]}`` of what each was delivered."""
    delivered = {}

    def drive(i):
        opt = BOHB(
            configspace=branin_space(seed=seed + i),
            run_id="%s-%d" % (run_id, i), tenant_id="tenant%d" % i,
            executor=pool.executor_for("tenant%d" % i), min_budget=1,
            max_budget=9, eta=3, seed=seed + i)
        result = opt.run(n_iterations=brackets(i))
        opt.shutdown()
        delivered[i] = [(r.config_id, r.budget)
                        for r in result.get_all_runs()]

    threads = [threading.Thread(target=drive, args=(i,), daemon=True)
               for i in range(n_tenants)]
    for t in threads:
        t.start()
        if apart_s:
            time.sleep(apart_s)
    for t in threads:
        t.join()
    assert sorted(delivered) == list(range(n_tenants))
    return delivered


#: a bracket's programs: the solo bucket program and the megabatch program
SOLO, PACKED, STAGE_BATCH = "fused_bucket", "megabatch_bracket", "vmap_batch"
TENANTS = (1, 4, 16)


@pytest.fixture(scope="module")
def serve():
    """One-shot pools under 1, 4 and 16 tenants of ragged demand (tenant
    ``i`` runs ``1 + (i + 2) % 3`` brackets: three, one, two, ...), each
    pool's objective its own. Which brackets of a wave meet in a round, and
    so which are packed and how wide a stage batch is, is the threads'
    timing: only the lone tenant's programs are the same wave after wave."""
    runs = {}
    for n in TENANTS:
        pool = ServePool(VmapBackend(own_objective()), branin_space(seed=0),
                         pack_window_s=0.02)
        runs[n] = twice(
            lambda: tenant_wave(pool, n, lambda i: 1 + (i + 2) % 3))
        runs[n]["buckets"] = pool.snapshot()["buckets"]
    return runs


@pytest.mark.parametrize("n", TENANTS)
def test_serve_compiles(serve, n):
    assert 1 <= serve[n]["first"].compiles <= CEILINGS["serve"][0]
    assert serve[n]["first"].compiles + serve[n]["again"].compiles <= (
        CEILINGS["serve"][0])


@pytest.mark.parametrize("n", TENANTS)
def test_serve_bytes(serve, n):
    assert 0 < megabytes(serve[n]["first"]) <= CEILINGS["serve"][1]


def test_serve_compiles_no_program_a_tenant(serve):
    """Sixteen tenants, the bracket programs of one: the solo program of
    every bucket (compiled ahead, whoever comes) and at most one packed
    program a bucket; what else a larger wave builds is a wider stage batch."""
    for run in serve.values():
        built = run["first"].built + run["again"].built
        assert run["buckets"] >= 1
        assert built[SOLO] == run["buckets"]
        assert built[PACKED] <= run["buckets"]
    assert serve[1]["first"].built[PACKED] == 0  # nobody to be packed with
    assert serve[16]["first"].compiles - serve[1]["first"].compiles < 16 - 1


def test_serve_second_wave_compiles_nothing(serve):
    """The same tenants again. Alone, a tenant's wave is the same programs:
    nothing is built. Among others, a wave may meet in rounds the first did
    not: what it builds then is the packed program of a bucket or a stage
    batch of a new width, never a solo program, a proposal kernel or
    anything that is a tenant's."""
    assert serve[1]["again"].compiles == 0
    for n in (4, 16):
        assert set(serve[n]["again"].built) <= {PACKED, STAGE_BATCH}, n
        assert serve[n]["again"].compiles <= serve[n]["buckets"] + 3


@pytest.fixture(scope="module")
def continuous():
    """``ServePool(continuous=True)``, four lanes: two waves of eight
    tenants of equal demand (two brackets each) joining 20 ms apart and
    leaving as they finish."""
    pool = ServePool(VmapBackend(own_objective()), branin_space(seed=0),
                     pack_window_s=0.02, continuous=True, lane_count=4)
    chunks = obs.get_metrics().counter("serve.continuous.chunks")
    chunks_before = chunks.value
    waves, bill = counted(lambda: [
        tenant_wave(pool, 8, lambda i: 2, seed=s, apart_s=0.02,
                    run_id="c%d" % s) for s in (0, 100)])
    return {
        "waves": waves, "bill": bill, "pool": pool,
        "lane_programs": bill.built["continuous_bracket"],
        "chunks": chunks.value - chunks_before,
        "gauges": obs.get_metrics().snapshot()["gauges"],
    }


def test_continuous_compiles(continuous):
    assert 1 <= continuous["bill"].compiles <= CEILINGS["continuous"][0]


def test_continuous_bytes(continuous):
    assert 0 < megabytes(continuous["bill"]) <= CEILINGS["continuous"][1]


def test_continuous_ledger_is_pinned_to_the_bucket_set(continuous):
    """However many tenants came and went: one resident lane program a
    bucket family."""
    buckets = continuous["pool"].snapshot()["buckets"]
    assert buckets >= 1 and continuous["chunks"] >= 1
    assert 1 <= continuous["lane_programs"] <= buckets


def test_continuous_starves_no_lane(continuous):
    assert continuous["gauges"]["serve.lanes.starved"] == 0
    assert 0 < continuous["gauges"]["serve.lane_occupancy"] <= 1.0


def test_continuous_allocation_is_fair(continuous):
    """Equal demand: no tenant under 80 % of its deficit-fair share of the
    served cost."""
    served = continuous["pool"].scheduler.served_cost
    assert len(served) == 8
    fair = sum(served.values()) / len(served)
    assert min(served.values()) >= 0.8 * fair, served


def test_continuous_delivers_every_result_once(continuous):
    # two brackets of Branin 1..9 a tenant: 9 + 3 + 1 and 3 + 1 + ... rows
    for wave in continuous["waves"]:
        sizes = {len(rows) for rows in wave.values()}
        assert len(sizes) == 1 and sizes.pop() > 13
        assert all(len(set(rows)) == len(rows) for rows in wave.values())


@pytest.fixture(scope="module")
def slo(tmp_path_factory):
    """A pool that has served a tenant's three brackets serves them again
    under a live SLO evaluator that journals. A lone tenant's waves are the
    same programs, so what the second compiles is what the evaluator adds."""
    from hpbandster_tpu.obs.alerts import scan_slo_records
    from hpbandster_tpu.obs.summarize import read_merged_ex

    pool = ServePool(VmapBackend(own_objective()), branin_space(seed=0),
                     pack_window_s=0.02)
    _, alone = counted(lambda: tenant_wave(pool, 1, lambda i: 3))
    journal = str(tmp_path_factory.mktemp("slo") / "journal.jsonl")
    handle = obs.configure(journal_path=journal, slo=True)
    try:
        _, watched = counted(lambda: tenant_wave(pool, 1, lambda i: 3))
        live = (list(handle.slo.transitions), handle.slo.published())
    finally:
        handle.close()
    records, skipped = read_merged_ex([journal])
    offline = scan_slo_records(records)
    return {"alone": alone, "watched": watched, "live": live,
            "offline": (list(offline.transitions), offline.published()),
            "specs": len(offline.specs), "records": len(records),
            "skipped": skipped}


def test_slo_evaluator_adds_no_compile_to_the_pools_own(slo):
    assert slo["records"] > 0 and slo["specs"] == 6  # the default pack ran
    assert slo["watched"].compiles == 0
    assert 1 <= slo["alone"].compiles <= CEILINGS["slo"][0]
    assert megabytes(slo["alone"], slo["watched"]) <= CEILINGS["slo"][1]


def test_slo_replay_of_a_served_wave_is_identical(slo):
    """The journal of a real pool's wave, read back offline, gives the live
    manager's transitions and published values (``tests/test_slo.py`` holds
    the same over a written stream that breaches)."""
    assert slo["skipped"] == 0
    assert slo["offline"] == slo["live"]


# ------------------------------------- the draws of a lane's initial weights
@pytest.fixture(scope="module")
def lane_brackets():
    """The small LFM2 lane's bracket of 9, 3, 1 lanes through ``FusedBOHB``,
    its 13 evaluations one loop, on a device that holds the unit draw of the
    initial weights beside a lane and on one that does not: ``{how: (where
    the bracket's jaxpr draws its random bits, the chunk's row, every run's
    loss)}``. A new evaluation object a sweep: the executable's key holds
    its identity, not the device's bytes."""
    import sys

    from hpbandster_tpu.ops import fused as fused_ops
    from hpbandster_tpu.workloads import lfm2

    from lane_names import random_bits
    from lfm2_small import SMALL, load

    sys.modules.setdefault("program", load("program.py"))
    cfg = load("configs", "lfm2-sgd.py").lane_config(SMALL)._replace(attn_query_block=16)
    patch, out = pytest.MonkeyPatch(), {}
    try:
        for how, lanes, spare in (("held", 2, -1), ("drawn", 1, 1)):
            eval_fn = lfm2.make_lfm2_eval_fn(cfg, data_seed=SMALL["data_seed"])
            # a byte short of two lanes: one lane and its draw; a byte over one
            patch.setattr(fused_ops, "_device_memory_bytes",
                          lambda room=lanes * eval_fn.lane_facts.bytes + spare: room)
            places = random_bits(jax.make_jaxpr(lambda v: fused_ops.fused_sh_bracket(
                eval_fn, v, (9, 3, 1), (1.0, 3.0, 9.0)))(jax.numpy.zeros((9, 4))).jaxpr)
            opt = FusedBOHB(configspace=lfm2.lfm2_space(seed=11), eval_fn=eval_fn,
                            run_id="draw", min_budget=1, max_budget=9, eta=3, seed=11)
            result = opt.run(n_iterations=1)
            out[how] = (places, opt.run_stats[-1], sorted(
                (r.config_id, r.budget, r.loss) for r in result.get_all_runs()))
            out[how + ".gauge"] = obs.get_metrics().snapshot()["gauges"]["sweep.lane.init_draws"]
    finally:
        patch.undo()
    return out


def test_a_bracket_in_turn_draws_outside_its_loop_where_the_draw_fits(lane_brackets):
    """The program's own count: the random bits of the lanes' initial
    weights are drawn before the loop over the bracket's 13 evaluations and
    nowhere inside it; on a device that cannot hold the draw beside a lane
    they are drawn inside, an evaluation each."""
    (outside, inside), row, _ = lane_brackets["held"]
    assert outside > 0 and inside == 0
    assert (row["evaluations"], row["lanes_at_once"], row["init_draws"]) == (13, 1, 1)
    (outside, inside), row, _ = lane_brackets["drawn"]
    assert outside == 0 and inside > 0
    assert (row["evaluations"], row["lanes_at_once"], row["init_draws"]) == (13, 1, 13)
    assert (lane_brackets["held.gauge"], lane_brackets["drawn.gauge"]) == (1, 13)


def test_a_draw_handed_over_changes_a_loss_in_its_last_bits(lane_brackets):
    """The same sweep either way: the first rung's configurations and
    budgets, and its losses to what a step makes of the last two bits of
    the initial weights (an evaluation that draws and scales in one fusion
    has the compiler fold the draw's last factor into the scale)."""
    import statistics

    held, drawn = lane_brackets["held"][2], lane_brackets["drawn"][2]
    assert len(held) == 13 == len(drawn)
    assert sorted(budget for _, budget, _ in held) == sorted(budget for _, budget, _ in drawn)
    first = [[run for run in runs if run[1] == 1] for runs in (held, drawn)]
    assert [run[0] for run in first[0]] == [run[0] for run in first[1]] and len(first[0]) == 9
    assert statistics.median(
        abs(got - want) / abs(want)
        for (_, _, got), (_, _, want) in zip(*first)) < 1e-4


# ------------------------------------------------------ the executable key
def test_sweep_key_reads_no_environment(monkeypatch):
    """What selects a program is an argument of the driver, so the key of
    an executable is a function of the request alone: equal under any value
    of the process environment, and built without a look at it."""
    opt = FusedBOHB(
        configspace=branin_space(seed=0), eval_fn=branin_from_vector,
        run_id="key", min_budget=1, max_budget=9, eta=3, seed=0)
    plans = [opt._plan(i) for i in range(3)]
    caps = pow2_capacities(plan_additions(plans))
    driver = opt._sweep_driver(True, resident=True, device_metrics=False)
    key = driver._key(plans, caps)

    class Watched(dict):
        """The environment, keeping the names it is asked for."""

        asked = []

        def get(self, name, default=None):
            self.asked.append(name)
            return dict.get(self, name, default)

        def __getitem__(self, name):
            self.asked.append(name)
            return dict.__getitem__(self, name)

        def __contains__(self, name):
            self.asked.append(name)
            return dict.__contains__(self, name)

    monkeypatch.setattr(os, "environ", Watched(os.environ))
    assert driver._key(plans, caps) == key
    assert Watched.asked == []
