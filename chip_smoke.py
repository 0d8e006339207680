#!/usr/bin/env python
"""chip_smoke — the quickest proof that the fused sweep still starts and
runs on the TPU.

One process, the entry points a user calls (``FusedBOHB`` ->
``make_fused_sweep_fn`` -> ``fused_sh_bracket`` -> ``Result``), default
flags, no fallback and nothing caught: any failed check raises and the
exit code is non-zero. Without a TPU it exits before touching the program.

Legs (weights and data are random, made from seeds):

* **A headline** — Branin, 27 brackets, budgets 1..81, eta 3: one program.
* **B trainer** — the default ``TransformerConfig()`` trainer at full
  width, 2 brackets 3..81: 57 trainings (``bench_transformer``'s call).
* **C chunked** — Branin, 9 brackets 1..9, ``chunk_brackets=3``: the
  dynamic-count executable, device-resident state between chunks, buffer
  donation (on only off-CPU, so this is where it executes).
* **D resident** — Branin, 36 brackets 1..729 (10,123 evaluations),
  ``resident=True``: capacity 8192 through the Pallas scorer.
* **K kernel** — the scorer alone at n_obs 16384, against its XLA
  reference.
* **E mesh** — with more than one device: leg A over
  ``config_mesh(jax.devices())``, plus 1-chip vs N-chip scores and picks.

Per leg it prints compile seconds as set-up, device seconds and wall
seconds (``run()`` returns after the results are fetched, so the device
has finished), and counts — never rates. The last stdout line is one JSON
object naming the device as jax reports it.
"""

import importlib.metadata
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# importing the package initialises no backend; the chip is first touched
# in main(), after the backend check
from hpbandster_tpu.obs.profile import device_peaks
from hpbandster_tpu.ops import pallas_kde
from hpbandster_tpu.ops.bracket import hyperband_bracket
from hpbandster_tpu.ops.kde import (
    KDE,
    LOG_PDF_FLOOR,
    kde_logpdf,
    normal_reference_bandwidths,
)
from hpbandster_tpu.ops.sweep import sweep_donation_safe
from hpbandster_tpu.optimizers import FusedBOHB
from hpbandster_tpu.parallel import config_mesh
from hpbandster_tpu.utils.compile_cache import enable_persistent_compile_cache
from hpbandster_tpu.workloads.toys import branin_from_vector, branin_space
from hpbandster_tpu.workloads.transformer import (
    TransformerConfig,
    make_transformer_error_fn,
    transformer_space,
)


def check(ok, message):
    """An assertion that survives ``python -O``."""
    if not ok:
        raise AssertionError(message)


def cache_entries(cache_dir):
    return set(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else set()


def check_device_path(name, opt):
    """The program that ran is the device one: the Mosaic-compiled scorer,
    never the interpreter or the XLA scorer."""
    check(opt.use_pallas and not opt.pallas_interpret,
          "%s: use_pallas=%r pallas_interpret=%r"
          % (name, opt.use_pallas, opt.pallas_interpret))
    check("tpu_custom_call" in opt.last_executable.as_text(),
          "%s: no tpu_custom_call in the compiled program" % name)


def run_leg(name, opt, n_iterations, max_incumbent=None, **run_kwargs):
    """Run one sweep through ``FusedBOHB.run`` and hold its ``Result`` to
    the per-leg contract; returns the ``Result``."""
    t0 = time.perf_counter()
    res = opt.run(n_iterations=n_iterations, **run_kwargs)
    wall_s = time.perf_counter() - t0

    expected = sum(
        sum(hyperband_bracket(
            i, opt.min_budget, opt.max_budget, opt.eta).num_configs)
        for i in range(n_iterations)
    )
    runs = res.get_all_runs()
    check(len(runs) == expected,
          "%s: %d runs, schedule holds %d" % (name, len(runs), expected))
    # a crashed evaluation is masked to loss None; anything else is finite
    crashed = sum(r.loss is None for r in runs)
    check(all(np.isfinite(r.loss) for r in runs if r.loss is not None),
          "%s: a non-finite loss escaped the crash mask" % name)
    check(crashed < len(runs), "%s: every evaluation crashed" % name)
    trajectory = res.get_incumbent_trajectory(all_budgets=False)["losses"]
    check(len(trajectory) > 0, "%s: empty incumbent trajectory" % name)
    check(all(b <= a for a, b in zip(trajectory, trajectory[1:])),
          "%s: incumbent trajectory increases: %r" % (name, trajectory))
    if max_incumbent is not None:
        check(trajectory[-1] <= max_incumbent,
              "%s: incumbent %.4f above the sanity bound %.4f"
              % (name, trajectory[-1], max_incumbent))

    check_device_path(name, opt)

    print("leg %s: evaluations=%d crashed=%d chunks=%d compiles=%d "
          "setup_s=%.1f device_s=%.2f wall_s=%.2f incumbent=%.5f"
          % (name, len(runs), crashed, len(opt.run_stats),
             sum(not s["compile_cache_hit"] for s in opt.run_stats),
             sum(s["build_compile_s"] for s in opt.run_stats),
             sum(s["execute_fetch_s"] for s in opt.run_stats),
             wall_s, trajectory[-1]), flush=True)
    return res


def branin_opt(run_id, max_budget, seed, mesh=None):
    return FusedBOHB(
        configspace=branin_space(seed=seed), eval_fn=branin_from_vector,
        run_id=run_id, min_budget=1, max_budget=max_budget, eta=3, seed=seed,
        mesh=mesh,
    )


def synthetic_rows(rng, n, cards):
    """``f32[n, d]`` unit-cube rows: a category index where ``cards[j]``
    names a cardinality, a uniform draw where it is 0."""
    rows = np.zeros((n, len(cards)), np.float32)
    for j, card in enumerate(cards):
        rows[:, j] = rng.integers(card, size=n) if card else rng.uniform(size=n)
    return rows


def synthetic_kde(rng, capacity, live, cards):
    data = np.zeros((capacity, len(cards)), np.float32)
    data[:live] = synthetic_rows(rng, live, cards)
    mask = np.zeros(capacity, np.float32)
    mask[:live] = 1.0
    bw = normal_reference_bandwidths(data, mask, jnp.asarray(cards, jnp.int32))
    return KDE(jnp.asarray(data), jnp.asarray(mask), bw)


def leg_kernels():
    """The scorer alone, at a size past what leg D hands it, against its XLA
    reference."""
    rng = np.random.default_rng(0)
    cards = [0, 4, 0]
    vartypes = jnp.asarray([0, 1, 0], jnp.int32)
    cards_dev = jnp.asarray(cards, jnp.int32)
    good = synthetic_kde(rng, 16384, 9000, cards)
    bad = synthetic_kde(rng, 16384, 15000, cards)
    cands = jnp.asarray(synthetic_rows(rng, 8192, cards))

    t0 = time.perf_counter()
    scores = pallas_kde.pallas_score_candidates(
        cands, good, bad, vartypes, cards_dev
    ).block_until_ready()
    first_s = time.perf_counter() - t0
    check(scores.shape == (8192,) and bool(jnp.isfinite(scores).all()),
          "K: scorer output shape %r or non-finite" % (scores.shape,))
    ref_rows = cands[:512]
    lg = jax.vmap(lambda c: kde_logpdf(c, good, vartypes, cards_dev))(ref_rows)
    lb = jax.vmap(lambda c: kde_logpdf(c, bad, vartypes, cards_dev))(ref_rows)
    want = jnp.maximum(lg, LOG_PDF_FLOOR) - jnp.maximum(lb, LOG_PDF_FLOOR)
    err = float(jnp.max(jnp.abs(scores[:512] - want)))
    check(err < 5e-3, "K: scorer vs XLA reference max|d|=%g" % err)

    print("leg K: scorer n_obs=16384 candidates=8192 first_call_s=%.2f "
          "max_abs_err=%.2e" % (first_s, err), flush=True)


def leg_mesh(devices, res_a):
    """Leg A over every chip, and the same stage of proposals scored on
    one chip and on all of them."""
    mesh = config_mesh(devices)
    opt = branin_opt("smoke-E", 81, seed=0, mesh=mesh)
    res_e = run_leg("E", opt, 27, max_incumbent=2.0)
    text = opt.last_executable.as_text()
    n_collectives = sum(
        text.count(op + "(") for op in
        ("all-reduce", "all-gather", "collective-permute", "all-to-all",
         "reduce-scatter")
    )
    check(n_collectives > 0, "E: no collectives in the mesh program")
    shardings = jax.tree.leaves(opt.last_executable.output_shardings)
    check(all(len(s.device_set) == len(devices) for s in shardings),
          "E: an output of the mesh program does not span every device")

    rng = np.random.default_rng(1)
    cards = [0, 0]
    vartypes = jnp.zeros(2, jnp.int32)
    cards_dev = jnp.asarray(cards, jnp.int32)
    good = synthetic_kde(rng, 8192, 1200, cards)
    bad = synthetic_kde(rng, 8192, 6800, cards)
    cands = jnp.asarray(synthetic_rows(rng, 729 * 64, cards))

    def score(mesh):
        return jax.jit(lambda c: pallas_kde.pallas_score_candidates(
            c, good, bad, vartypes, cards_dev, mesh=mesh))(cands)

    one, many = score(None), score(mesh)
    check(len(many.sharding.device_set) == len(devices),
          "E: sharded scores do not live on every device")
    err = float(jnp.max(jnp.abs(one - many)))
    check(err < 1e-4, "E: 1-chip vs %d-chip scores max|d|=%g"
          % (len(devices), err))

    def propose(mesh):
        return jax.jit(lambda k: pallas_kde.pallas_propose_batch(
            k, good, bad, vartypes, cards_dev, 729, mesh=mesh)
        )(jax.random.key(7))

    p_one, p_many = propose(None), propose(mesh)
    same = float(jnp.mean(jnp.all(jnp.abs(p_one - p_many) < 1e-6, axis=1)))
    # a pick may flip only where two candidates score within rounding
    check(same >= 0.99, "E: only %.4f of proposals agree" % same)

    inc_a = res_a.get_incumbent_trajectory(all_budgets=False)["losses"][-1]
    inc_e = res_e.get_incumbent_trajectory(all_budgets=False)["losses"][-1]
    print("leg E: devices=%d collectives=%d score_max_abs_err=%.2e "
          "proposals_equal=%.4f incumbent_1chip=%.5f incumbent_mesh=%.5f"
          % (len(devices), n_collectives, err, same, inc_a, inc_e),
          flush=True)


def main():
    if jax.default_backend() != "tpu":
        sys.exit(
            "chip_smoke: jax found no TPU (default backend %r); nothing "
            "to prove" % jax.default_backend()
        )
    t_start = time.perf_counter()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print("device: %s" % json.dumps(device))
    print("versions: jax=%s jaxlib=%s libtpu=%s" % (
        jax.__version__, importlib.metadata.version("jaxlib"),
        importlib.metadata.version("libtpu")))
    peaks = device_peaks(devices[0])
    check(peaks["flops_per_s"] and peaks["bytes_per_s"],
          "device_kind %r has no row in both peak tables "
          "(workloads/flops.py, obs/profile.py): %r"
          % (device["kind"], peaks))

    cache_dir = enable_persistent_compile_cache()
    entries_before = cache_entries(cache_dir)
    print("compile cache: dir=%s entries_before=%d"
          % (cache_dir, len(entries_before)), flush=True)

    res_a = run_leg("A", branin_opt("smoke-A", 81, seed=0), 27,
                    max_incumbent=2.0)

    opt_b = FusedBOHB(
        configspace=transformer_space(seed=0),
        eval_fn=make_transformer_error_fn(TransformerConfig(), data_seed=0),
        run_id="smoke-B", min_budget=3, max_budget=81, eta=3, seed=0,
    )
    run_leg("B", opt_b, 2)

    check(sweep_donation_safe(), "C: buffer donation resolved OFF on a TPU")
    opt_c = branin_opt("smoke-C", 9, seed=1)
    run_leg("C", opt_c, 9, chunk_brackets=3)
    check([s["dynamic_counts"] for s in opt_c.run_stats] == [True] * 3,
          "C: chunks did not run the dynamic-count executable")
    # one executable serves all three chunks, and after the first the
    # observation state never leaves the device (donated in place)
    check([s["compile_cache_hit"] for s in opt_c.run_stats]
          == [False, True, True],
          "C: chunks recompiled: %r" % opt_c.run_stats)
    check(all(s["warm_upload_bytes"] <= 4 for s in opt_c.run_stats[1:]),
          "C: warm state went back through the host: %r" % opt_c.run_stats)

    opt_d = branin_opt("smoke-D", 729, seed=2)
    run_leg("D", opt_d, 36, resident=True, max_incumbent=2.0)

    leg_kernels()

    if len(devices) > 1:
        leg_mesh(devices, res_a)

    entries_after = cache_entries(cache_dir)
    # an entry is "<program name>-<key hash>[-cache]"; a warm run adds
    # none, so a short list names exactly the programs that missed
    added = sorted(e.split("-")[0] for e in entries_after - entries_before)
    print("compile cache: dir=%s entries_before=%d entries_after=%d "
          "added=%d %s"
          % (cache_dir, len(entries_before), len(entries_after), len(added),
             added if len(added) <= 8 else ""))
    print("total_s=%.1f" % (time.perf_counter() - t_start))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
