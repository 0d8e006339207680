"""The calls into the system under test, and nothing else.

This file and ``configs/<name>.py`` are the only ones that import
``hpbandster_tpu``. A configuration's builder makes its evaluation object
once and hands it here; what comes back is ``sweep(seed) -> raw``, one whole
sweep from constructing the optimizer to the result in the host's hands.
``raw["extract"]()`` turns the program's result into plain arrays, and is
called only after the window has closed.
"""

import numpy as np
from jax.profiler import TraceAnnotation


def enable_compile_cache():
    """The program's own switch: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    the fixed ``<checkout>/.jax_compilation_cache``."""
    from hpbandster_tpu.utils.compile_cache import enable_persistent_compile_cache

    return enable_persistent_compile_cache()


def make_sweep(space_fn, evaluation, config, traffic, devices):
    """``evaluation`` is ``{"eval_fn": f}`` or ``{"stateful_eval": s}``:
    the one object both executable caches key on, so it is made once."""
    entry = {"fused_bohb": _fused_bohb_sweep, "sharded": _sharded_sweep}
    return entry[traffic["entry"]](space_fn, evaluation, config, traffic, devices)


def _ladder(config):
    return dict(min_budget=config["min_budget"], max_budget=config["max_budget"],
                eta=config["eta"])


def _fused_bohb_sweep(space_fn, evaluation, config, traffic, devices):
    from hpbandster_tpu.optimizers import FusedBOHB

    def sweep(seed):
        with TraceAnnotation("bench:construct"):
            opt = FusedBOHB(configspace=space_fn(seed=seed), run_id="bench",
                            seed=seed, **_ladder(config), **evaluation)
        with TraceAnnotation("bench:run"):
            result = opt.run(**traffic["run"])
        stats = opt.run_stats
        evaluations = opt.total_evaluated  # a local: the closure below keeps no optimizer alive
        return {
            "evaluations": evaluations,
            "build_compile_s": sum(s["build_compile_s"] for s in stats),
            "execute_fetch_s": sum(s["execute_fetch_s"] for s in stats),
            "compiles": sum(not s["compile_cache_hit"] for s in stats),
            "extract": lambda: _runs_record(result, evaluations),
        }

    return sweep


def _runs_record(result, evaluations):
    """Every run of a ``Result``: bracket, lane, budget, loss (NaN where
    the program masked a crash) and the hyperparameters it reports."""
    names = None
    bracket, lane, budget, loss, values = [], [], [], [], []
    for (b, _, i), datum in result.data.items():
        names = names or sorted(datum.config)
        row = [datum.config[n] for n in names]
        for bud, val in datum.results.items():
            bracket.append(b)
            lane.append(i)
            budget.append(bud)
            loss.append(np.nan if val is None else val)
            values.append(row)
    values = np.asarray(values, np.float64)
    return {
        "kind": "runs",
        "evaluations": evaluations,
        "bracket": np.asarray(bracket),
        "lane": np.asarray(lane),
        "budget": np.asarray(budget, np.float64),
        "loss": np.asarray(loss, np.float64),
        "config": {n: values[:, j] for j, n in enumerate(names)},
        "trajectory": result.get_incumbent_trajectory(all_budgets=False)["losses"],
    }


def _sharded_sweep(space_fn, evaluation, config, traffic, devices):
    from hpbandster_tpu.parallel import config_mesh
    from hpbandster_tpu.parallel.multihost import run_sharded_fused_sweep

    mesh = config_mesh(devices)

    def sweep(seed):
        with TraceAnnotation("bench:run"):
            out = run_sharded_fused_sweep(
                evaluation.get("eval_fn"), space_fn(seed=seed), seed=seed, mesh=mesh,
                stateful_eval=evaluation.get("stateful_eval"),
                n_configs=traffic["n_configs"], n_brackets=traffic["n_brackets"],
                **_ladder(config), **traffic["run"])
        record = {
            "kind": "incumbent",
            "evaluations": out["evaluations"],
            "incumbent": out["incumbent"],
            "per_bracket_loss": out["per_bracket_loss"],
            "budget": out["budgets"][-1],
        }
        return {
            "evaluations": out["evaluations"],
            # this entry point jits on first call: its build time is not told
            # apart from dispatch-to-fetch
            "build_compile_s": None,
            "execute_fetch_s": out["execute_fetch_s"],
            "compiles": 0,
            "extract": lambda: record,
        }

    return sweep
