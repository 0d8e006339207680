"""FusedBOHB — the whole-sweep-on-device optimizer driver.

Same knob surface as :class:`~hpbandster_tpu.optimizers.bohb.BOHB`, but
instead of driving brackets through the Master/executor loop it compiles the
ENTIRE ``n_iterations`` sweep into one XLA computation (``ops/sweep.py``)
and replays the device outputs into the standard ``SuccessiveHalving`` /
``Datum`` / ``Result`` bookkeeping afterward — so result logging, analysis
and visualization tooling see exactly the structures the reference produces
(SURVEY.md §2 "Result / logging"), while the optimization itself pays one
device dispatch + one result fetch for the whole run.

Use this whenever the objective is jittable — conditional spaces and
forbidden clauses are supported on-device (``ops/sweep.py``:
``compile_active_mask`` / ``compile_forbidden_mask``). Fall back to ``BOHB``
with a ``BatchedExecutor`` (per-bracket fusion) or the host worker pool for
non-jittable objectives, or for the rare condition forms without a device
representation (construction raises ``ValueError`` for those).
"""

from __future__ import annotations

import logging
import math
import sys
import time
import weakref
from typing import Any, Dict, List, Optional

import numpy as np

from hpbandster_tpu import obs
from hpbandster_tpu.core.iteration import Datum, Status
from hpbandster_tpu.core.job import Job
from hpbandster_tpu.core.result import Result
from hpbandster_tpu.core.successive_halving import SuccessiveHalving
from hpbandster_tpu.ops.bracket import (
    BracketPlan,
    budget_ladder,
    hyperband_bracket,
    max_sh_iterations,
)
from hpbandster_tpu.ops.sweep import build_space_codec
from hpbandster_tpu.ops.sweep_driver import (
    _SWEEP_EXE_CACHE,
    SweepDriver,
    check_once,
)
from hpbandster_tpu.space import ConfigurationSpace

__all__ = ["FusedBOHB", "FusedHyperBand", "FusedRandomSearch", "FusedH2BO",
           "sweep_instruction_facts", "sweep_phase_maps"]


#: what ``sweep_phase_maps`` and ``sweep_instruction_facts`` have read of an
#: executable's text, kept while the executable lives: the text is fetched
#: and parsed once a process, whatever the number of families asked for
_PROGRAM_TEXTS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _program_texts():
    """The parsed text of every sweep executable this process holds."""
    from hpbandster_tpu.obs.profile import parse_program_text

    for compiled in _SWEEP_EXE_CACHE.values():
        program = _PROGRAM_TEXTS.get(compiled)
        if program is None:
            program = _PROGRAM_TEXTS[compiled] = parse_program_text(compiled)
        yield program


def sweep_phase_maps(scopes=None) -> Dict[str, Dict[str, str]]:
    """``{module name: {instruction name: phase}}`` over every sweep
    executable this process holds: the program's own map from what a
    profiler trace prints (``XLA Modules`` events named
    ``<module name>(<id>)`` enclosing ``XLA Ops`` named by instruction)
    to the phases of ``obs.timeline.DEVICE_SCOPES``
    (``obs.profile.device_phase_map``), or to those of another closed list
    of scope names given as ``scopes`` (``obs.timeline.LANE_SCOPES``: the
    parts of a lane, inside the trainer; ``PASS_SCOPES``: its forward,
    recomputed and backward passes; ``MOE_SCOPES``: the pieces of its
    expert layer). Two executables of one module name
    (a chunked run that crossed a capacity bucket) share an entry; an
    instruction name they give different phases is left out, and so is an
    executable whose text names no scope of the list at all. An
    executable's text is fetched and parsed once (seconds for a large
    program: the call graph and the ``op_name``s serve every list), so call
    it after the sweeps, never between them."""
    from hpbandster_tpu.obs.profile import device_phase_map

    maps: Dict[str, Dict[str, str]] = {}
    clashed = set()
    for program in _program_texts():
        phases = device_phase_map(program, scopes)
        if not phases:
            # loaded from a persistent cache that a commit without these
            # scopes filled (the cache's key leaves metadata out): nothing
            # to tell
            continue
        for name, phase in phases.items():
            if maps.setdefault(program.module, {}).setdefault(name, phase) != phase:
                clashed.add((program.module, name))
    for module, name in clashed:
        del maps[module][name]
    return maps


def sweep_instruction_facts(scopes=None) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """``{module name: {instruction name: {"opcode", "kind", "named",
    "adopted", "shape", "op_name"}}}`` over the same executables, for every
    instruction of each: its opcode, its kind (one of
    ``obs.timeline.OP_KINDS``: ``obs.profile.device_kind_map``), whether its
    own line carries an ``op_name``, and the name of ``scopes`` it takes
    from what reads it where it has none by name
    (``obs.profile.adopted_phase_map``; ``None`` for an instruction that has
    one by name, and for an orphan); for a table a person reads, the
    beginning of its shape and its ``op_name``. What
    ``sweep_phase_maps(scopes)`` says of a name is not repeated here: the
    two are read side by side. Its rules hold: an instruction that two
    executables of one module name give different facts is left out, and
    so is an executable whose text names no scope of the list. Nothing that
    ``sweep_phase_maps`` has parsed is parsed again; the walk over readers
    is made anew each call (half a second for the largest lane's program):
    call it once, after the sweeps."""
    from hpbandster_tpu.obs.profile import _adopted, device_kind_map, device_phase_map

    facts: Dict[str, Dict[str, Dict[str, Any]]] = {}
    clashed = set()
    for program in _program_texts():
        phases = device_phase_map(program, scopes)
        if not phases:
            continue
        kinds = device_kind_map(program)
        adopted = _adopted(program, phases, kinds)
        instructions = program.instructions
        for rows in program.computations.values():
            for name, op_name, _ in rows:
                fact = {"opcode": instructions[name][0], "kind": kinds[name],
                        "named": op_name is not None, "adopted": adopted.get(name),
                        "shape": instructions[name][3], "op_name": op_name}
                if facts.setdefault(program.module, {}).setdefault(name, fact) != fact:
                    clashed.add((program.module, name))
    for module, name in clashed:
        del facts[module][name]
    return facts


def _lane_accounting(eval_fn, plans, outputs) -> Dict[str, Any]:
    """What a chunk's ``run_stats`` row says of the lanes of an ``eval_fn``
    whose maker stated its facts (``ops.fused.LaneFacts``): the steps and
    tokens its evaluations trained (a stateless evaluation trains its whole
    budget), how many lanes the widest rung evaluated side by side, how
    often the program drew a lane's initial weights (once a loop that was
    handed the draw, else once a turn of it: ``ops.fused.init_draws``), and the
    mean over the evaluations of each device counter. Published as gauges
    ``sweep.lane.<name>`` too. Empty for every other evaluation."""
    facts = getattr(eval_fn, "lane_facts", None)
    if facts is None:
        return {}
    from hpbandster_tpu.ops.fused import init_draws, lanes_at_once

    steps = sum(n * int(round(b)) for plan in plans
                for n, b in zip(plan.num_configs, plan.budgets))
    row = {
        "lane_steps": steps,
        "lane_tokens": steps * facts.tokens_per_step,
        "lanes_at_once": lanes_at_once(
            eval_fn, max(n for plan in plans for n in plan.num_configs)),
        "init_draws": sum(init_draws(eval_fn, plan.num_configs) for plan in plans),
    }
    if facts.counters:
        counted = np.concatenate([out.lane_counters for out in outputs])
        row.update(zip(facts.counters, np.nanmean(counted, axis=0).tolist()))
    for name, value in row.items():
        obs.get_metrics().gauge("sweep.lane." + name).set(value)
    return row


class _ReplayIteration(SuccessiveHalving):
    """SuccessiveHalving whose promotion decisions replay the device's.

    The fused sweep already decided every promotion on-device; the host
    bookkeeping must record those decisions verbatim (they follow the same
    top-k rule, but the device is authoritative)."""

    promotion_rule = "fused_replay"

    def __init__(self, *args, promotion_sets: List[set], **kwargs):
        super().__init__(*args, **kwargs)
        self._promotion_sets = promotion_sets

    def _advance_to_next_stage(self, config_ids, losses) -> np.ndarray:
        promoted = self._promotion_sets[self.stage]
        return np.array([cid[2] in promoted for cid in config_ids], bool)

    def add_configurations(self, configs: List[Dict], infos: List[Dict]) -> None:
        """A rung's fresh configurations at once: what one
        ``add_configuration(config, info)`` a configuration leaves, with
        its checks made once for all of them. The result logger's
        ``new_config`` and the journal's ``config_sampled`` are reached,
        once a configuration and in that order, only while a logger or a
        sink is attached."""
        stage = self.stage
        if self.is_finished:
            raise RuntimeError("iteration is finished, cannot add configurations")
        first = self.actual_num_configs[stage]
        if configs and first + len(configs) > self.num_configs[stage]:
            raise RuntimeError(
                f"stage {stage} of iteration {self.HPB_iter} is already full"
            )
        budget = self.budgets[stage]
        logger = self.result_logger
        observed = logger is not None or obs.get_bus().active
        for i, (config, info) in enumerate(zip(configs, infos), start=first):
            config_id = (self.HPB_iter, stage, i)
            self.data[config_id] = Datum(
                config=config, config_info=info, budget=budget
            )
            self.actual_num_configs[stage] = i + 1
            if observed:
                if logger is not None:
                    logger.new_config(config_id, config, info)
                obs.emit_config_sampled(config_id, budget, info)


def _check_objective(eval_fn, stateful_eval, d: int, min_budget: float) -> None:
    """The constructor's admission check: raise ``ValueError`` for an
    objective that the sweep cannot run, where the first ``run()`` would
    die with an opaque XLA broadcasting error from deep inside the sweep
    trace. ``jax.eval_shape`` is abstract: no backend or device work, but
    a whole Python trace of the objective on the host (55-61 ms for the
    ``mlp-sgd`` ensemble's step, seconds for a large lane), which is why
    ``FusedBOHB.__init__`` pays it once an object and not once a
    construction. The budget is passed CONCRETE exactly as the sweep does,
    so Python-level loops over epochs inside eval_fn stay legal;
    min_budget keeps any such unrolling as small as possible."""
    import jax as _jax
    import jax.numpy as _jnp

    if stateful_eval is not None:
        # same fail-fast contract for the stateful seam: a 2-lane
        # abstract init->step round-trip surfaces protocol bugs
        # (wrong arity, non-batched losses) before the sweep trace
        # buries them in an opaque XLA error
        try:
            _, losses_sds = _jax.eval_shape(
                lambda v: stateful_eval.step_fn(
                    stateful_eval.init_fn(v), v, min_budget, 0.0
                ),
                _jax.ShapeDtypeStruct((2, d), _jnp.float32),
            )
        except Exception as e:
            raise ValueError(
                f"stateful_eval failed under abstract evaluation "
                f"(init_fn + step_fn over f32[2, {d}] vectors): "
                f"{type(e).__name__}: {e}"
            ) from e
        if tuple(getattr(losses_sds, "shape", ())) != (2,):
            raise ValueError(
                "stateful_eval.step_fn must return per-lane losses "
                f"f32[n], got shape {getattr(losses_sds, 'shape', None)}"
            )
        return
    try:
        out_sds = _jax.eval_shape(
            lambda v: eval_fn(v, min_budget),
            _jax.ShapeDtypeStruct((d,), _jnp.float32),
        )
    except Exception as e:
        # deliberately broad: eval_shape surfaces plain bugs inside
        # eval_fn (wrong arity, NameError) as well as tracing errors,
        # so the banner says what was ATTEMPTED, not what went wrong —
        # the chained original exception carries the real diagnosis
        # (ADVICE r4)
        raise ValueError(
            f"eval_fn(config_vector f32[{d}], budget) failed under "
            f"abstract evaluation (jax.eval_shape) for this {d}-dim "
            f"space: {type(e).__name__}: {e}"
        ) from e
    leaves = _jax.tree_util.tree_leaves(out_sds)
    shapes = [tuple(getattr(l, "shape", ())) for l in leaves]
    if len(leaves) != 1 or shapes[0] != ():
        raise ValueError(
            "eval_fn must return a single SCALAR loss, got "
            f"{len(leaves)} output leaves with shapes {shapes} — "
            "reduce per-example losses (e.g. .mean()) and drop aux "
            "outputs before returning"
        )


class FusedBOHB:
    def __init__(
        self,
        configspace: Optional[ConfigurationSpace] = None,
        eval_fn=None,
        run_id: str = "fused",
        eta: float = 3,
        min_budget: float = 0.01,
        max_budget: float = 1,
        min_points_in_model: Optional[int] = None,
        top_n_percent: int = 15,
        num_samples: int = 64,
        random_fraction: float = 1 / 3,
        bandwidth_factor: float = 3.0,
        min_bandwidth: float = 1e-3,
        seed: Optional[int] = None,
        mesh=None,
        axis: str = "config",
        result_logger=None,
        working_directory: str = ".",
        logger: Optional[logging.Logger] = None,
        previous_result: Optional[Result] = None,
        use_pallas: Optional[bool] = None,
        stateful_eval=None,
    ):
        """One evaluation seam: a jittable ``eval_fn(config_vector, budget)
        -> scalar loss`` or a ``StatefulEval``; the other arguments are
        :class:`~hpbandster_tpu.optimizers.bohb.BOHB`'s.

        The objective is checked here under ``jax.eval_shape``
        (``_check_objective``: a ``ValueError`` for a loss of the wrong
        shape or an objective that cannot be traced), once an evaluation
        object, space dimension and ``min_budget``, not once a
        construction: the check is a whole Python trace of the objective
        on the host. So an object that is MUTATED IN PLACE after it passed
        (a closure's captured state swapped for one that returns another
        shape) is not checked again; the executable cache assumes the same
        of the same object, and the sweep's own trace still fails on it,
        only less readably. An ``eval_fn`` that states ``lane_facts`` is
        not traced at all, and one that cannot be hashed every time.
        """
        if configspace is None:
            raise ValueError("you have to provide a valid ConfigurationSpace object")
        if eval_fn is None and stateful_eval is None:
            raise ValueError(
                "FusedBOHB needs a jittable eval_fn(config_vector, budget) "
                "-> loss, or a StatefulEval (warm-continuation ensemble "
                "training, ops.fused.StatefulEval)"
            )
        if eval_fn is not None and stateful_eval is not None:
            raise ValueError(
                "eval_fn and stateful_eval are exclusive: one evaluation "
                "seam per optimizer"
            )
        from hpbandster_tpu.obs.timeline import ADMISSION, sweep_span

        #: seconds of the spans that ran before a ``run_stats`` row existed
        #: to hold them (construction, a run's planning and set-up): the
        #: next row appended takes them as its ``phase_s``
        self._phase_carry: Dict[str, float] = {}
        with sweep_span("construct", ADMISSION, self._phase_carry):
            self.configspace = configspace
            self.codec = build_space_codec(configspace)
            # conditional spaces: the condition DAG compiles to an on-device
            # activity mask (ops.sweep.compile_active_mask); raises for
            # condition forms without a device representation
            if configspace.get_conditions():
                from hpbandster_tpu.ops.sweep import compile_active_mask

                self.active_mask_fn = compile_active_mask(configspace, self.codec)
                self._conditions_sig = tuple(
                    repr(c) for c in configspace.get_conditions()
                )
            else:
                self.active_mask_fn = None
                self._conditions_sig = ()
            # forbidden clauses: compiled predicate + in-trace rejection
            # resampling; the clamp fallback is a host-verified valid config
            if configspace.get_forbiddens():
                from hpbandster_tpu.ops.sweep import compile_forbidden_mask

                self.forbidden_fn = compile_forbidden_mask(configspace, self.codec)
                # deterministic in the optimizer seed (not the space's shared
                # RNG), so the clamp result is reproducible run to run
                fb_rng = np.random.default_rng(
                    0xFB if seed is None else (int(seed) ^ 0xFB)
                )
                fb = configspace.to_vector(
                    configspace.sample_configuration(rng=fb_rng)
                )
                self._fallback_vector = np.nan_to_num(
                    np.asarray(fb, np.float32), nan=0.0
                )
                self._forbiddens_sig = tuple(
                    repr(c) for c in configspace.get_forbiddens()
                ) + (self._fallback_vector.tobytes(),)
            else:
                self.forbidden_fn = None
                self._fallback_vector = None
                self._forbiddens_sig = ()
            # fail fast on an objective of the wrong shape (_check_objective),
            # the first time this process meets the object: the check is a
            # whole Python trace of it, and its verdict can only be what it
            # was the construction before
            d = int(self.codec.kind.shape[0])
            if getattr(eval_fn, "lane_facts", None) is not None:
                # a maker that states its lane's facts (ops.fused.LaneFacts)
                # has stated a scalar loss with them: a lane that large
                # takes seconds of host time to trace, and a process that
                # paid them once would still pay them in its set-up
                traced = False
            else:
                lowest = float(min_budget)
                traced = check_once(
                    (stateful_eval if eval_fn is None else eval_fn,
                     d, lowest),
                    lambda: _check_objective(
                        eval_fn, stateful_eval, d, lowest),
                )
            #: 1 where this constructor traced its objective, 0 where
            #: the memo (or ``lane_facts``) answered: the first
            #: ``run_stats`` row takes it as its ``construct_traced``
            #: (the trace's seconds are ``construct``'s)
            self._construct_traced = int(traced)
            self.eval_fn = eval_fn
            self.stateful_eval = stateful_eval
            self.run_id = run_id
            self.eta = float(eta)
            self.min_budget = float(min_budget)
            self.max_budget = float(max_budget)
            self.min_points_in_model = min_points_in_model
            self.top_n_percent = int(top_n_percent)
            self.num_samples = int(num_samples)
            self.random_fraction = float(random_fraction)
            self.bandwidth_factor = float(bandwidth_factor)
            self.min_bandwidth = float(min_bandwidth)
            self.mesh = mesh
            self.axis = axis
            # Pallas acquisition scorer inside the sweep trace. Default (None):
            # ON whenever the backend is a TPU (its speed against the XLA
            # scorer is not measured on current code; HPB_USE_PALLAS=0 is the
            # explicit opt-out until a chip A/B settles it). =1 forces it even
            # off-TPU, where the kernel runs in the Pallas interpreter, like
            # explicitly passing use_pallas=True on a CPU/GPU backend. On a
            # TPU the kernel is always Mosaic-compiled, never interpreted.
            from hpbandster_tpu.ops.pallas_kde import pallas_available

            if use_pallas is None:
                import os

                env = os.environ.get("HPB_USE_PALLAS", "")
                use_pallas = True if env == "1" else (
                    False if env == "0" else pallas_available()
                )
            self.use_pallas = bool(use_pallas)
            self.pallas_interpret = self.use_pallas and not pallas_available()
            self.result_logger = result_logger
            self.working_directory = working_directory
            self.logger = logger or logging.getLogger("hpbandster_tpu.fused_bohb")
            self.rng = np.random.default_rng(seed)

            self.max_SH_iter = max_sh_iterations(min_budget, max_budget, eta)
            self.budgets = budget_ladder(min_budget, max_budget, eta)
            self.iterations: List[SuccessiveHalving] = []
            self.config: Dict[str, Any] = {
                "time_ref": None,
                "eta": self.eta,
                "min_budget": self.min_budget,
                "max_budget": self.max_budget,
                "budgets": list(self.budgets),
                "max_SH_iter": self.max_SH_iter,
                "min_points_in_model": min_points_in_model,
                "top_n_percent": top_n_percent,
                "num_samples": num_samples,
                "random_fraction": random_fraction,
                "bandwidth_factor": bandwidth_factor,
                "min_bandwidth": min_bandwidth,
            }
            #: stats for tests/benchmarks
            self.total_evaluated = 0
            #: per-chunk device timings (compile vs execute seconds), appended by
            #: every ``run()``
            self.run_stats: List[Dict[str, Any]] = []
            #: the AOT-compiled executable of the last chunk dispatched
            #: (``.as_text()``, ``.cost_analysis()``, shardings): what
            #: ``chip_smoke.py`` inspects to prove which program ran
            self.last_executable = None
            #: optional on-device promotion scorer (see FusedH2BO); None = the
            #: plain successive-halving raw-loss top-k
            self.promotion_rank_fn = None
            #: last run's decoded device-telemetry record (None until a run
            #: with the metrics plane on completes — obs/device_metrics.py)
            self.last_device_telemetry: Optional[Dict[str, Any]] = None

            # warm start (reference: previous_result= replays old data into the
            # model, SURVEY.md §5): old (config, budget, loss) observations seed
            # the device observation buffers; the old data rides into the final
            # Result as a finished pseudo-iteration under negative ids
            self._warm_v: Dict[float, np.ndarray] = {}
            self._warm_l: Dict[float, np.ndarray] = {}
            self.warmstart_iteration: List[Any] = []
            if previous_result is not None:
                self._ingest_previous_result(previous_result)

    def _ingest_previous_result(self, previous_result: Result) -> None:
        from hpbandster_tpu.core.warmstart import WarmStartIteration

        per_budget_v: Dict[float, List[np.ndarray]] = {}
        per_budget_l: Dict[float, List[float]] = {}
        id2conf = previous_result.get_id2config_mapping()
        for run in previous_result.get_all_runs(only_largest_budget=False):
            cfg = id2conf[run.config_id]["config"]
            vec = self.configspace.to_vector(cfg).astype(np.float32)
            if self.active_mask_fn is None:
                # condition-free: the device fit does not impute, so NaNs
                # (from foreign results) must not reach it
                vec = np.nan_to_num(vec, nan=0.0)
            b = float(run.budget)
            # crashed (None) losses register as maximally bad, like
            # BOHBKDE.new_result
            loss = np.inf if run.loss is None else float(run.loss)
            per_budget_v.setdefault(b, []).append(vec)
            per_budget_l.setdefault(b, []).append(loss)
        for b in per_budget_v:
            self._warm_v[b] = np.stack(per_budget_v[b])
            self._warm_l[b] = np.asarray(per_budget_l[b], np.float32)

        class _NoOpGenerator:
            def new_result(self, job, update_model=True):
                pass

        self.warmstart_iteration = [
            WarmStartIteration(previous_result, _NoOpGenerator())
        ]

    # ------------------------------------------------------------------ run
    def _plan(self, iteration: int):
        """Bracket shape for global iteration ``iteration`` — the
        get_next_iteration seam of the fused tier."""
        return hyperband_bracket(
            iteration, self.min_budget, self.max_budget, self.eta
        )

    def _sweep_driver(self, dynamic, incumbent_only=False,
                      **mode) -> SweepDriver:
        """This optimizer's sweep program behind the one driver of the
        device sweep (``ops/sweep_driver.py``); ``mode`` is the rest of
        ``SweepDriver``'s (``resident``, ``device_metrics``, ``cold``, ...)."""
        return SweepDriver(
            self.eval_fn, self.codec,
            dict(
                # exactly one of eval_fn and stateful_eval is non-None (ctor
                # contract), so the pair keys stateless and stateful
                # executables apart
                stateful_eval=self.stateful_eval,
                num_samples=self.num_samples,
                random_fraction=self.random_fraction,
                top_n_percent=self.top_n_percent,
                min_points_in_model=self.min_points_in_model,
                bandwidth_factor=self.bandwidth_factor,
                min_bandwidth=self.min_bandwidth,
                mesh=self.mesh,
                axis=self.axis,
                use_pallas=self.use_pallas,
                pallas_interpret=self.pallas_interpret,
                rank_fn=self.promotion_rank_fn,
                active_mask_fn=self.active_mask_fn,
                forbidden_fn=self.forbidden_fn,
                fallback_vector=self._fallback_vector,
            ),
            space_sig=(self._conditions_sig, self._forbiddens_sig),
            dynamic=dynamic,
            incumbent_only=incumbent_only,
            # the dynamic tier returns (and the warm inputs donate into)
            # the updated observation state, so consecutive chunks thread
            # it device-to-device across chunk boundaries — the ensemble
            # state itself is bracket-local scratch and never part of it
            thread_state=dynamic and not incumbent_only,
            warm_v=self._warm_v,
            warm_l=self._warm_l,
            **mode,
        )

    def run(
        self,
        n_iterations: int = 1,
        min_n_workers: int = 1,
        profile_dir: Optional[str] = None,
        chunk_brackets: Optional[int] = None,
        checkpoint_path: Optional[str] = None,
        dynamic_counts: Optional[bool] = None,
        resident: bool = False,
        device_metrics: Optional[bool] = None,
    ) -> Result:
        """Run brackets as fused device computation(s).

        ``n_iterations`` is the TOTAL bracket count including previous
        ``run()`` calls on this instance (Master.run's resume semantics):
        a second call only runs the remaining brackets, continuing the
        HyperBand bracket rotation — and its proposals see all earlier
        results (they thread into the next computation as warm data).

        ``chunk_brackets=None`` (default) compiles the whole remaining
        schedule into ONE program. Setting it to K runs the schedule in
        fused chunks of K brackets, threading the accumulated observations
        into each next chunk as warm data (identical model information, in
        stage-chunked form) — bounding program size for very long sweeps,
        streaming results (and ``result_logger`` lines) chunk by chunk,
        and leaving completed chunks' results intact if a later chunk dies.
        Host bookkeeping is PIPELINED: chunk k's reference-shaped replay
        runs while chunk k+1 executes on the device (``run_stats``
        records the hidden time as ``replay_overlap_s``), so streamed
        lines lag one chunk; with ``checkpoint_path`` set the replay is
        sequential (each checkpoint captures fully-replayed state).

        ``profile_dir`` captures a ``jax.profiler`` trace of the sweep
        (TensorBoard/Perfetto-viewable).

        ``checkpoint_path`` writes a fused-tier checkpoint (warm
        observations, bracket rotation, RNG state, replayed bookkeeping)
        after EVERY completed chunk — a killed chunked run resumes from the
        last boundary via :meth:`load_checkpoint` on a freshly-constructed
        optimizer with the same settings, and completes with results
        identical to an uninterrupted run.

        ``dynamic_counts=None`` (default) picks the executable style from
        the chunking knob: ``chunk_brackets`` set -> the dynamic-count
        sweep (observation counts are traced inputs over pow2-bucketed
        buffers, so consecutive chunks — and a checkpoint resume — reuse
        one compiled program until a capacity bucket doubles: O(log n)
        compiles per run where the static tier pays one compile per chunk,
        each chunk's counts being burned into its trace); unchunked ->
        the static tier (exact-count slices, the cheapest per-bracket
        model math). Pass True/False to force either. Both tiers are
        deterministic in the optimizer seed and draw from the SAME
        proposal distribution, but they are distinct RNG consumers (the
        dynamic tier's donor pick runs over the mask-padded buffer), so
        model-based brackets make different — equally valid — draws; the
        tiers are not bitwise twins, the same way the host trickle and
        batched tiers are not.

        ``resident=True`` compiles the schedule as ONE resident program:
        the HyperBand rotation's repeating round traces once and a
        ``lax.scan`` drives it over rounds (``ops/sweep.py``
        ``resident=True``), so bracket rotation, KDE refit and promotion
        never surface to host between brackets and program size is
        O(rotation) instead of O(brackets). One dispatch, one fetch —
        the bookkeeping replay and the final Result are identical to the
        unrolled dynamic tier on the same seed (bit-parity pinned in
        ``tests/test_resident.py``). Incompatible with
        ``chunk_brackets`` (it replaces chunking) and with
        ``dynamic_counts=False``. For the incumbent-only variant whose
        host traffic is one seed up + one incumbent down, see
        :meth:`run_incumbent`.

        ``device_metrics`` turns the in-trace metrics plane on
        (``ops/sweep.py`` ``device_metrics=True``): per-rung loss
        histograms, crash/promotion counts, KDE-refit flags and the
        incumbent trail accumulate ON DEVICE (payload O(schedule), never
        O(configs)) and decode at the end of the run into the obs
        pipeline — ``sweep.device_metrics.*`` / ``sweep.rung.*`` gauges
        plus one journaled ``device_telemetry`` record
        (``obs/device_metrics.py``). ``None`` (default) follows
        ``HPB_DEVICE_METRICS=1``; off otherwise — telemetry changes the
        compiled program, so the default is explicit, never inferred
        from the ambient bus.

        Every phase of the call is one ``obs.timeline.sweep_span``: a
        ``hpb:<name>`` region in any live profiler trace, a journal event
        when a sink listens, and always its seconds under ``phase_s`` of
        the chunk's ``run_stats`` row (docs/observability.md lists the
        names). ``run`` encloses the rest; a dotted name lies inside the
        span it is named after (``replay.configs`` in ``bracket_replay``,
        ``compile.*`` in ``compile_lookup``).
        """
        del min_n_workers  # API symmetry with Master.run; no worker pool here
        from hpbandster_tpu.obs.timeline import ADMISSION, sweep_span
        from hpbandster_tpu.obs.trace import current_trace, new_trace

        #: one trace identity for this run() call's whole sweep: every
        #: span, chunk record, compile event and the decoded device-
        #: telemetry record share it, so the flight recorder
        #: (obs/timeline.py) and summarize's trace_timelines can stitch the
        #: fused sweep — host phases AND the device loop — into one
        #: per-trace timeline whose phases sum to the sweep's wall. The
        #: per-evaluation records the replay emits stay outside it (they
        #: are jobs, counted one by one). An already-active trace (a
        #: serving layer driving this run) wins.
        sweep_trace = current_trace() or new_trace(self.run_id)
        with sweep_span(
            "run", ADMISSION, self._phase_carry, trace=sweep_trace
        ) as run_span:
            result = self._run_sweep(
                run_span, n_iterations, profile_dir, chunk_brackets,
                checkpoint_path, dynamic_counts, resident, device_metrics,
            )
        # after the last span has closed: the file holds finished rows
        self._write_timings_sidecar()
        return result

    def _run_sweep(self, run_span, n_iterations, profile_dir, chunk_brackets,
                   checkpoint_path, dynamic_counts, resident, device_metrics):
        """The body of :meth:`run`, inside its ``run`` span."""
        import functools

        from hpbandster_tpu.obs import timeline
        from hpbandster_tpu.obs.timeline import ADMISSION, PROMOTION, TRANSFER

        sweep_trace = run_span.trace
        sweep_span = functools.partial(timeline.sweep_span, trace=sweep_trace)

        first = len(self.iterations)
        #: where this call's spans add their seconds: until the first chunk
        #: adopts it, the dict that also holds construction's
        phase_s = self._phase_carry
        # planning is the sweep's admission work: schedule geometry +
        # bracket_created records, before anything boards the device
        with sweep_span("sweep_planning", ADMISSION, phase_s):
            plans = [self._plan(i) for i in range(first, int(n_iterations))]
        # everything between planning and the first dispatch — mesh
        # probing, tier policy, transfer baselines — is still admission
        # work on the timeline
        with sweep_span("sweep_setup", ADMISSION, phase_s):
            if self.config["time_ref"] is None:
                self.config["time_ref"] = time.time()
            if resident and chunk_brackets is not None:
                raise ValueError(
                    "resident=True replaces chunking (the whole schedule is one "
                    "scanned program) — drop chunk_brackets"
                )
            if resident and dynamic_counts is False:
                raise ValueError(
                    "resident=True requires the dynamic-count tier (observation "
                    "counts are scan carry) — drop dynamic_counts=False"
                )
            chunk = len(plans) if chunk_brackets is None else max(int(chunk_brackets), 1)
            # dynamic-count policy: chunked mode IS the compile-reuse tier. The
            # choice must not peek at the remaining schedule length — a run
            # killed after its first chunk and a longer uninterrupted run must
            # execute bit-identical first chunks for the checkpoint resume
            # guarantee to hold, so only the caller-visible chunking knob (and
            # nothing derived from how many brackets remain) may select the tier
            dynamic = resident or (
                (chunk_brackets is not None)
                if dynamic_counts is None else bool(dynamic_counts)
            )
            # (a cold resident sweep uploads zero-filled buffers here, the
            # bare seed in run_sharded_fused_sweep: ROADMAP, named debt)
            driver = self._sweep_driver(
                dynamic, resident=resident, device_metrics=device_metrics,
                cold="stream_all", trace=sweep_trace, profile_dir=profile_dir,
            )
            done = first
            #: deferred host bookkeeping of the PREVIOUS chunk: replaying the
            #: reference-shaped Datum/SuccessiveHalving state machine is the
            #: expensive host-path term (docs/perf_notes.md, ~20% of warm
            #: wall), and the NEXT chunk's device inputs only need the cheap
            #: _accumulate_obs fold — so the replay runs while the device
            #: executes the next chunk instead of serializing with it
            pending_replay = None
            overlap_s = None

        def _flush_replay():
            """Idempotent: runs the deferred replay exactly once. Clears
            the slot BEFORE replaying so a replay crash can never re-run
            half-replayed bookkeeping (which would duplicate Datum
            registrations)."""
            nonlocal pending_replay, overlap_s
            if pending_replay is None:
                return
            job, pending_replay = pending_replay, None
            t_r = time.perf_counter()
            job()
            overlap_s = time.perf_counter() - t_r

        while plans:
            chunk_plans, plans = plans[:chunk], plans[chunk:]
            # this chunk's phase seconds, its row's ``phase_s``: the first
            # row takes what construction and set-up carried
            phase_s, self._phase_carry = self._phase_carry, {}
            construct_traced, self._construct_traced = self._construct_traced, 0
            seed = np.uint32(self.rng.integers(2**32, dtype=np.uint32))
            overlap_s = None
            try:
                # pipelining: the previous chunk's bookkeeping replays
                # inside this chunk's device window
                outputs, stat = driver.run_chunk(
                    chunk_plans, seed, phase_s, first_bracket=done,
                    while_device_runs=_flush_replay,
                )
                self.last_executable = driver.last_executable
                if resident:
                    # scan-stacked per-rotation-position outputs -> the
                    # flat per-bracket list the replay below consumes
                    from hpbandster_tpu.ops.sweep import (
                        resident_rotation,
                        unstack_resident_outputs,
                    )

                    with sweep_span("unstack", TRANSFER, phase_s):
                        _, n_rounds, _ = resident_rotation(chunk_plans)
                        outputs = unstack_resident_outputs(outputs, n_rounds)
            finally:
                # any failure above (arg building, a bucket-doubling
                # recompile, dispatch, fetch) must still land the COMPLETED
                # previous chunk's results in self.iterations — otherwise a
                # retry run() would re-execute a chunk whose observations
                # _accumulate_obs already folded into the warm data
                # (duplicated observations). And a replay crash here must
                # not mask the device error already being raised.
                in_flight = sys.exc_info()[1] is not None
                try:
                    _flush_replay()  # no-op when the overlap point ran it
                except Exception:
                    if not in_flight:
                        raise
                    self.logger.exception(
                        "deferred replay of the previous chunk failed "
                        "while a later chunk was already failing; its "
                        "results are missing from this Result"
                    )
            from hpbandster_tpu.ops.fused import _unpack_stages

            # chunk accounting — run_stats row, the sweep_chunk journal
            # record (and its sink write), per-job attribution info —
            # is host bookkeeping the timeline charges to promotion
            with sweep_span("chunk_accounting", PROMOTION, phase_s):
                driver.journal(stat, len(self.run_stats), phase_s)
                stat.update({
                    "chunk_index": len(self.run_stats),
                    "dynamic_counts": bool(dynamic),
                    # evaluations whose replay built a Job: all of them
                    # under a result logger or a journal sink, else none
                    "replay_jobs_built": 0,
                    # first-rung configurations that from_vectors decoded a
                    # column at a time; the rest went through from_vector
                    "replay_configs_by_column": 0,
                    # 1 on the first row of an optimizer whose constructor
                    # traced its objective for the admission check
                    "construct_traced": construct_traced,
                    # seconds per span name, this chunk's share of the
                    # sweep's wall; the spans that close after this point
                    # (this one, obs_fold, the chunk's bracket_replay
                    # whenever it runs, the call's result and run on its
                    # last row) add to the same dict
                    "phase_s": phase_s,
                })
                if overlap_s is not None:
                    # host replay of the PRIOR chunk that ran inside this
                    # chunk's device window
                    stat["replay_overlap_s"] = round(overlap_s, 4)
                stat.update(_lane_accounting(self.eval_fn, chunk_plans, outputs))
                self.run_stats.append(stat)
                # per-job device-timing attribution (VERDICT r1 #10): every run
                # of this chunk carries the chunk's compile/execute seconds into
                # Result.info / results.json, so timing claims reproduce from
                # run artifacts alone
                job_info = {
                    "fused_chunk": stat["chunk_index"],
                    "chunk_compile_s": stat["build_compile_s"],
                    "chunk_compile_cache_hit": stat["compile_cache_hit"],
                    "chunk_execute_s": stat["execute_fetch_s"],
                    "chunk_evaluations": stat["evaluations"],
                }

            staged = []
            # the eager observation fold is successive-halving bookkeeping
            # on the host path — a promotion-phase slice on the timeline
            with sweep_span("obs_fold", PROMOTION, phase_s):
                for b_i, (plan, out) in enumerate(
                    zip(chunk_plans, outputs), start=done
                ):
                    stages = _unpack_stages(
                        (out.idx_packed, out.loss_packed), plan.num_configs
                    )
                    staged.append((b_i, plan, out, stages))
                    # accumulated EAGERLY: later chunks AND later run()
                    # calls consume these as warm data — the model, like
                    # the Master's, sees all past results
                    self._accumulate_obs(plan, out, stages)

            def replay_now(staged=staged, job_info=job_info, stat=stat):
                # the replay is promotion bookkeeping wherever it runs —
                # here, or inside the next chunk's device window — and its
                # seconds and its count go to the row of the chunk it replays
                span = functools.partial(sweep_span, totals=stat["phase_s"])
                with span("bracket_replay", PROMOTION):
                    for b_i, plan, out, stages in staged:
                        self._replay_bracket(
                            b_i, plan, out, stages, job_info, span, stat
                        )

            done += len(chunk_plans)
            if checkpoint_path is not None:
                # the checkpoint captures replayed bookkeeping at this
                # boundary, so checkpointed runs replay sequentially —
                # resume-equals-uninterrupted stays bitwise either way
                # (replay content never depends on when it runs)
                replay_now()
                self.save_checkpoint(checkpoint_path)
            else:
                pending_replay = replay_now
        if pending_replay is not None:
            # last chunk has no successor to hide behind
            pending_replay()
        # the call's result and run spans land on its last row
        run_span.totals = phase_s
        with sweep_span("result", PROMOTION, phase_s):
            # this call's whole transfer bill as gauges, and the decoded
            # device telemetry (None with the metrics plane off)
            _, decoded = driver.finish()
            if decoded is not None:
                self.last_device_telemetry = decoded
            return Result(
                list(self.iterations) + self.warmstart_iteration, self.config
            )

    def run_incumbent(
        self,
        n_iterations: int = 1,
        profile_dir: Optional[str] = None,
        resident: bool = True,
        device_metrics: Optional[bool] = None,
    ) -> Dict[str, Any]:
        """Incumbent-only (resident) sweep: the whole multi-bracket
        schedule as one device program whose only host traffic is one
        uint32 seed (plus any warm observations) up and one
        :class:`~hpbandster_tpu.ops.sweep.SweepIncumbent` down — one
        vector + one scalar + per-bracket bests, whatever the config
        count. This is the ROADMAP "in-trace everything" mode: per-rung
        promotion decisions never leave the device, so there is NO
        per-config Result bookkeeping; instead the payload is journaled
        as a ``sweep_incumbent`` audit record (``obs replay`` re-scores
        it) with the sweep's measured h2d/d2h byte bill attached, and the
        per-sweep transfer gauges are published. Does not advance
        :attr:`iterations` — it is a one-shot query, not a resumable run.

        Returns a stats dict: ``incumbent`` (vector/loss/bracket/
        per-bracket bests), ``evaluations``, compile/execute seconds and
        the ``transfers`` delta dict.

        ``device_metrics`` (default: ``HPB_DEVICE_METRICS``) turns the
        in-trace metrics plane on: the O(schedule) telemetry pytree
        rides the incumbent's d2h — per-rung histograms and crash/
        promotion counts for a sweep whose per-rung decisions otherwise
        never leave the device — decoded into the gauges + one
        ``device_telemetry`` record, and returned under
        ``"device_telemetry"``. ``"phase_s"`` holds the call's seconds by
        span name, the names :meth:`run` uses for the phases this entry
        point has.
        """
        from hpbandster_tpu.obs.timeline import ADMISSION, PROMOTION, sweep_span
        from hpbandster_tpu.obs.trace import current_trace, new_trace, use_trace
        from hpbandster_tpu.parallel.mesh import is_multiprocess_mesh

        if is_multiprocess_mesh(self.mesh):
            raise ValueError(
                "run_incumbent drives single-process meshes; use "
                "parallel.multihost.run_sharded_fused_sweep(resident=True) "
                "for the SPMD pod tier"
            )
        if int(n_iterations) < 1:
            raise ValueError("run_incumbent needs n_iterations >= 1")
        phase_s: Dict[str, float] = {}
        inc_trace = current_trace() or new_trace(self.run_id)
        with use_trace(inc_trace), sweep_span("run", ADMISSION, phase_s):
            with sweep_span("sweep_planning", ADMISSION, phase_s):
                plans = [self._plan(i) for i in range(int(n_iterations))]
            # the chunked tier's capacity policy, so a warm-started incumbent
            # query shares buffer shapes with runs that agree on history; its
            # buffers are padded on the host whatever the mesh
            driver = self._sweep_driver(
                True, resident=resident, incumbent_only=True,
                device_metrics=device_metrics, trace=inc_trace,
                profile_dir=profile_dir,
            )
            seed = np.uint32(self.rng.integers(2**32, dtype=np.uint32))
            inc, stat = driver.run_chunk(plans, seed, phase_s)
            self.last_executable = driver.last_executable
            with sweep_span("result", PROMOTION, phase_s):
                # the resident sweep is one chunk: its sweep_chunk record
                # is the rung_compute interval the flight recorder lays the
                # decoded per-rung sections onto
                driver.journal(stat, 0, phase_s)
                link, decoded = driver.finish()
                incumbent = {
                    "vector": [float(x) for x in np.asarray(inc.vector)],
                    "loss": float(np.asarray(inc.loss)),
                    "bracket": int(np.asarray(inc.bracket)),
                    "per_bracket_loss": [
                        float(x) for x in np.asarray(inc.per_bracket_loss)
                    ],
                }
                obs.emit_sweep_incumbent(
                    evaluations=stat["evaluations"],
                    d2h_bytes=link["transfer_bytes_d2h"],
                    h2d_bytes=link["transfer_bytes_h2d"],
                    host_syncs=link["transfers_h2d"] + link["transfers_d2h"],
                    **incumbent,
                )
                out = {
                    "incumbent": incumbent,
                    "evaluations": stat["evaluations"],
                    "build_compile_s": stat["build_compile_s"],
                    "compile_cache_hit": stat["compile_cache_hit"],
                    "execute_fetch_s": stat["execute_fetch_s"],
                    "transfers": link,
                    "phase_s": phase_s,
                }
                if decoded is not None:
                    self.last_device_telemetry = decoded
                    out["device_telemetry"] = decoded
        return out

    def _write_timings_sidecar(self) -> None:
        """Persist ``run_stats`` as ``fused_timings.json`` next to the
        result logger's JSONL files (when one is configured). Entries merge
        with whatever is already on disk — a second optimizer sharing the
        logger (warm-start flow) or a checkpoint-resumed run appends rather
        than clobbering the earlier timing trail; entries already present
        (restored-from-checkpoint stats) are not duplicated. Each row's
        ``phase_s`` is the sweep's breakdown by span name: what a stalled
        sweep names its phase with, no profiler needed."""
        results_fn = getattr(self.result_logger, "results_fn", None)
        if not results_fn:
            return
        import json
        import os

        path = os.path.join(os.path.dirname(results_fn), "fused_timings.json")
        existing: List[Dict[str, Any]] = []
        if os.path.exists(path):
            try:
                with open(path) as fh:
                    existing = json.load(fh)
            except (OSError, ValueError):
                existing = []
        # a row restored from a checkpoint lacks the spans that closed
        # after the checkpoint was written: the same row all the same, and
        # the file's copy is the finished one
        def identity(row):
            return {k: v for k, v in row.items() if k != "phase_s"}

        on_disk = [identity(s) for s in existing]
        merged = existing + [
            s for s in self.run_stats if identity(s) not in on_disk
        ]
        with open(path, "w") as fh:
            json.dump(merged, fh, indent=1)

    # ---------------------------------------------------------- checkpoint
    def save_checkpoint(self, path: str) -> None:
        """Fused-tier twin of ``core.checkpoint.save_checkpoint``: warm
        observations + bracket rotation + RNG state + replayed bookkeeping
        at the last chunk boundary."""
        from hpbandster_tpu.core.checkpoint import save_fused_checkpoint

        t0 = time.monotonic()
        save_fused_checkpoint(self, path)
        obs.emit(
            obs.CHECKPOINT_WRITTEN,
            path=path, duration_s=round(time.monotonic() - t0, 6),
        )

    def load_checkpoint(self, path: str) -> None:
        """Restore into a freshly-constructed optimizer (same constructor
        settings; bracket shapes are verified). The next ``run()`` continues
        with the remaining brackets and reproduces an uninterrupted run."""
        from hpbandster_tpu.core.checkpoint import load_fused_checkpoint

        load_fused_checkpoint(self, path)

    def _accumulate_obs(self, plan, out, stages) -> None:
        """Fold one replayed bracket's (vector, loss) observations into the
        warm buffers so the next chunk's device model sees them."""
        vectors = np.asarray(out.vectors)
        for (idx_s, losses_s), budget in zip(stages, plan.budgets):
            b = float(budget)
            vecs = vectors[np.asarray(idx_s)]
            losses = np.where(
                np.isnan(losses_s), np.inf, losses_s
            ).astype(np.float32)
            if b in self._warm_v:
                self._warm_v[b] = np.concatenate([self._warm_v[b], vecs])
                self._warm_l[b] = np.concatenate([self._warm_l[b], losses])
            else:
                self._warm_v[b] = vecs.astype(np.float32)
                self._warm_l[b] = losses

    # --------------------------------------------------------------- replay
    def _replay_bracket(
        self, b_i: int, plan, out, stages, job_info: Optional[Dict], span,
        stat: Dict,
    ) -> None:
        """One bracket's device outputs into the reference's bookkeeping.
        ``span(name, phase)`` opens a ``sweep_span`` of the caller's: its
        row's ``phase_s``, its sweep's trace. ``stat`` is that row: its
        ``replay_configs_by_column`` takes the configurations decoded a
        column at a time, its ``replay_jobs_built`` the ``Job`` objects the
        runs' replay built (:meth:`_replay_runs`)."""
        from hpbandster_tpu.obs.timeline import PROMOTION

        vectors = np.asarray(out.vectors)
        mb_mask = np.asarray(out.model_based, dtype=bool)
        promotion_sets = [set(int(i) for i in idx) for idx, _ in stages[1:]]
        promotion_sets.append(set())

        def no_sampler(budget):  # replay adds every config explicitly
            raise RuntimeError("fused replay must not sample fresh configs")

        # journal parity with the Master tiers: the replayed bracket
        # announces its plan, then its config_sampled/promotion_decision
        # records flow from the shared BaseIteration bookkeeping below
        obs.emit_bracket_created(
            b_i, plan.num_configs, plan.budgets,
            eta=self.eta, random_fraction=self.random_fraction,
        )
        it = _ReplayIteration(
            HPB_iter=b_i,
            num_configs=list(plan.num_configs),
            budgets=list(plan.budgets),
            config_sampler=no_sampler,
            promotion_sets=promotion_sets,
            result_logger=self.result_logger,
        )
        self.iterations.append(it)

        # two spans a bracket, never one an evaluation: decoding the
        # bracket's configurations, then replaying its runs
        with span("replay.configs", PROMOTION):
            first_rung = vectors[: plan.num_configs[0]]
            if self.configspace.decodes_by_column(first_rung):
                stat["replay_configs_by_column"] += len(first_rung)
            it.add_configurations(
                self.configspace.from_vectors(first_rung),
                [
                    {
                        "model_based_pick": picked,
                        # decision detail (KDE budget, l/g score) stayed on
                        # device; the audit record still attributes the arm
                        "sample_reason": "fused_sweep",
                        "fused_sweep": True,
                    }
                    for picked in mb_mask[: len(first_rung)].tolist()
                ],
            )
        with span("replay.runs", PROMOTION):
            stat["replay_jobs_built"] += self._replay_runs(it, stages, job_info)

    def _replay_runs(self, it, stages, job_info) -> int:
        """Every run of one replayed bracket, rung by rung from the
        device's arrays: each lane's ``Datum`` takes what
        ``register_result`` would write, then one ``process_results()``
        advances the bracket and makes the rung's promotion records.
        Nothing is ever pending here, so ``get_next_run`` is not polled:
        it rescans the bracket from its first entry on every call.

        A ``Job`` is an object for someone to look at: it is built,
        journalled and handed to the result logger only while a logger or
        a sink is attached. Returns how many were built."""
        now = time.time
        jobs_built = 0
        for stage_no, (idx, losses) in enumerate(stages):
            budget = it.budgets[stage_no]
            observed = self.result_logger is not None or obs.get_bus().active
            # ascending lane index is the bracket's insertion order, the
            # order get_next_run handed runs out in: the journal and the
            # incumbent trajectory (sorted by 'finished') keep it
            order = np.argsort(idx, kind="stable")
            for lane, loss in zip(
                idx[order].tolist(), losses[order].tolist()
            ):
                config_id = (it.HPB_iter, 0, lane)
                datum = it.data[config_id]
                if datum.status != Status.QUEUED or datum.budget != budget:
                    raise RuntimeError(
                        f"device ran {config_id} at budget {budget}, the "
                        f"bracket holds it {datum.status.name} at "
                        f"{datum.budget}"
                    )
                # only NaN means crashed; a genuine +/-inf loss (diverged
                # run) is a valid maximally-bad result
                crashed = loss != loss
                exception = (
                    f"non-finite loss {loss!r} at budget {budget}"
                    if crashed else None
                )
                info = None if crashed else dict(job_info or {})
                if observed:
                    job = Job(
                        config_id,
                        config=datum.config,
                        budget=budget,
                        working_directory=self.working_directory,
                    )
                    job.time_it("submitted")
                    job.time_it("started")
                    if not crashed:
                        job.result = {"loss": loss, "info": info}
                    job.exception = exception
                    job.time_it("finished")
                    stamps = dict(job.timestamps)
                    # the fused tier's loss-carrying result record — journal
                    # parity with Master.job_callback (no run_s: the
                    # evaluation executed inside a fused device chunk,
                    # per-job host timing would be fiction; sweep_chunk
                    # carries the real durations)
                    obs.emit(
                        obs.JOB_FAILED if crashed else obs.JOB_FINISHED,
                        config_id=list(config_id), budget=budget,
                        # non-finite (NaN-crashed or inf-diverged) -> null:
                        # bare NaN/Infinity is not strict JSON (same rule
                        # as the master)
                        loss=loss if math.isfinite(loss) else None,
                    )
                    if self.result_logger is not None:
                        self.result_logger(job)
                    jobs_built += 1
                else:
                    stamps = {
                        "submitted": now(), "started": now(), "finished": now(),
                    }
                datum.results[budget] = None if crashed else loss
                datum.exceptions[budget] = exception
                datum.time_stamps[budget] = stamps
                if not crashed:
                    datum.infos[budget] = info
                # crashed evaluations stay in the bracket as REVIEW with a
                # None loss, as register_result leaves them: never promoted
                datum.status = Status.REVIEW
                self.total_evaluated += 1
            if not it.process_results():
                raise RuntimeError(
                    f"bracket {it.HPB_iter} did not advance past rung "
                    f"{stage_no}: the device's lanes do not fill it"
                )
        return jobs_built

    def shutdown(self, shutdown_workers: bool = False) -> None:
        """API symmetry with Master; nothing to tear down."""


class FusedHyperBand(FusedBOHB):
    """HyperBand on the fused whole-sweep path: identical bracket schedule,
    pure-random proposals (no KDE is even traced — ``min_points_in_model``
    is set unreachably high, so the model gate never opens)."""

    def __init__(self, *args, **kwargs):
        kwargs["random_fraction"] = 1.0
        kwargs["min_points_in_model"] = 2**30
        super().__init__(*args, **kwargs)


class FusedH2BO(FusedBOHB):
    """H2BO on the fused path: promotions rank by an ON-DEVICE power-law
    learning-curve extrapolation of each config's loss to the bracket's
    final budget (``ops.bracket.power_law_extrapolate``, the jittable twin
    of ``models.learning_curves.PowerLawModel``) instead of the raw
    current-stage loss; KDE proposals are unchanged BOHB."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        from hpbandster_tpu.ops.bracket import power_law_extrapolate

        self.promotion_rank_fn = power_law_extrapolate


class FusedRandomSearch(FusedHyperBand):
    """RandomSearch on the fused path: degenerate single-stage brackets
    sized like the matching HyperBand bracket's first stage, all evaluated
    at ``max_budget`` (the reference baseline, SURVEY.md §2 'RandomSearch
    optimizer')."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # host RandomSearch parity: every run executes at max_budget, so the
        # Result's HB_config must not advertise the unused ladder
        self.config["budgets"] = [self.max_budget]

    def _plan(self, iteration: int):
        base = hyperband_bracket(
            iteration, self.min_budget, self.max_budget, self.eta
        )
        return BracketPlan(
            num_configs=(base.num_configs[0],), budgets=(self.max_budget,)
        )
