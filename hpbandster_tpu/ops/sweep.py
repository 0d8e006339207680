"""Whole-sweep fusion: an entire multi-bracket BOHB run as ONE device program.

The key observation: for the batched executor (static worker set, one
bracket at a time) the complete BOHB sweep has a **static dataflow**. Bracket
shapes come from the HyperBand arithmetic, observation counts per budget
accumulate deterministically stage by stage, and therefore the good/bad KDE
split sizes, the "largest budget with a trained model" choice, and every
``top_k`` promotion width are Python constants at trace time. Only the data
values are dynamic. So the *whole sweep* — proposal sampling, KDE fits,
stage evaluations, promotion decisions — jits into a single XLA computation
taking one uint32 seed and returning every bracket's configs and losses.

Why it matters: the per-bracket path pays ~3 host<->device round-trips per
bracket (proposal fetch + packed-result fetch). The fused sweep pays ONE
dispatch + one result fetch for the entire run.

Reference semantics reproduced on-device (SURVEY.md §2 "BOHB config
generator", §3.4): per-budget good/bad KDE split at ``top_n_percent``,
``min_points_in_model`` gate, largest-trained-budget model selection,
``random_fraction`` interleave, truncnorm-around-good-points candidates
scored by ``l(x)/g(x)``, crashed runs recorded as maximally bad. Conditional
spaces ARE supported: the condition DAG compiles to an on-device activity
predicate (:func:`compile_active_mask`), inactive dims evaluate as 0 and are
donor-imputed before KDE fits (host parity with
``BOHBKDE.impute_conditional_data``); forbidden clauses compile to a device
predicate with in-trace rejection resampling
(:func:`compile_forbidden_mask`). Condition forms without a numeric device
representation (e.g. order comparisons on categorical parents) raise at
construction — the per-bracket path remains the fallback for those.
"""

from __future__ import annotations

from typing import Any, Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hpbandster_tpu.obs.runtime import tracked_jit
from hpbandster_tpu.ops.bracket import BracketPlan
from hpbandster_tpu.ops.fused import (
    _CRASH_RANK,
    _pack_stages,
    StatefulEval,
    fused_sh_bracket,
    shard_rows,
    stage_telemetry,
)
from hpbandster_tpu.ops.kde import (
    KDE,
    fit_kde_pair_masked,
    impute_conditional_masked,
    normal_reference_bandwidths,
    propose,
)

__all__ = ["SpaceCodec", "build_space_codec", "quantize_unit", "random_unit",
           "random_unit_sharded", "compile_active_mask",
           "compile_forbidden_mask", "make_fused_sweep_fn",
           "SweepBracketOutput", "SweepIncumbent", "plan_additions",
           "pow2_capacities", "ResidentSweepOutputs", "resident_rotation",
           "unstack_resident_outputs", "DeviceMetrics",
           "init_device_metrics", "init_lane_state", "decode_lane_state",
           "sweep_donation_safe", "StatefulEval"]


def pow2_capacities(counts: dict, floor: int = 256) -> dict:
    """Pow2-bucketed observation capacities with a generous floor — THE
    one definition of the dynamic tier's buffer-shape policy (see the
    rationale at the chunked driver's call site): ``FusedBOHB.run`` /
    ``run_incumbent``, the sharded driver, and the parity tests must all
    agree on it or executable sharing (and the checkpoint-resume shape
    guarantee) silently breaks."""
    floor = max(int(floor), 1)
    return {
        float(b): 1 << max(int(n) - 1, floor - 1).bit_length()
        for b, n in counts.items()
    }


def plan_additions(plans: Sequence[BracketPlan]) -> dict:
    """Per-budget observation counts a plan sequence appends — the ONE
    definition shared by capacity seeding, the dynamic warm-count clamp,
    and ``FusedBOHB``'s bucket sizing (they must agree or the three
    silently drift)."""
    out: dict = {}
    for plan in plans:
        for k, b in zip(plan.num_configs, plan.budgets):
            out[float(b)] = out.get(float(b), 0) + int(k)
    return out


class SpaceCodec(NamedTuple):
    """Static per-dim description of a search space, enough to quantize and
    sample unit-hypercube vectors entirely on-device (conditions and
    forbiddens live in separately compiled predicates, not in the codec).

    Built host-side from a ``ConfigurationSpace`` (:func:`build_space_codec`)
    and closed over at trace time — all arrays are plain numpy.

    dim kinds: 0 = float, 1 = integer, 2 = categorical/ordinal (index repr),
    3 = constant.
    """

    kind: np.ndarray      # int32[d]
    log: np.ndarray       # bool[d]
    lower: np.ndarray     # float64[d] (1.0-safe for non-log dims)
    upper: np.ndarray     # float64[d]
    q: np.ndarray         # float64[d]; NaN = no quantization
    cards: np.ndarray     # int32[d] choices per discrete dim (0 = continuous)
    vartypes: np.ndarray  # int32[d] KDE vartype codes ('c'=0,'u'=1,'o'=2)
    logits: np.ndarray    # float32[d, kmax] sampling log-probs, -inf padded

    @property
    def signature(self) -> Tuple:
        """Hashable identity for compile caches."""
        return tuple(
            (a.tobytes(), a.shape) for a in self
        )


def build_space_codec(configspace) -> SpaceCodec:
    """Extract the static codec. Conditions are supported on-device via
    :func:`compile_active_mask`, forbiddens via :func:`compile_forbidden_mask`
    + in-trace rejection resampling (``make_fused_sweep_fn``)."""
    from hpbandster_tpu.space.hyperparameters import (
        CategoricalHyperparameter,
        Constant,
        OrdinalHyperparameter,
        UniformFloatHyperparameter,
        UniformIntegerHyperparameter,
    )

    hps = configspace.get_hyperparameters()
    d = len(hps)
    kind = np.zeros(d, np.int32)
    log = np.zeros(d, bool)
    lower = np.ones(d, np.float64)
    upper = np.full(d, 2.0, np.float64)
    q = np.full(d, np.nan, np.float64)
    cards = np.zeros(d, np.int32)
    kmax = max([hp.num_choices for hp in hps] + [1])
    logits = np.full((d, kmax), -np.inf, np.float32)

    for i, hp in enumerate(hps):
        if isinstance(hp, Constant):
            kind[i] = 3
            cards[i] = 1
            logits[i, 0] = 0.0
        elif isinstance(hp, UniformFloatHyperparameter):
            kind[i] = 0
            log[i] = hp.log
            lower[i], upper[i] = hp.lower, hp.upper
            if hp.q is not None:
                q[i] = hp.q
        elif isinstance(hp, UniformIntegerHyperparameter):
            kind[i] = 1
            log[i] = hp.log
            lower[i], upper[i] = hp.lower, hp.upper
        elif isinstance(hp, CategoricalHyperparameter):
            kind[i] = 2
            cards[i] = hp.num_choices
            logits[i, : hp.num_choices] = np.log(
                np.maximum(np.asarray(hp.probabilities, np.float64), 1e-300)
            )
        elif isinstance(hp, OrdinalHyperparameter):
            kind[i] = 2
            cards[i] = hp.num_choices
            logits[i, : hp.num_choices] = 0.0
        else:
            raise ValueError(f"unsupported hyperparameter type {type(hp).__name__}")
    return SpaceCodec(
        kind=kind, log=log, lower=lower, upper=upper, q=q, cards=cards,
        vartypes=np.asarray(configspace.vartypes()), logits=logits,
    )


def _int_log_bounds(codec: SpaceCodec) -> Tuple[np.ndarray, np.ndarray]:
    """The reference codec's widened log bounds for integer dims
    (hyperparameters.py UniformIntegerHyperparameter)."""
    lo = np.where(
        codec.lower > 1, codec.lower - 0.4999, np.maximum(codec.lower, 1) * 0.5001
    )
    hi = codec.upper + 0.4999
    return lo, hi


def quantize_unit(codec: SpaceCodec, u: jax.Array) -> jax.Array:
    """Jittable twin of host ``to_vector(from_vector(u))``: snap
    unit-hypercube vectors to representable configurations. (Activity of
    conditional dims is decided separately by :func:`compile_active_mask`.)

    ``u`` is ``f32[..., d]``. Bit-level parity with the host codec is not
    required (both are fixed points of each other's rounding; the bin-center
    integer convention makes the decode robust to f32 rounding).
    """
    kind = jnp.asarray(codec.kind)
    u_raw = u.astype(jnp.float32)
    # float/int dims live in [0,1]; categorical dims hold raw choice indices
    u = jnp.clip(u_raw, 0.0, 1.0)

    # floats: identity unless quantized (q), then value-space snap
    lo = jnp.asarray(codec.lower, jnp.float32)
    hi = jnp.asarray(codec.upper, jnp.float32)
    safe_lo = jnp.maximum(lo, 1e-30)
    log_lo, log_hi = jnp.log(safe_lo), jnp.log(jnp.maximum(hi, 1e-30))
    val_lin = lo + u * (hi - lo)
    val_log = jnp.exp(log_lo + u * (log_hi - log_lo))
    val = jnp.where(jnp.asarray(codec.log), val_log, val_lin)
    qs = jnp.asarray(np.nan_to_num(codec.q, nan=1.0), jnp.float32)
    has_q = jnp.asarray(np.isfinite(codec.q))
    val_q = jnp.clip(jnp.round(val / qs) * qs, lo, hi)
    enc_lin = (val_q - lo) / jnp.maximum(hi - lo, 1e-30)
    enc_log = (jnp.log(jnp.maximum(val_q, 1e-30)) - log_lo) / jnp.maximum(
        log_hi - log_lo, 1e-30
    )
    u_float = jnp.where(
        has_q,
        jnp.clip(jnp.where(jnp.asarray(codec.log), enc_log, enc_lin), 0.0, 1.0),
        u,
    )

    # integers: decode (bin-center / widened-log), round, re-encode
    ilo, ihi = _int_log_bounds(codec)
    ilo = jnp.asarray(ilo, jnp.float32)
    ihi = jnp.asarray(ihi, jnp.float32)
    n_int = jnp.maximum(hi - lo + 1.0, 1.0)
    v_lin = lo - 0.5 + u * n_int
    log_ilo = jnp.log(jnp.maximum(ilo, 1e-30))
    log_ihi = jnp.log(jnp.maximum(ihi, 1e-30))
    v_log = jnp.exp(log_ilo + u * (log_ihi - log_ilo))
    vi = jnp.clip(jnp.round(jnp.where(jnp.asarray(codec.log), v_log, v_lin)), lo, hi)
    enc_i_lin = (vi - lo + 0.5) / n_int
    enc_i_log = jnp.clip(
        (jnp.log(jnp.maximum(vi, 1e-30)) - log_ilo)
        / jnp.maximum(log_ihi - log_ilo, 1e-30),
        0.0,
        1.0,
    )
    u_int = jnp.where(jnp.asarray(codec.log), enc_i_log, enc_i_lin)

    # categorical / ordinal: snap to the nearest index
    kf = jnp.maximum(jnp.asarray(codec.cards, jnp.float32), 1.0)
    u_cat = jnp.clip(jnp.round(u_raw), 0.0, kf - 1.0)

    out = jnp.where(kind == 0, u_float, u)
    out = jnp.where(kind == 1, u_int, out)
    out = jnp.where(kind == 2, u_cat, out)
    out = jnp.where(kind == 3, 0.0, out)
    return out


def random_unit(codec: SpaceCodec, key: jax.Array, n: int) -> jax.Array:
    """``n`` uniform configuration vectors, matching the host sampler's
    semantics per dim (uniform unit for float/int, weighted categorical,
    uniform ordinal, 0 for constants). Returns un-quantized ``f32[n, d]`` —
    pass through :func:`quantize_unit` before evaluating."""
    d = codec.kind.shape[0]
    k_u, k_c = jax.random.split(key)
    u = jax.random.uniform(k_u, (n, d))
    idx = jax.random.categorical(
        k_c, jnp.asarray(codec.logits)[None, :, :], axis=-1, shape=(n, d)
    ).astype(jnp.float32)
    kind = jnp.asarray(codec.kind)
    out = jnp.where(kind == 2, idx, u)
    out = jnp.where(kind == 3, 0.0, out)
    return out


def random_unit_sharded(
    codec: SpaceCodec, key: jax.Array, n: int, n_shards: int
) -> jax.Array:
    """Per-shard PRNG derivation of :func:`random_unit` for a config batch
    sharded ``n_shards`` ways.

    Shard ``s`` draws its ``n // n_shards`` rows from
    ``jax.random.fold_in(key, s)`` — each shard's stream is independent of
    the others and of the batch's total size, so under a sharded jit every
    device generates exactly its own rows locally (no sampled bytes cross
    the ICI before evaluation). With ``n_shards == 1`` the base key is used
    UNFOLDED, so the sharded sampler on a 1-device mesh is bit-identical
    to :func:`random_unit` (the parity bar in ``tests/test_sharded.py``).
    Different shard counts are distinct — equally valid — RNG consumers,
    the same contract as the dynamic-count tier (docs/perf_notes.md).
    """
    n_shards = max(int(n_shards), 1)
    if n_shards == 1:
        return random_unit(codec, key, n)
    if n % n_shards != 0:
        raise ValueError(
            f"sharded sampling needs n % n_shards == 0, got {n} rows over "
            f"{n_shards} shards — pad the stage-0 count to a mesh multiple "
            "(parallel.mesh.pad_to_shards / ops.bracket.mesh_aligned_plan)"
        )
    per = n // n_shards
    return jnp.concatenate(
        [
            random_unit(codec, jax.random.fold_in(key, s), per)
            for s in range(n_shards)
        ]
    )


def _decode_values(codec: SpaceCodec, q: jax.Array) -> jax.Array:
    """Decode one quantized unit vector to the numeric values conditions
    compare against: floats/ints to their real value, categorical/ordinal
    dims to their choice INDEX (value-level comparisons are resolved to
    indices at compile time), constants to 0."""
    kind = jnp.asarray(codec.kind)
    lo = jnp.asarray(codec.lower, jnp.float32)
    hi = jnp.asarray(codec.upper, jnp.float32)
    log_lo = jnp.log(jnp.maximum(lo, 1e-30))
    log_hi = jnp.log(jnp.maximum(hi, 1e-30))
    v_lin = lo + q * (hi - lo)
    v_log = jnp.exp(log_lo + q * (log_hi - log_lo))
    v_float = jnp.where(jnp.asarray(codec.log), v_log, v_lin)

    ilo, ihi = _int_log_bounds(codec)
    ilo = jnp.asarray(ilo, jnp.float32)
    ihi = jnp.asarray(ihi, jnp.float32)
    n_int = jnp.maximum(hi - lo + 1.0, 1.0)
    vi_lin = lo - 0.5 + q * n_int
    vi_log = jnp.exp(
        jnp.log(jnp.maximum(ilo, 1e-30))
        + q * (jnp.log(jnp.maximum(ihi, 1e-30)) - jnp.log(jnp.maximum(ilo, 1e-30)))
    )
    v_int = jnp.clip(
        jnp.round(jnp.where(jnp.asarray(codec.log), vi_log, vi_lin)), lo, hi
    )

    out = jnp.where(kind == 0, v_float, q)
    out = jnp.where(kind == 1, v_int, out)
    out = jnp.where(kind == 2, jnp.round(q), out)
    out = jnp.where(kind == 3, 0.0, out)
    return out


def compile_active_mask(configspace, codec: SpaceCodec):
    """Compile the space's condition DAG to a jittable activity predicate.

    Returns ``mask_fn(q: f32[d]) -> bool[d]`` (vmap over batches) deciding,
    from a QUANTIZED unit vector, which dims are conditionally active —
    the device twin of ``ConfigurationSpace._active_set`` (a child is
    active iff every condition on it holds, and a condition on an inactive
    parent is false). Raises ``ValueError`` for condition forms without a
    numeric device representation (e.g. order comparisons on non-numeric
    ordinals) — callers fall back to the per-bracket path.
    """
    from hpbandster_tpu.space.conditions import (
        AndConjunction,
        EqualsCondition,
        GreaterThanCondition,
        InCondition,
        LessThanCondition,
        NotEqualsCondition,
        OrConjunction,
    )
    from hpbandster_tpu.space.hyperparameters import (
        CategoricalHyperparameter,
        Constant,
        OrdinalHyperparameter,
    )

    hps = configspace.get_hyperparameters()
    names = configspace.get_hyperparameter_names()
    index = {n: i for i, n in enumerate(names)}
    hp_by_name = dict(zip(names, hps))

    def cond_value_to_number(parent_name: str, value) -> float:
        """Resolve a condition's comparison value to the decoded-number
        domain of :func:`_decode_values` for that parent dim."""
        hp = hp_by_name[parent_name]
        if isinstance(hp, (CategoricalHyperparameter, OrdinalHyperparameter)):
            return float(hp.index(value))  # compare by choice index
        if isinstance(hp, Constant):
            return 0.0 if value == hp.value else float("nan")  # never equal
        return float(value)

    def ordinal_order_value(parent_name: str, value) -> float:
        """Greater/Less on an ordinal compares VALUES host-side; on device
        we compare indices, which is order-faithful only if the sequence is
        numerically sorted."""
        hp = hp_by_name[parent_name]
        seq = hp.sequence
        try:
            numeric = [float(v) for v in seq]
        except (TypeError, ValueError):
            raise ValueError(
                f"device conditions need a numeric ordinal sequence for "
                f"order comparisons on {parent_name!r}"
            )
        if numeric != sorted(numeric):
            raise ValueError(
                f"ordinal {parent_name!r} is not numerically sorted; order "
                f"comparisons have no index representation"
            )
        return float(hp.index(value))

    def compile_cond(c):
        if isinstance(c, AndConjunction):
            subs = [compile_cond(x) for x in c.components]
            return lambda dec, act: jnp.all(
                jnp.stack([f(dec, act) for f in subs])
            )
        if isinstance(c, OrConjunction):
            subs = [compile_cond(x) for x in c.components]
            return lambda dec, act: jnp.any(
                jnp.stack([f(dec, act) for f in subs])
            )
        j = index[c.parent_name]
        parent_hp = hp_by_name[c.parent_name]
        is_ord = isinstance(parent_hp, OrdinalHyperparameter)
        if isinstance(c, EqualsCondition):
            v = cond_value_to_number(c.parent_name, c.value)
            test = lambda x: x == v  # noqa: E731
        elif isinstance(c, NotEqualsCondition):
            v = cond_value_to_number(c.parent_name, c.value)
            test = lambda x: x != v  # noqa: E731
        elif isinstance(c, InCondition):
            vals = [cond_value_to_number(c.parent_name, v) for v in c.value]
            test = lambda x: jnp.any(  # noqa: E731
                jnp.stack([x == v for v in vals])
            )
        elif isinstance(c, (GreaterThanCondition, LessThanCondition)):
            # the decoded number for a categorical dim is its choice INDEX;
            # comparing float(c.value) against an index would silently build
            # a wrong activity mask (host compares raw values) — no device
            # representation, so reject and let callers fall back.
            if isinstance(parent_hp, CategoricalHyperparameter):
                raise ValueError(
                    f"order condition on categorical parent "
                    f"{c.parent_name!r} has no device representation"
                )
            v = (
                ordinal_order_value(c.parent_name, c.value)
                if is_ord else float(c.value)
            )
            if isinstance(c, GreaterThanCondition):
                test = lambda x, v=v: x > v  # noqa: E731
            else:
                test = lambda x, v=v: x < v  # noqa: E731
        else:
            raise ValueError(
                f"condition type {type(c).__name__} has no device compilation"
            )
        return lambda dec, act, j=j, test=test: act[j] & test(dec[j])

    # per-dim compiled condition list, evaluated in topological order so a
    # parent's activity is decided before any of its children
    topo = configspace._topological_order()
    per_dim = {
        index[name]: [
            compile_cond(c)
            for c in configspace.get_conditions()
            if c.child_name == name
        ]
        for name in topo
    }

    def mask_fn(q: jax.Array) -> jax.Array:
        dec = _decode_values(codec, q)
        act = jnp.ones(len(names), bool)
        for name in topo:
            j = index[name]
            for fn in per_dim[j]:
                act = act.at[j].set(act[j] & fn(dec, act))
        return act

    return mask_fn


def compile_forbidden_mask(configspace, codec: SpaceCodec):
    """Compile the space's forbidden clauses to a jittable predicate.

    Returns ``forbidden_fn(q: f32[d], act: bool[d]) -> bool[]`` — True when
    the QUANTIZED vector violates any forbidden clause — the device twin of
    ``ConfigurationSpace.is_forbidden``. A clause term on an inactive dim is
    False (host parity: ``is_forbidden`` only sees active values). Equality
    on a continuous dim uses a 1e-6 relative tolerance (the f32 decode
    cannot reproduce host float64 values exactly; host equality on a
    continuous draw is measure-zero anyway); discrete dims compare their
    choice indices exactly. Raises ``ValueError`` for clause types without
    a device compilation — callers fall back to the per-bracket path.
    """
    from hpbandster_tpu.space.forbidden import (
        ForbiddenAndConjunction,
        ForbiddenEqualsClause,
        ForbiddenInClause,
    )
    from hpbandster_tpu.space.hyperparameters import (
        CategoricalHyperparameter,
        Constant,
        OrdinalHyperparameter,
    )

    names = configspace.get_hyperparameter_names()
    index = {n: i for i, n in enumerate(names)}
    hp_by_name = dict(zip(names, configspace.get_hyperparameters()))

    def value_to_number(name: str, value) -> float:
        hp = hp_by_name[name]
        if isinstance(hp, (CategoricalHyperparameter, OrdinalHyperparameter)):
            return float(hp.index(value))
        if isinstance(hp, Constant):
            return 0.0 if value == hp.value else float("nan")  # never equal
        return float(value)

    def eq_term(name: str, value):
        if name not in index:
            raise ValueError(f"forbidden clause on unknown parameter {name!r}")
        j = index[name]
        v = value_to_number(name, value)
        if int(codec.kind[j]) == 0:  # continuous: f32-tolerant equality
            # tolerance must track the f32 DECODE error model per scale
            # kind: a linear decode (lo + u*(hi-lo)) has absolute error
            # ~ulps of max(|lo|,|hi|,range); a log decode (exp of a lerp in
            # log space) has error RELATIVE to the decoded value. A single
            # absolute tolerance would either let forbidden configs slip
            # through on wide linear ranges or over-forbid log dims near
            # small clause values. 1e-5 ≈ 80 f32 ulps of headroom.
            lo, hi = float(codec.lower[j]), float(codec.upper[j])
            if bool(codec.log[j]):
                tol = 1e-5 * max(abs(v), 1e-30)
            else:
                tol = 1e-5 * max(hi - lo, abs(lo), abs(hi))
            return lambda dec, act, j=j, v=v, tol=tol: act[j] & (
                jnp.abs(dec[j] - v) <= tol
            )
        return lambda dec, act, j=j, v=v: act[j] & (dec[j] == v)

    def compile_clause(c):
        if isinstance(c, ForbiddenAndConjunction):
            subs = [compile_clause(x) for x in c.components]
            return lambda dec, act: jnp.all(
                jnp.stack([f(dec, act) for f in subs])
            )
        if isinstance(c, ForbiddenEqualsClause):
            return eq_term(c.name, c.value)
        if isinstance(c, ForbiddenInClause):
            terms = [eq_term(c.name, v) for v in c.values]
            return lambda dec, act: jnp.any(
                jnp.stack([f(dec, act) for f in terms])
            )
        raise ValueError(
            f"forbidden clause type {type(c).__name__} has no device compilation"
        )

    clauses = [compile_clause(c) for c in configspace.get_forbiddens()]

    def forbidden_fn(q: jax.Array, act: jax.Array) -> jax.Array:
        if not clauses:
            return jnp.zeros((), bool)
        dec = _decode_values(codec, q)
        return jnp.any(jnp.stack([f(dec, act) for f in clauses]))

    return forbidden_fn


def _sweep_donation_safe() -> bool:
    """Whether the state-threading sweep may donate its warm buffers.

    Donation is on wherever the backend is an accelerator and off on CPU.
    The CPU gate dates from jax 0.4.37, where the CPU PJRT backend
    intermittently corrupted the heap when a donated dict-pytree aliased
    the returned state (3/6 suite runs died with donation on, 0/6 off).
    On the installed jax 0.9.0 the same hazard did not reproduce: 3/3
    runs of the chunked, resident, sharded, checkpoint and serving test
    files passed with ``HPB_SWEEP_DONATE=1`` — too few runs to retire a
    gate whose failure mode is a dead test process, so it stays. The
    state thread itself (keeping the buffers device-resident between
    chunks) is safe everywhere and carries the transfer win; donation
    only adds the in-place alias. ``HPB_SWEEP_DONATE=1``/``0`` forces
    either way.
    """
    import os

    env = os.environ.get("HPB_SWEEP_DONATE", "")
    if env in ("0", "1"):
        return env == "1"
    return jax.default_backend() != "cpu"


class SweepBracketOutput(NamedTuple):
    """Per-bracket device outputs of the fused sweep."""

    #: quantized stage-0 configuration vectors, f32[n0, d]
    vectors: jax.Array
    #: True where the proposal was model-based, bool[n0]
    model_based: jax.Array
    #: stage-major concatenation of original-row indices, i32[sum(ns)]
    idx_packed: jax.Array
    #: matching losses (NaN = crashed), f32[sum(ns)]
    loss_packed: jax.Array
    #: what an ``eval_fn`` with device counters (``ops.fused.LaneFacts``)
    #: counted beside each loss, f32[sum(ns), k]; ``None`` (no leaf, so the
    #: program's outputs are what they were) for every other evaluation
    lane_counters: Any = None


class SweepIncumbent(NamedTuple):
    """The ``incumbent_only=True`` sweep's entire device->host payload.

    At 100k-1M configs the per-stage records are the transfer bill (and
    the host bookkeeping bill); the 100k/1M tiers only need the winner.
    The incumbent is the best FINAL-stage (largest-budget) loss across
    every bracket — crashed (NaN) rows rank behind any real loss via the
    shared crash-rank constant, so an all-crashed sweep still returns a
    row (with a NaN loss) rather than garbage.
    """

    #: the winning configuration's quantized vector, f32[d]
    vector: jax.Array
    #: its final-stage loss (NaN = every candidate crashed), f32[]
    loss: jax.Array
    #: which bracket (index into ``plans``) produced it, i32[]
    bracket: jax.Array
    #: each bracket's best final-stage loss, f32[len(plans)]
    per_bracket_loss: jax.Array


class ResidentSweepOutputs(NamedTuple):
    """Full (non-incumbent) outputs of a ``resident=True`` sweep.

    ``stacked`` holds one :class:`SweepBracketOutput` per ROTATION
    position whose leaves carry a leading round axis (``lax.scan``'s
    stacking); ``tail`` holds the per-bracket outputs of the partial
    final round, unrolled. :func:`unstack_resident_outputs` flattens
    both into the per-bracket list the unrolled sweep returns.
    """

    stacked: Tuple[SweepBracketOutput, ...]
    tail: Tuple[SweepBracketOutput, ...]


class DeviceMetrics(NamedTuple):
    """The in-trace telemetry pytree — the sweep's metrics plane.

    Every leaf is sized by the SCHEDULE (brackets x rungs x bins), never
    by the config count, so carrying it through ``run_bracket`` and the
    resident ``lax.scan`` adds a constant to the final d2h payload
    whatever the sweep size — the resident flat-host-link contract
    (``tests/test_program_counts.py``
    ``test_resident_telemetry_rides_the_flat_link`` asserts it with
    telemetry ON). Rows
    beyond a bracket's actual rung count stay at their init value; the
    host decoder (``obs.device_metrics.decode_device_metrics``) walks
    the plan shapes and never reads them. Bin layout is owned by
    ``obs/device_metrics.py`` (``bin_edges()``): ONE schema for the
    in-trace accumulator and every host twin.
    """

    #: per-(bracket, rung) loss histogram over the log-spaced bins;
    #: NaN (crashed) losses are excluded (counted in ``crashes``)
    loss_hist: jax.Array   # i32[n_brackets, max_rungs, N_BINS]
    #: per-(bracket, rung) evaluation counts (the static stage widths,
    #: recorded so the decoded record is self-describing)
    evals: jax.Array       # i32[n_brackets, max_rungs]
    #: per-(bracket, rung) crashed (NaN-loss) evaluation counts
    crashes: jax.Array     # i32[n_brackets, max_rungs]
    #: per-(bracket, rung) promoted-config counts (rows advancing to the
    #: next rung; 0 at each bracket's final rung)
    promotions: jax.Array  # i32[n_brackets, max_rungs]
    #: per-bracket KDE-refit flag: 1 when the bracket's proposals came
    #: from a fit with an OPEN model gate (matches the host model's
    #: largest-trained-budget gate arithmetic)
    model_fits: jax.Array  # i32[n_brackets]
    #: per-bracket best FINAL-stage loss (NaN = every candidate crashed,
    #: same crash-rank ordering as the incumbent fold); the decoder
    #: derives the running incumbent / improvement deltas from it
    best_final: jax.Array  # f32[n_brackets]
    #: per-(bracket, rung) monotonically increasing sequence stamp: the
    #: rung's global position in the sweep's execution order (-1 = the
    #: rung never ran). The stamp is what lets the flight recorder
    #: (``obs/timeline.py``) lay resident-scan rungs out in true device
    #: order — the scan's stacked outputs lose it — and it rides the
    #: same O(schedule) payload, so the flat d2h bill is untouched
    rung_seq: jax.Array    # i32[n_brackets, max_rungs]


def init_device_metrics(n_brackets: int, max_rungs: int, n_bins: int) -> DeviceMetrics:
    """Zero-initialized metrics carry (``best_final`` inits to NaN — a
    bracket that has not run yet has no best; ``rung_seq`` inits to -1 —
    a rung that never ran has no position in the execution order)."""
    return DeviceMetrics(
        loss_hist=jnp.zeros((n_brackets, max_rungs, n_bins), jnp.int32),
        evals=jnp.zeros((n_brackets, max_rungs), jnp.int32),
        crashes=jnp.zeros((n_brackets, max_rungs), jnp.int32),
        promotions=jnp.zeros((n_brackets, max_rungs), jnp.int32),
        model_fits=jnp.zeros((n_brackets,), jnp.int32),
        best_final=jnp.full((n_brackets,), jnp.nan, jnp.float32),
        rung_seq=jnp.full((n_brackets, max_rungs), -1, jnp.int32),
    )


def init_lane_state(n_lanes: int) -> jax.Array:
    """Fresh per-lane incumbent carry for a continuous-batching program
    (``serve/continuous.py`` over ``ops.buckets.
    fused_sh_bracket_bucketed_packed_carry``): one RANK-SPACE f32 per
    lane, ``+inf`` = the lane has observed nothing yet.

    Rank space is the incumbent fold's ordering domain (the same
    convention as the resident sweep's incumbent carry): a real loss is
    itself, a crashed (NaN) evaluation is ``_CRASH_RANK`` (behind every
    real loss, ahead of emptiness), and ``+inf`` is untouched — so the
    in-trace fold is one ``minimum`` with no NaN special-casing, and the
    carry threads device-to-device across chunks exactly like the
    resident sweep's obs state. :func:`decode_lane_state` is the host
    twin that maps rank space back to loss-or-None.
    """
    return jnp.full((int(n_lanes),), jnp.inf, jnp.float32)


def decode_lane_state(carry) -> List[Optional[float]]:
    """Host decode of one rank-space lane carry: per lane, the running
    incumbent loss, ``float('nan')`` for a lane that has only ever
    crashed, or None for a lane that has observed nothing."""
    out: List[Optional[float]] = []
    for v in np.asarray(carry, np.float32):
        v = float(v)
        if v == float("inf"):
            out.append(None)
        elif v >= float(_CRASH_RANK):
            out.append(float("nan"))
        else:
            out.append(v)
    return out


#: public name for the donation gate (serve/continuous.py threads its
#: lane carry device-to-device and donates under the same CPU caveat)
sweep_donation_safe = _sweep_donation_safe


def resident_rotation(plans: Sequence[BracketPlan]) -> Tuple[int, int, int]:
    """``(period, n_rounds, n_tail)`` of a bracket schedule.

    The HyperBand rotation repeats its bracket shapes with a short
    period, so the resident sweep traces ONE round and ``lax.scan``-s it:
    program size O(period), not O(brackets). ``period`` is the smallest
    ``p`` with ``plans[i] == plans[i - p]`` for every ``i >= p`` (falls
    back to ``len(plans)`` for an aperiodic schedule — the scan then has
    a single round and the resident program degenerates to the unrolled
    one); ``n_tail = len(plans) - period * n_rounds`` brackets of the
    partial last round run unrolled after the scan.
    """
    plans = [BracketPlan(tuple(p.num_configs), tuple(p.budgets)) for p in plans]
    n = len(plans)
    if n == 0:
        raise ValueError("resident rotation needs at least one bracket")
    period = n
    for cand in range(1, n):
        if all(plans[i] == plans[i - cand] for i in range(cand, n)):
            period = cand
            break
    n_rounds = n // period
    return period, n_rounds, n - period * n_rounds


def unstack_resident_outputs(
    raw: ResidentSweepOutputs, n_rounds: int
) -> List[SweepBracketOutput]:
    """Flatten a (fetched) :class:`ResidentSweepOutputs` into the flat
    per-bracket output list the unrolled sweep returns, in bracket order
    (round-major over the rotation, then the tail)."""
    outs: List[SweepBracketOutput] = []
    for r in range(int(n_rounds)):
        for pos_out in raw.stacked:
            outs.append(jax.tree.map(lambda leaf: leaf[r], pos_out))
    outs.extend(SweepBracketOutput(*o) for o in raw.tail)
    return outs


#: device imputation moved to ops/kde.py (the in-trace refit op needs it
#: too); the old private name stays importable for existing callers
_impute_conditional_device = impute_conditional_masked


def _fit_kde_pair_device(
    vecs: jax.Array,
    losses: jax.Array,
    n_good: int,
    n_bad: int,
    cards: jax.Array,
    min_bandwidth: float,
    impute_key: Optional[jax.Array] = None,
) -> Tuple[KDE, KDE]:
    """Device twin of BOHBKDE._fit_kde_pair/_make_kde: stable sort by loss,
    top ``n_good`` / bottom ``n_bad`` rows, normal-reference bandwidths.
    Pass ``impute_key`` for conditional spaces — NaN (inactive) dims are
    then donor-imputed per split side, like the host model."""
    def mk(data: jax.Array) -> KDE:
        mask = jnp.ones(data.shape[0], jnp.float32)
        bw = normal_reference_bandwidths(data, mask, cards, min_bandwidth)
        return KDE(data, mask, bw)

    with jax.named_scope("hpb.kde_fit"):
        n = vecs.shape[0]
        order = jnp.argsort(losses, stable=True)
        good = vecs[order[:n_good]]
        bad = vecs[order[n - n_bad:]]
        if impute_key is not None:
            kg, kb = jax.random.split(impute_key)
            good = _impute_conditional_device(kg, good, cards)
            bad = _impute_conditional_device(kb, bad, cards)
        return mk(good), mk(bad)


#: the traced-count fit moved to ops/kde.py (fit_kde_pair_masked) so the
#: in-trace refit+propose op and this sweep share one definition; the old
#: private name stays importable (tests/test_kde_oracle.py uses it)
_fit_kde_pair_dynamic = fit_kde_pair_masked


def make_fused_sweep_fn(
    eval_fn: Callable[[jax.Array, float], jax.Array],
    plans: Sequence[BracketPlan],
    codec: SpaceCodec,
    *,
    num_samples: int = 64,
    random_fraction: float = 1 / 3,
    top_n_percent: int = 15,
    min_points_in_model: Optional[int] = None,
    bandwidth_factor: float = 3.0,
    min_bandwidth: float = 1e-3,
    mesh=None,
    axis: str = "config",
    warm_counts: Optional[dict] = None,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    rank_fn: Optional[Callable] = None,
    active_mask_fn: Optional[Callable] = None,
    forbidden_fn: Optional[Callable] = None,
    fallback_vector: Optional[np.ndarray] = None,
    max_forbidden_retries: int = 8,
    dynamic_counts: bool = False,
    capacities: Optional[dict] = None,
    return_state: bool = False,
    shard_sampling: bool = False,
    incumbent_only: bool = False,
    resident: bool = False,
    device_metrics: bool = False,
    stateful_eval=None,
    program_name: Optional[str] = None,
) -> Callable[..., List[SweepBracketOutput]]:
    """Trace + jit the whole sweep; returns ``fn(seed[, warm_v, warm_l])``.

    Model bookkeeping mirrors ``models/bohb_kde.py`` with all counts static:
    a budget's KDE pair exists once it holds ``min_points_in_model + 2``
    observations and both split sides exceed ``dim``; proposals use the
    largest such budget, refit at every bracket start from all observations
    accumulated so far (the batched path's stage-chunked model updates).

    ``warm_counts`` (budget -> n, static) enables warm starting: the jitted
    fn then takes two extra pytree args ``warm_v`` (budget -> f32[n, d]) and
    ``warm_l`` (budget -> f32[n]) whose leaves seed the observation buffers
    — traced inputs, so re-warming with fresh data of the same shape reuses
    the compiled program.

    ``forbidden_fn`` (from :func:`compile_forbidden_mask`) enables forbidden
    clauses on-device by rejection resampling INSIDE the trace: each
    bracket's proposals are checked, violating rows are redrawn uniformly up
    to ``max_forbidden_retries`` times, and any row still forbidden after
    that is replaced by ``fallback_vector`` (a host-verified valid
    configuration) — bounded work, static shapes, no host round-trip.

    ``dynamic_counts=True`` keeps observation COUNTS out of the compiled
    program: the jitted fn takes ``(seed, warm_v, warm_l, warm_n)`` where
    each ``warm_v[b]`` / ``warm_l[b]`` is a FULL-capacity buffer and
    ``warm_n[b]`` a traced i32 count. Model gating, good/bad split sizes
    and the largest-trained-budget selection all become traced arithmetic
    (:func:`_fit_kde_pair_dynamic`), so a chunked or warm-started sweep
    reuses ONE executable as observations accumulate instead of
    recompiling at every chunk boundary — the static path burns every
    count into the trace and a K-chunk run costs K compiles. Proposal math
    then runs over full capacity buffers (mask-weighted), a constant-factor
    cost the chunked tier accepts for compile reuse. ``capacities``
    (budget -> slots, must cover warm + every plan's additions) pins the
    buffer shapes so all chunks of one run agree on them.

    ``shard_sampling=True`` (requires ``mesh``) is the 100k-1M scale mode:
    stage-0 proposals are drawn per shard of the config axis
    (:func:`random_unit_sharded` — shard ``s`` folds its index into the
    bracket key, so every device generates its own rows locally and no
    candidate bytes ever cross the host link or the ICI before
    evaluation), and every bracket stage plus the dynamic observation
    buffers carry explicit sharding constraints over ``axis`` so the
    config batch stays distributed through the whole rung ladder — rung
    promotion masks lower to on-device reductions across shards, never a
    host gather. On a 1-device mesh this mode is BIT-IDENTICAL to the
    unsharded program (the parity bar in ``tests/test_sharded.py``);
    across mesh sizes it is a distinct RNG consumer (per-shard streams),
    like the dynamic tier.

    ``incumbent_only=True`` shrinks the device->host payload to a single
    :class:`SweepIncumbent` — the winning (vector, loss, bracket) plus
    per-bracket best losses — instead of per-stage records: at 1M configs
    the stage records ARE the transfer (and host-replay) bill, and only
    the final incumbent needs to leave the device loop. With
    ``return_state`` the fn returns ``(incumbent, state)``.

    ``resident=True`` is the whole-outer-loop fusion (ROADMAP "in-trace
    everything at 1M"): instead of unrolling every bracket into the
    trace (program size O(brackets); a chunked driver then surfaces to
    host per chunk), the HyperBand rotation's repeating round of bracket
    shapes is traced ONCE and driven by an in-trace ``lax.scan`` over
    rounds — bracket rotation, KDE refit (the traced-count
    ``fit_kde_pair_masked`` path), rung promotion, observation-state
    threading and the incumbent update all stay device-resident across
    the whole schedule. Requires ``dynamic_counts=True`` (observation
    counts evolve across scan iterations, so they must be traced). With
    ``incumbent_only=True`` the entire sweep's device->host traffic is
    one seed up and one :class:`SweepIncumbent` down, whatever the
    config count; without it the fn returns
    :class:`ResidentSweepOutputs` (scan-stacked per-rotation-position
    outputs + the unrolled tail) — flatten with
    :func:`unstack_resident_outputs`. Bracket ``b_i``'s RNG key is
    ``fold_in(key, b_i)`` with a TRACED ``b_i`` of the same value the
    unrolled trace folds concretely, so the resident and unrolled
    dynamic tiers are bit-identical on the same seed and capacities
    (the parity bar in ``tests/test_resident.py``).

    ``return_state=True`` (dynamic tier only) makes the jitted fn ALSO
    return the end-of-sweep observation state ``(obs_v, obs_l, counts)``
    — the same pytrees the warm inputs arrived as — so a chunked driver
    can thread the state device-to-device across chunk boundaries: the
    warm observation buffers stop round-tripping through the host (no
    h2d re-upload per chunk), the compile/transfer tax the runtime
    telemetry measured (ROADMAP). On accelerator backends the warm
    inputs are additionally DONATED to the returned state
    (``donate_argnums`` — XLA aliases each buffer to its updated twin in
    place); on CPU donation stays off (:func:`_sweep_donation_safe`). When
    donation is active the inputs are CONSUMED per call; pass fresh
    arrays (or the previous call's returned state) each time.

    ``device_metrics=True`` threads a fixed-shape :class:`DeviceMetrics`
    accumulator through every bracket (and the resident scan carry): per
    rung, log-binned loss histograms, crash/evaluation/promotion counts;
    per bracket, KDE-refit flags and best-final losses. Payload size is
    O(brackets x rungs x bins) — independent of the config count, so it
    rides the existing final d2h without perturbing the resident
    flat-link bill's shape. The jitted fn then ALSO returns the metrics
    pytree: ``(result, metrics)``, or with ``return_state``
    ``(result, metrics, state)``. Every path (unrolled static, dynamic
    chunked, sharded, resident) accumulates through the same
    ``run_bracket`` body, so the schema is identical — and parity
    testable — by construction; decode host-side with
    ``obs.device_metrics.decode_device_metrics``.

    ``stateful_eval`` (an :class:`~hpbandster_tpu.ops.fused.StatefulEval`,
    exclusive with ``eval_fn``) switches every bracket's rung ladder to
    the warm-continuation protocol: each bracket's ensemble of live
    training states is built in-trace (``init_fn``), rung promotions
    gather the surviving weight/optimizer pytrees by the same top-k
    indices the rung ranked by, and each stage trains only its
    INCREMENTAL budget — see ``fused_sh_bracket`` and
    ``workloads/ensemble.py``. The ensemble state is bracket-local device
    scratch: it never enters the scan carry or the d2h payload, so the
    resident flat-host-link bill is untouched however large the models
    are. All sweep modes (static, dynamic, sharded, resident) compose
    with it unchanged.

    ``program_name`` overrides the base name the compiled program is
    tracked under (``obs.runtime`` ledger; default ``"fused_sweep"``) —
    the resident/spmd suffixes still apply. Distinct workloads get
    distinct ledger rows, which is what lets a caller find ITS
    program's cost analysis in ``obs.profile.roofline_report``.
    """
    from hpbandster_tpu.parallel.mesh import is_multiprocess_mesh, shard_count

    d = int(codec.kind.shape[0])
    if (eval_fn is None) == (stateful_eval is None):
        raise ValueError(
            "provide exactly one evaluation seam: eval_fn (stateless) or "
            "stateful_eval (StatefulEval warm continuation)"
        )
    if forbidden_fn is not None and fallback_vector is None:
        raise ValueError("forbidden_fn requires a fallback_vector")
    if return_state and not dynamic_counts:
        raise ValueError(
            "return_state=True requires dynamic_counts=True: the static "
            "tier burns counts into the trace, there is no reusable state"
        )
    if shard_sampling and mesh is None:
        raise ValueError("shard_sampling=True requires a mesh")
    if incumbent_only and not plans:
        raise ValueError("incumbent_only=True needs at least one bracket")
    if resident and not dynamic_counts:
        raise ValueError(
            "resident=True requires dynamic_counts=True: the scan carries "
            "observation counts across rounds, so they must be traced"
        )
    if resident and not plans:
        raise ValueError("resident=True needs at least one bracket")
    n_shards = shard_count(mesh, axis) if shard_sampling else 1
    if n_shards > 1:
        for p in plans:
            if int(p.num_configs[0]) % n_shards:
                raise ValueError(
                    f"shard_sampling: stage-0 count {p.num_configs[0]} is "
                    f"not a multiple of the {n_shards}-way '{axis}' axis — "
                    "build plans with ops.bracket.mesh_aligned_plan (or pad "
                    "via parallel.mesh.pad_to_shards)"
                )
    #: pin the dynamic observation state's boundary shardings over the
    #: config axis on single-process meshes: chunked drivers thread the
    #: returned state straight back into the (AOT-compiled) next call, so
    #: input and output shardings must be stable by CONSTRUCTION, not by
    #: XLA's whim. Multi-process meshes keep the replicated contract below.
    pin_state_shards = (
        dynamic_counts and mesh is not None and not is_multiprocess_mesh(mesh)
    )
    min_pts = (d + 1) if min_points_in_model is None else max(int(min_points_in_model), d + 1)
    plans = [BracketPlan(tuple(p.num_configs), tuple(p.budgets)) for p in plans]
    warm_counts = {float(b): int(n) for b, n in (warm_counts or {}).items() if n > 0}

    # static per-budget observation capacities across the whole sweep
    additions = plan_additions(plans)
    caps: dict = {float(b): int(n) for b, n in warm_counts.items()}
    for b, k in additions.items():
        caps[b] = caps.get(b, 0) + k
    if capacities is not None:
        for b, need in caps.items():
            if capacities.get(float(b), 0) < need:
                raise ValueError(
                    f"capacities[{b}]={capacities.get(float(b))} cannot hold "
                    f"the {need} observations this sweep accumulates there"
                )
        caps = {float(b): int(n) for b, n in capacities.items()}

    vartypes_dev = jnp.asarray(codec.vartypes)
    cards_dev = jnp.asarray(codec.cards)

    # metrics-plane constants: the bin schema is owned by the obs layer
    # (ONE definition for the in-trace accumulator and the host decoder)
    if device_metrics:
        from hpbandster_tpu.obs.device_metrics import N_BINS, bin_edges

        dm_edges = bin_edges().astype(np.float32)
        dm_rungs = max(len(p.num_configs) for p in plans) if plans else 0
        dm_bins = N_BINS
        # per-bracket base of the global rung sequence stamp: cumulative
        # rung counts over the STATIC schedule, indexed at (possibly
        # traced) b_i inside run_bracket — the resident scan's bracket
        # index is a scalar i32, and gathering from a static table is
        # how the stamp stays monotonic across rounds without carrying
        # an extra counter through the scan
        dm_seq_base = jnp.asarray(
            np.cumsum([0] + [len(p.num_configs) for p in plans])[:-1],
            jnp.int32,
        )

    def trained_split(n: int) -> Optional[Tuple[int, int]]:
        """Host-side static twin of the _fit_kde_pair gate."""
        # run_bracket reaches this only on the static tier
        # (dynamic_counts=False), where counts[b] are Python ints burned
        # into the trace; the traced-counts tier routes to dynamic_gate,
        # the i32 twin of this gate. The tier split is a closure constant
        # a path-insensitive analysis cannot correlate.
        # graftlint: disable=trace-escape — static-tier-only host gate (see above)
        if n < min_pts + 2:
            return None
        n_good = max(min_pts, (top_n_percent * n) // 100)
        n_bad = max(min_pts, ((100 - top_n_percent) * n) // 100)
        if n_good <= d or n_bad <= d:
            return None
        return n_good, n_bad

    def _propose_model_vecs(good: KDE, bad: KDE, k_prop: jax.Array, n0: int):
        if use_pallas:
            from hpbandster_tpu.ops.pallas_kde import pallas_propose_batch

            return pallas_propose_batch(
                k_prop, good, bad, vartypes_dev, cards_dev, n0,
                num_samples, bandwidth_factor, min_bandwidth,
                pallas_interpret, mesh=mesh, axis=axis,
            )
        with jax.named_scope("hpb.sample"):
            keys = jax.random.split(k_prop, n0)
        return jax.vmap(
            lambda k: propose(
                k, good, bad, vartypes_dev, cards_dev,
                num_samples, bandwidth_factor, min_bandwidth,
            )[0]
        )(keys)

    # dynamic-count machinery: the gate arithmetic is the i32-traced twin of
    # trained_split (same integer formulas, so the model opens at exactly
    # the same observation counts as the static path and the host model)
    capmax = max(caps.values(), default=0)
    any_trainable = any(trained_split(c) is not None for c in caps.values())

    def dynamic_gate(cnt: jax.Array):
        n_good = jnp.maximum(min_pts, (top_n_percent * cnt) // 100)
        n_bad = jnp.maximum(min_pts, ((100 - top_n_percent) * cnt) // 100)
        has = (cnt >= min_pts + 2) & (n_good > d) & (n_bad > d)
        return has, n_good, n_bad

    def dynamic_proposals(
        obs_v, obs_l, counts, rand_vecs, k_prop, k_frac, k_fit, n0
    ):
        """Largest-trained-budget selection + fit + proposal, all traced.

        Budget priority is a static descending unroll; the selected
        budget's buffer is widened to ``capmax`` so one fit serves
        whichever budget wins. When no budget's gate is open the fit runs
        on empty buffers (harmless, NaN-free) and ``mb_mask`` discards
        every model pick — matching the static path's all-random bracket.
        """
        with jax.named_scope("hpb.kde_fit"):
            sel_v = jnp.zeros((capmax, d), jnp.float32)
            sel_l = jnp.full((capmax,), jnp.inf, jnp.float32)
            sel_n = jnp.zeros((), jnp.int32)
            any_model = jnp.zeros((), bool)
            for b in sorted(caps, reverse=True):
                has, _, _ = dynamic_gate(counts[b])
                take = has & ~any_model
                pad = capmax - caps[b]
                pv = jnp.pad(obs_v[b], ((0, pad), (0, 0)))
                pl = jnp.pad(obs_l[b], (0, pad), constant_values=jnp.inf)
                sel_v = jnp.where(take, pv, sel_v)
                sel_l = jnp.where(take, pl, sel_l)
                sel_n = jnp.where(take, counts[b], sel_n)
                any_model = any_model | has
            _, n_good, n_bad = dynamic_gate(sel_n)
        good, bad = _fit_kde_pair_dynamic(
            sel_v, sel_l, sel_n, n_good, n_bad, cards_dev, min_bandwidth,
            impute_key=k_fit if active_mask_fn is not None else None,
        )
        model_vecs = _propose_model_vecs(good, bad, k_prop, n0)
        with jax.named_scope("hpb.sample"):
            mb_mask = any_model & (
                jax.random.uniform(k_frac, (n0,)) >= random_fraction
            )
            proposals = jnp.where(mb_mask[:, None], model_vecs, rand_vecs)
        # any_model rides along for the metrics plane: it is the traced
        # twin of "a KDE refit ran with an open gate this bracket"
        return proposals, mb_mask, any_model

    if resident:
        rotation, n_rounds, _tail_count = resident_rotation(plans)
        round_plans = plans[:rotation]
        tail_plans = plans[rotation * n_rounds:]

    def init_obs_state(warm_v, warm_l, warm_n):
        """Seed the per-budget observation buffers: full-capacity with
        traced counts on the dynamic tier, exact-count slices burned into
        the trace on the static tier."""
        if dynamic_counts:
            # full-capacity buffers in, traced counts; pad slots pinned to
            # (0-vector, +inf loss) regardless of what the caller sent.
            # Each budget's additions over the whole schedule are static,
            # so clamping the traced warm count to (capacity - additions)
            # keeps every later append inside the buffer — an oversized
            # caller count truncates its newest warm rows deterministically
            # instead of silently clobbering fresh observations through
            # dynamic_update_slice's start-index clamping.
            obs_v, obs_l, counts = {}, {}, {}
            for b, cap in caps.items():
                # a budget present in `capacities` but absent from the
                # warm inputs (exported-API callers may oversize the
                # capacity map for a later chunk) defaults to an empty
                # count-0 buffer instead of a trace-time KeyError
                # (ADVICE r4); a budget present in only SOME of the three
                # warm dicts is a caller bug — name it instead of letting
                # warm_v[b] raise bare or silently dropping the data
                have = warm_n is not None and b in warm_n
                have_v = warm_v is not None and b in warm_v
                have_l = warm_l is not None and b in warm_l
                if not (have == have_v == have_l):
                    raise ValueError(
                        f"inconsistent warm inputs for budget {b}: present "
                        f"in warm_n={have}, warm_v={have_v}, "
                        f"warm_l={have_l} — each budget must appear in all "
                        f"three dicts or none"
                    )
                n_b = jnp.minimum(
                    jnp.asarray(warm_n[b] if have else 0, jnp.int32),
                    cap - additions.get(b, 0),
                )
                live = jnp.arange(cap, dtype=jnp.int32) < n_b
                v = (jnp.asarray(warm_v[b], jnp.float32) if have
                     else jnp.zeros((cap, d), jnp.float32))
                l = (jnp.asarray(warm_l[b], jnp.float32) if have
                     else jnp.full((cap,), jnp.inf, jnp.float32))
                obs_v[b] = jnp.where(live[:, None], v, 0.0)
                obs_l[b] = jnp.where(
                    live & ~jnp.isnan(l), l, jnp.inf
                )
                counts[b] = n_b
            if pin_state_shards:
                obs_v = {b: shard_rows(v, mesh, axis)
                         for b, v in obs_v.items()}
                obs_l = {b: shard_rows(l, mesh, axis)
                         for b, l in obs_l.items()}
        else:
            obs_v = {
                b: jnp.zeros((cap, d), jnp.float32) for b, cap in caps.items()
            }
            obs_l = {b: jnp.zeros(cap, jnp.float32) for b, cap in caps.items()}
            counts = {b: 0 for b in caps}  # python ints: static
            for b, n in warm_counts.items():
                obs_v[b] = obs_v[b].at[:n].set(warm_v[b].astype(jnp.float32))
                obs_l[b] = obs_l[b].at[:n].set(
                    jnp.where(jnp.isnan(warm_l[b]), jnp.inf, warm_l[b]).astype(
                        jnp.float32
                    )
                )
                counts[b] = n
        return obs_v, obs_l, counts

    def init_incumbent():
        """(best_key, best_loss, best_vec, best_bracket, per_bracket) —
        the cross-bracket incumbent fold's carry. ``per_bracket`` is a
        fixed f32[len(plans)] written at the bracket's index (the array
        form both the unrolled loop and the resident scan can update)."""
        return (
            jnp.asarray(jnp.inf, jnp.float32),
            jnp.asarray(jnp.nan, jnp.float32),
            jnp.zeros((d,), jnp.float32),
            jnp.asarray(-1, jnp.int32),
            jnp.zeros((len(plans),), jnp.float32),
        )

    def run_bracket(b_i, plan, key, obs_v, obs_l, counts, inc, metrics):
        """One bracket: sample/propose -> forbidden resampling -> fused
        rung ladder -> observation append -> incumbent fold -> metrics
        accumulation.

        ``b_i`` may be a Python int (the unrolled trace) or a traced i32
        (the resident scan's round arithmetic): ``fold_in`` is
        value-deterministic, so both derive identical draws for the same
        bracket index — the resident/unrolled bit-parity contract.
        Functional: returns updated ``(obs_v, obs_l, counts, inc,
        metrics, out)`` without mutating the caller's dicts (the scan
        carry requires it); ``out`` is the bracket's
        :class:`SweepBracketOutput` or ``None`` under ``incumbent_only``;
        ``metrics`` is the :class:`DeviceMetrics` carry (``None`` when
        the metrics plane is off — nothing extra is traced then). All
        metrics writes index row ``b_i``, which works for both the
        unrolled (concrete) and scanned (traced) index.
        """
        obs_v, obs_l, counts = dict(obs_v), dict(obs_l), dict(counts)
        n0 = plan.num_configs[0]
        # the scopes below (obs.timeline.DEVICE_SCOPES) are flat, never
        # nested: an instruction's op_name holds at most one of them
        with jax.named_scope("hpb.sample"):
            k_rand, k_prop, k_frac, k_fit = jax.random.split(
                jax.random.fold_in(key, b_i), 4
            )
            # per-shard derivation under shard_sampling: each shard's rows
            # come from its own folded key, so generation stays local to
            # the owning device (n_shards == 1 falls through to the
            # unfolded base key — the 1-device-mesh bit-parity contract)
            rand_vecs = random_unit_sharded(codec, k_rand, n0, n_shards)
            if n_shards > 1:
                rand_vecs = shard_rows(rand_vecs, mesh, axis)

        #: metrics-plane KDE gate flag for this bracket: traced under the
        #: dynamic tier (the gate is count-arithmetic), concrete 0/1 on
        #: the static tier — both are the same host-model gate
        fit_flag = jnp.zeros((), jnp.int32)
        if dynamic_counts:
            if not any_trainable:
                # no budget's gate can open even at full capacity
                # (FusedHyperBand/RandomSearch) — skip tracing the
                # model math entirely
                proposals = rand_vecs
                mb_mask = jnp.zeros(n0, bool)
            else:
                proposals, mb_mask, any_model = dynamic_proposals(
                    obs_v, obs_l, counts, rand_vecs, k_prop, k_frac,
                    k_fit, n0,
                )
                fit_flag = any_model.astype(jnp.int32)
        else:
            model_budget = None
            for b in sorted(caps, reverse=True):
                if trained_split(counts[b]) is not None:
                    model_budget = b
                    break

            if model_budget is None:
                proposals = rand_vecs
                mb_mask = jnp.zeros(n0, bool)
            else:
                fit_flag = jnp.ones((), jnp.int32)
                n = counts[model_budget]
                n_good, n_bad = trained_split(n)
                good, bad = _fit_kde_pair_device(
                    obs_v[model_budget][:n], obs_l[model_budget][:n],
                    n_good, n_bad, cards_dev, min_bandwidth,
                    impute_key=k_fit if active_mask_fn is not None else None,
                )
                model_vecs = _propose_model_vecs(good, bad, k_prop, n0)
                with jax.named_scope("hpb.sample"):
                    mb_mask = (
                        jax.random.uniform(k_frac, (n0,)) >= random_fraction
                    )
                    proposals = jnp.where(
                        mb_mask[:, None], model_vecs, rand_vecs
                    )

        with jax.named_scope("hpb.sample"):
            mb_mask, eval_vectors, out_vectors = finish_sample(
                proposals, mb_mask, k_rand, n0
            )

        lane_counters: List[jax.Array] = []
        stages = fused_sh_bracket(
            eval_fn, eval_vectors, plan.num_configs, plan.budgets,
            rank_fn=rank_fn, lane_counters=lane_counters,
            # per-stage sharding constraints: the rung ladder's
            # survivor batches stay distributed over the config axis
            # (promotion masks reduce across shards on-device)
            mesh=mesh if shard_sampling else None, axis=axis,
            # warm-continuation seam: the bracket's live training states
            # stay device-internal (bracket-local scratch, never carried)
            stateful=stateful_eval,
        )

        with jax.named_scope("hpb.obs_update"):
            for (idx_s, losses_s), k_s, budget in zip(
                stages, plan.num_configs, plan.budgets
            ):
                b = float(budget)
                c = counts[b]
                upd_l = jnp.where(jnp.isnan(losses_s), jnp.inf, losses_s)
                if dynamic_counts:
                    obs_v[b] = jax.lax.dynamic_update_slice_in_dim(
                        obs_v[b], out_vectors[idx_s], c, 0
                    )
                    obs_l[b] = jax.lax.dynamic_update_slice_in_dim(
                        obs_l[b], upd_l, c, 0
                    )
                else:
                    obs_v[b] = obs_v[b].at[c:c + k_s].set(out_vectors[idx_s])
                    obs_l[b] = obs_l[b].at[c:c + k_s].set(upd_l)
                counts[b] = c + k_s
            if metrics is not None:
                metrics = fold_metrics(metrics, b_i, plan, stages, fit_flag)

        out = None
        if incumbent_only:
            with jax.named_scope("hpb.incumbent"):
                inc = fold_incumbent(inc, b_i, stages, out_vectors)
        else:
            with jax.named_scope("hpb.obs_update"):
                idx_packed, loss_packed = _pack_stages(stages)
                out = SweepBracketOutput(
                    out_vectors[:n0], mb_mask, idx_packed, loss_packed,
                    # stage 0 counted its padding rows too
                    jnp.concatenate([c[:k] for c, k in zip(
                        lane_counters, plan.num_configs)])
                    if lane_counters else None,
                )
        return obs_v, obs_l, counts, inc, metrics, out

    def finish_sample(proposals, mb_mask, k_rand, n0):
        """Quantisation, forbidden resampling and the activity mask: from
        raw proposals to ``(mb_mask, eval_vectors, out_vectors)``."""
        vectors = quantize_unit(codec, proposals)

        if forbidden_fn is not None:
            # in-trace rejection resampling (bounded, static shapes):
            # redraw forbidden rows uniformly; anything still forbidden
            # after the retry budget clamps to the known-valid fallback
            def batch_act(vecs):
                if active_mask_fn is not None:
                    return jax.vmap(active_mask_fn)(vecs)
                return jnp.ones(vecs.shape, bool)

            k_forb = jax.random.fold_in(k_rand, 0x7FB)
            resampled = jnp.zeros(n0, bool)
            for t in range(max_forbidden_retries):
                forbidden_rows = jax.vmap(forbidden_fn)(
                    vectors, batch_act(vectors)
                )
                resampled = resampled | forbidden_rows
                fresh = quantize_unit(
                    codec,
                    random_unit(codec, jax.random.fold_in(k_forb, t), n0),
                )
                vectors = jnp.where(
                    forbidden_rows[:, None], fresh, vectors
                )
            forbidden_rows = jax.vmap(forbidden_fn)(
                vectors, batch_act(vectors)
            )
            fb = quantize_unit(
                codec, jnp.asarray(fallback_vector, jnp.float32)
            )
            vectors = jnp.where(
                forbidden_rows[:, None], fb[None, :], vectors
            )
            # a redrawn/clamped row is uniform (or the fallback), not a
            # model pick — don't let it masquerade as model-based in
            # config_info / analysis
            mb_mask = mb_mask & ~resampled

        if active_mask_fn is not None:
            # conditional space: evaluation sees 0 in inactive dims
            # (host parity: to_vector -> NaN -> nan_to_num(0)), while
            # observations and outputs carry NaN so the host decoder
            # and the KDE imputation see the true activity pattern
            active = jax.vmap(active_mask_fn)(vectors)
            eval_vectors = jnp.where(active, vectors, 0.0)
            out_vectors = jnp.where(active, vectors, jnp.nan)
        else:
            eval_vectors = out_vectors = vectors
        # shard_rows, NOT a raw with_sharding_constraint: constraining a
        # batch that does not divide the config axis miscompiles under
        # XLA CPU SPMD on multi-axis meshes (stage indices come back
        # scaled by the other axis' size — the __graft_entry__ dryrun's
        # (config, model) mesh with a 9-row bracket), and shard_rows is
        # the one place that divisibility policy lives
        eval_vectors = shard_rows(eval_vectors, mesh, axis)
        return mb_mask, eval_vectors, out_vectors

    def fold_metrics(metrics, b_i, plan, stages, fit_flag):
        """One bracket's rungs into the :class:`DeviceMetrics` carry."""
        # metrics plane: per-rung histograms / crash counts plus the
        # per-bracket refit flag and best final loss, all written at
        # row b_i (concrete OR traced — the resident/unrolled parity
        # contract extends to telemetry). O(n) binning per stage is
        # trivial next to the stage evaluation it accompanies; the
        # carried arrays are O(schedule), never O(configs).
        m_hist, m_ev, m_cr, m_pr, m_sq = (
            metrics.loss_hist, metrics.evals, metrics.crashes,
            metrics.promotions, metrics.rung_seq,
        )
        depth = len(plan.num_configs)
        for s, ((_idx_s, losses_s), k_s) in enumerate(
            zip(stages, plan.num_configs)
        ):
            h_s, c_s = stage_telemetry(losses_s, dm_edges)
            m_hist = m_hist.at[b_i, s].set(h_s)
            m_ev = m_ev.at[b_i, s].set(k_s)
            m_cr = m_cr.at[b_i, s].set(c_s)
            m_pr = m_pr.at[b_i, s].set(
                plan.num_configs[s + 1] if s + 1 < depth else 0
            )
            # global execution-order stamp: static per-bracket base
            # (gathered at the concrete-or-traced b_i) + the stage
            # offset — monotonically increasing over the whole
            # schedule, resident rounds included
            m_sq = m_sq.at[b_i, s].set(dm_seq_base[b_i] + s)
        _, loss_fin = stages[-1]
        key_fin = jnp.where(jnp.isnan(loss_fin), _CRASH_RANK, loss_fin)
        return DeviceMetrics(
            loss_hist=m_hist, evals=m_ev, crashes=m_cr,
            promotions=m_pr,
            model_fits=metrics.model_fits.at[b_i].set(fit_flag),
            best_final=metrics.best_final.at[b_i].set(
                loss_fin[jnp.argmin(key_fin)]
            ),
            rung_seq=m_sq,
        )

    def fold_incumbent(inc, b_i, stages, out_vectors):
        """Only the winner leaves the device loop: reduce the final
        (largest-budget) stage to its best row and fold it into the
        running cross-bracket incumbent — crashed (NaN) rows rank behind
        every real loss via the shared crash rank."""
        best_key, best_loss, best_vec, best_bracket, per_bracket = inc
        idx_f, loss_f = stages[-1]
        key_f = jnp.where(jnp.isnan(loss_f), _CRASH_RANK, loss_f)
        a = jnp.argmin(key_f)
        cand_key = key_f[a]
        take = cand_key < best_key
        best_key = jnp.where(take, cand_key, best_key)
        best_loss = jnp.where(take, loss_f[a], best_loss)
        best_vec = jnp.where(take, out_vectors[idx_f[a]], best_vec)
        best_bracket = jnp.where(
            take, jnp.asarray(b_i, jnp.int32), best_bracket
        )
        per_bracket = per_bracket.at[b_i].set(loss_f[a])
        return best_key, best_loss, best_vec, best_bracket, per_bracket

    # the function's name is the compiled module's (``jit_hpb_sweep``): what
    # a profiler trace's ``XLA Modules`` events carry, and what a reader
    # joins the program's phase map by, so it is the program's own and not
    # a name any other jitted ``sweep`` of the process would share
    def hpb_sweep(
        seed: jax.Array, warm_v=None, warm_l=None, warm_n=None
    ) -> List[SweepBracketOutput]:
        key = jax.random.key(seed)
        with jax.named_scope("hpb.obs_update"):
            obs_v, obs_l, counts = init_obs_state(warm_v, warm_l, warm_n)
        inc = init_incumbent() if incumbent_only else None
        # the metrics carry rides the same functional thread as the
        # incumbent (None = metrics plane off: a registered-empty pytree
        # node, legal in the scan carry exactly like the inc slot)
        metrics = (
            init_device_metrics(len(plans), dm_rungs, dm_bins)
            if device_metrics else None
        )
        outputs: List[SweepBracketOutput] = []
        if resident:
            # the resident outer loop: ONE traced round of the bracket
            # rotation, scanned over rounds — bracket rotation, KDE
            # refit, promotion and the incumbent update never surface to
            # host between brackets, and program size is O(rotation)
            # instead of O(brackets)
            def round_body(carry, r):
                obs_v, obs_l, counts, inc, metrics = carry
                outs = []
                for pos, plan in enumerate(round_plans):
                    obs_v, obs_l, counts, inc, metrics, out = run_bracket(
                        r * rotation + pos, plan, key,
                        obs_v, obs_l, counts, inc, metrics,
                    )
                    if not incumbent_only:
                        outs.append(out)
                if pin_state_shards:
                    # the scan carry is an AOT-stable boundary like the
                    # return_state one: in/out shardings must agree by
                    # construction, not by XLA's whim
                    obs_v = {b: shard_rows(v, mesh, axis)
                             for b, v in obs_v.items()}
                    obs_l = {b: shard_rows(l, mesh, axis)
                             for b, l in obs_l.items()}
                return (obs_v, obs_l, counts, inc, metrics), tuple(outs)

            (obs_v, obs_l, counts, inc, metrics), stacked = jax.lax.scan(
                round_body, (obs_v, obs_l, counts, inc, metrics),
                jnp.arange(n_rounds, dtype=jnp.int32),
            )
            tail_outs: List[SweepBracketOutput] = []
            for j, plan in enumerate(tail_plans):
                obs_v, obs_l, counts, inc, metrics, out = run_bracket(
                    n_rounds * rotation + j, plan, key,
                    obs_v, obs_l, counts, inc, metrics,
                )
                if not incumbent_only:
                    tail_outs.append(out)
            result = (
                SweepIncumbent(inc[2], inc[1], inc[3], inc[4])
                if incumbent_only
                else ResidentSweepOutputs(stacked, tuple(tail_outs))
            )
        else:
            for b_i, plan in enumerate(plans):
                obs_v, obs_l, counts, inc, metrics, out = run_bracket(
                    b_i, plan, key, obs_v, obs_l, counts, inc, metrics
                )
                if not incumbent_only:
                    outputs.append(out)
            result = (
                SweepIncumbent(inc[2], inc[1], inc[3], inc[4])
                if incumbent_only else outputs
            )
        if return_state:
            # the donated warm inputs alias these outputs buffer-for-buffer
            # (same pytree structure, shapes, dtypes) — the in-place state
            # thread chunked drivers hand back to the next call. Boundary
            # shardings re-pinned so the threaded state re-enters the AOT
            # executable with exactly the sharding it was lowered for.
            if pin_state_shards:
                obs_v = {b: shard_rows(v, mesh, axis)
                         for b, v in obs_v.items()}
                obs_l = {b: shard_rows(l, mesh, axis)
                         for b, l in obs_l.items()}
            if device_metrics:
                return result, metrics, (obs_v, obs_l, counts)
            return result, (obs_v, obs_l, counts)
        if device_metrics:
            return result, metrics
        return result

    from hpbandster_tpu.parallel.mesh import is_multiprocess_mesh

    # buffer-donation contract (docs/perf_notes.md): the warm observation
    # buffers are donated exactly when the call returns the updated state
    # they can alias (the dynamic chunked thread) AND the backend handles
    # aliasing safely. Elsewhere the outputs never match the input shapes,
    # so donation would be a no-op warning — declined explicitly.
    donate = (
        (1, 2, 3)
        if (dynamic_counts and return_state and _sweep_donation_safe())
        else ()
    )

    base_name = program_name or "fused_sweep"
    if is_multiprocess_mesh(mesh):
        # DCN tier (VERDICT r3 #6): the mesh spans several jax.distributed
        # processes. Every rank's SPMD driver replays the SAME sweep, so
        # inputs (seed + warm observations, identical on all ranks) and
        # outputs (the stage records every rank's bookkeeping consumes) pin
        # to fully-REPLICATED shardings — a rank could not device_get a
        # shard homed on another process. Evaluation still shards over the
        # 'config' axis via the with_sharding_constraint above; XLA inserts
        # the all-gathers (outputs are tiny: indices + losses + vectors).
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
        return tracked_jit(
            hpb_sweep,
            name=base_name + ("_resident_spmd" if resident else "_spmd"),
            in_shardings=rep, out_shardings=rep, donate_argnums=donate,
        )
    return tracked_jit(
        hpb_sweep,
        name=base_name + ("_resident" if resident else ""),
        donate_argnums=donate,
    )
