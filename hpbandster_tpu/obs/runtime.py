"""XLA runtime telemetry: compilation tracking + device sampling.

PRs 2–4 made the *job* tier observable; this module does the same for the
*runtime* tier — the XLA substrate whose silent failure modes (a
recompile storm in the fused sweep, a device quietly filling its memory)
erase exactly the wall-clock wins the fused paths exist to deliver.

Three instruments, all stdlib-only at import (jax loads lazily inside
the functions that need it, same rule as the rest of ``obs``):

* :func:`tracked_jit` — a thin ``jax.jit`` wrapper adopted by the repo's
  jit sites (``ops/fused.py``, ``ops/sweep.py``, ``ops/kde.py``,
  ``ops/bracket.py``, ``parallel/backends.py``). Every call whose
  abstract shape signature (shapes + dtypes + static values) has not
  been seen by that wrapper times the dispatch and journals one
  ``xla_compile`` event: function name, signature, compile seconds, and
  the per-function recompile counter. The measured seconds are the
  first-call wall time (trace + compile + first execution — compile
  dominates for anything XLA spends real time on); steady-state calls
  pay one signature hash + set lookup.
* :class:`DeviceSampler` — a periodic daemon thread publishing
  per-device gauges: ``memory_stats()`` bytes in use / limit where the
  backend reports them (TPU/GPU; CPU reports nothing), plus live-buffer
  counts and bytes from ``jax.live_arrays()``.
* :func:`note_transfer` — host<->device transfer counters incremented at
  the repo's own transfer choke points (``ops/fused.py`` dispatch and
  unpack, ``parallel/backends.py`` evaluate, the batched executor's
  wave assembly): buffer counts and byte totals per direction.

Everything lands in the shared :mod:`~hpbandster_tpu.obs.metrics`
registry (so the Prometheus exporter in :mod:`~hpbandster_tpu.obs.export`
scrapes it for free) and on the event bus (so the journal, the
``recompile_storm`` anomaly rule, and the summarize/report CLIs see it).

The wrapper itself never emits from inside a traced region: when a
tracked function is being traced INTO an enclosing computation (e.g.
``ops.kde.propose`` vmapped inside the fused sweep), its arguments are
tracers; the wrapper sees them and passes straight through — the outer
tracked boundary owns that compile.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

from hpbandster_tpu.obs import events as E
from hpbandster_tpu.obs.metrics import MetricsRegistry, get_metrics

__all__ = [
    "CompileTracker",
    "DeviceSampler",
    "compile_stats_from_records",
    "get_compile_tracker",
    "note_transfer",
    "publish_sweep_transfers",
    "runtime_snapshot",
    "start_device_sampler",
    "tracked_jit",
    "transfer_counters",
]

logger = logging.getLogger("hpbandster_tpu.obs")


# ------------------------------------------------------------ compile tracking
class CompileTracker:
    """Per-function compile ledger shared by every :func:`tracked_jit`.

    Aggregation is by function *label* (not wrapper instance) on purpose:
    a loop that keeps constructing fresh jitted closures of the same
    function — the exact storm the ``recompile_storm`` rule and the
    ``jit-in-loop`` lint exist for — shows up as one label compiling over
    and over, which is the true cost XLA pays.
    """

    #: per-ledger bound on retained costed programs — a shape-churning
    #: pathology must not grow the roofline table without limit (the
    #: storm is the recompile counters' job to surface)
    MAX_COSTED_PROGRAMS = 256

    def __init__(self) -> None:
        self._lock = threading.Lock()
        #: label -> {"compiles": int, "compile_s": float,
        #:           "last_signature": str, "last_compile_s": float}
        self._fns: Dict[str, Dict[str, Any]] = {}
        #: (label, signature) -> {"compiles", "compile_s", "flops",
        #: "bytes_accessed"} for programs whose compile reported a
        #: cost_analysis (the AOT path) — what roofline_report walks
        self._programs: "collections.OrderedDict" = collections.OrderedDict()

    def record(
        self,
        label: str,
        signature: str,
        seconds: float,
        registry: Optional[MetricsRegistry] = None,
        bus: Optional[E.EventBus] = None,
        cost: Optional[Dict[str, float]] = None,
    ) -> int:
        """Count one fresh compilation of ``label``; returns the
        function's cumulative compile count. Updates the shared metrics
        (``runtime.compiles``, per-fn counters, ``runtime.compile_seconds``)
        and emits one ``xla_compile`` event. ``cost`` is the compiled
        program's XLA cost analysis (``{"flops", "bytes_accessed"}``
        where reported): retained per (label, signature) for the
        roofline report and republished as ``runtime.flops.<fn>`` /
        ``runtime.bytes_accessed.<fn>`` counters."""
        with self._lock:
            slot = self._fns.get(label)
            if slot is None:
                slot = self._fns[label] = {"compiles": 0, "compile_s": 0.0}
            slot["compiles"] += 1
            slot["compile_s"] += float(seconds)
            slot["last_signature"] = signature
            slot["last_compile_s"] = float(seconds)
            n = slot["compiles"]
            if cost:
                key = (label, signature)
                prog = self._programs.pop(key, None)
                if prog is None:
                    prog = {"compiles": 0, "compile_s": 0.0}
                prog["compiles"] += 1
                prog["compile_s"] = round(prog["compile_s"] + float(seconds), 6)
                prog.update({k: float(v) for k, v in cost.items()})
                self._programs[key] = prog  # re-insert: LRU-newest
                while len(self._programs) > self.MAX_COSTED_PROGRAMS:
                    self._programs.popitem(last=False)
        reg = registry if registry is not None else get_metrics()
        reg.counter("runtime.compiles").inc()
        reg.counter(f"runtime.compiles.{label}").inc()
        reg.gauge("runtime.compile_seconds").inc(float(seconds))
        extra: Dict[str, Any] = {}
        if cost:
            for field in ("flops", "bytes_accessed"):
                v = cost.get(field)
                if v is not None:
                    reg.counter(f"runtime.{field}.{label}").inc(int(v))
                    extra[field] = float(v)
        target = bus if bus is not None else E.get_bus()
        target.emit(
            E.XLA_COMPILE,
            fn=label,
            signature=signature,
            compile_s=round(float(seconds), 6),
            compiles=n,
            recompiles=n - 1,
            **extra,
        )
        return n

    def program_costs(self) -> List[Dict[str, Any]]:
        """Every costed program in the ledger, insertion order: the
        roofline report's input rows."""
        with self._lock:
            return [
                {"fn": label, "signature": signature, **dict(prog)}
                for (label, signature), prog in self._programs.items()
            ]

    def snapshot(self) -> Dict[str, Any]:
        """JSON-serializable ledger: totals + per-function breakdown."""
        with self._lock:
            functions = {
                label: {
                    "compiles": slot["compiles"],
                    "compile_s": round(slot["compile_s"], 6),
                    "recompiles": slot["compiles"] - 1,
                    "last_signature": slot.get("last_signature"),
                }
                for label, slot in sorted(self._fns.items())
            }
        return {
            "total_compiles": sum(f["compiles"] for f in functions.values()),
            "total_compile_s": round(
                sum(f["compile_s"] for f in functions.values()), 6
            ),
            "functions": functions,
        }

    def reset(self) -> None:
        """Drop the ledger (test isolation)."""
        with self._lock:
            self._fns.clear()
            self._programs.clear()


_TRACKER = CompileTracker()


def get_compile_tracker() -> CompileTracker:
    """The process-wide compile ledger every :func:`tracked_jit` feeds."""
    return _TRACKER


def _leaf_key(leaf: Any) -> Any:
    """Hashable identity of one TRACED argument leaf: abstract
    (shape, dtype) for anything array-like, the python type (not value)
    for bare scalars — jax traces those as weak-typed values whose value
    never keys the dispatch cache — and the value for anything else."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    if isinstance(leaf, (bool, int, float, complex)):
        return ("weak", type(leaf).__name__)
    try:
        hash(leaf)
        return leaf
    except TypeError:
        return repr(leaf)


def _value_key(leaf: Any) -> Any:
    """Hashable identity of one STATIC argument: by value (jax bakes
    static values into the compiled program)."""
    try:
        hash(leaf)
        return leaf
    except TypeError:
        return repr(leaf)


#: (jax.tree_util.tree_flatten, jax.core.Tracer), bound once on first use —
#: the wrapper sits on the hot dispatch path, so per-call `import jax` +
#: attribute chains are real money (measured ~14µs of a ~18µs signature)
_JAX_BINDINGS: Optional[Tuple[Callable, type]] = None


def _flatten(x: Any):
    """``(leaves, treedef, traced)``: ``traced`` is True when any leaf is a
    ``jax.core.Tracer`` — the call is being traced INTO an enclosing
    computation and must pass through untracked."""
    global _JAX_BINDINGS
    if _JAX_BINDINGS is None:
        import jax

        _JAX_BINDINGS = (jax.tree_util.tree_flatten, jax.core.Tracer)
    tree_flatten, tracer_type = _JAX_BINDINGS
    leaves, treedef = tree_flatten(x)
    return leaves, treedef, any(isinstance(l, tracer_type) for l in leaves)


def _abstract_signature(
    args: Tuple,
    kwargs: Dict,
    static_nums: frozenset = frozenset(),
    static_names: frozenset = frozenset(),
) -> Optional[Tuple]:
    """Hashable abstract signature of a call, in the same terms jax's own
    dispatch cache keys on: tree structure + per-leaf shape/dtype for
    traced leaves (python scalars by type only — weak-typed), static args
    by value. Weak-type-vs-strong-type distinctions inside arrays are
    deliberately ignored — a documented trade for a wrapper cheap enough
    to sit on the hot dispatch path. ``None`` when an argument leaf is a
    tracer: the call belongs to an enclosing trace, not to a dispatch."""
    if not static_nums and not static_names:
        leaves, treedef, traced = _flatten((args, kwargs))
        if traced:
            return None
        return (treedef, tuple(map(_leaf_key, leaves)), (), ())
    t_args = tuple(a for i, a in enumerate(args) if i not in static_nums)
    s_args = tuple(
        (i, _value_key(a)) for i, a in enumerate(args) if i in static_nums
    )
    t_kwargs = {k: v for k, v in kwargs.items() if k not in static_names}
    s_kwargs = tuple(sorted(
        (k, _value_key(v)) for k, v in kwargs.items() if k in static_names
    ))
    leaves, treedef, traced = _flatten((t_args, t_kwargs))
    if traced:
        return None
    return (treedef, tuple(map(_leaf_key, leaves)), s_args, s_kwargs)


def _format_signature(sig: Tuple) -> str:
    """Human/journal form of :func:`_abstract_signature`:
    ``f32[8,2], f32[8], n=64``-style, truncated to a sane length."""
    parts: List[str] = []
    for key in sig[1]:
        if (
            isinstance(key, tuple)
            and len(key) == 2
            and isinstance(key[0], tuple)
            and isinstance(key[1], str)
        ):
            shape, dtype = key
            parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
        elif isinstance(key, tuple) and len(key) == 2 and key[0] == "weak":
            parts.append(f"weak_{key[1]}")
        else:
            parts.append(repr(key))
    for i, v in sig[2] if len(sig) > 2 else ():
        parts.append(f"static{i}={v!r}")
    for k, v in sig[3] if len(sig) > 3 else ():
        parts.append(f"{k}={v!r}")
    out = ", ".join(parts)
    return out if len(out) <= 200 else out[:197] + "..."


def tracked_jit(
    fn: Optional[Callable] = None,
    *,
    name: Optional[str] = None,
    tracker: Optional[CompileTracker] = None,
    registry: Optional[MetricsRegistry] = None,
    bus: Optional[E.EventBus] = None,
    **jit_kwargs: Any,
) -> Callable:
    """``jax.jit`` with compile telemetry: a drop-in wrapper that journals
    one ``xla_compile`` event per fresh abstract-shape signature.

    Usable bare (``tracked_jit(fn)``), with jit kwargs
    (``tracked_jit(fn, static_argnames="n")``), or as a decorator factory
    (``@partial(tracked_jit, static_argnames="n")``). ``name`` overrides
    the journal label (default: the function's ``__name__``).

    Signature tracking is per wrapper (each wrapper owns its own jit
    cache) while compile counts aggregate per label in the process-wide
    :class:`CompileTracker`. Calls whose arguments hold a
    ``jax.core.Tracer`` (an enclosing trace is live) pass straight through
    untracked — the wrapper must never emit from inside a traced region
    (the ``obs-emit-in-jit`` contract).
    """
    if fn is None:
        return partial(
            tracked_jit, name=name, tracker=tracker, registry=registry,
            bus=bus, **jit_kwargs,
        )
    import inspect

    import jax

    jitted = jax.jit(fn, **jit_kwargs)
    label = name or getattr(fn, "__name__", None) or "<anonymous>"
    trk = tracker if tracker is not None else _TRACKER
    seen: set = set()

    # mirror jax's static/traced split so the signature keys statics by
    # VALUE and traced leaves abstractly (static_argnames resolve to
    # positions too — jax accepts them positionally)
    names = jit_kwargs.get("static_argnames") or ()
    names = (names,) if isinstance(names, str) else tuple(names)
    nums = jit_kwargs.get("static_argnums")
    nums = (nums,) if isinstance(nums, int) else tuple(nums or ())
    static_nums = set(nums)
    try:
        params = list(inspect.signature(fn).parameters)
        for nm in names:
            if nm in params:
                static_nums.add(params.index(nm))
    except (TypeError, ValueError):
        pass  # builtins/exotic callables: keyword statics still resolve
    static_nums = frozenset(static_nums)
    static_names = frozenset(names)

    def wrapper(*args: Any, **kwargs: Any):
        if not E._ENABLED:
            return jitted(*args, **kwargs)
        try:
            sig = _abstract_signature(args, kwargs, static_nums, static_names)
        except Exception:
            # an exotic pytree must degrade to an untracked call, never
            # block the dispatch it was only supposed to observe
            logger.exception("tracked_jit signature for %r failed", label)
            return jitted(*args, **kwargs)
        if sig is None:
            # a tracer among the arguments: being traced into an enclosing
            # computation, whose tracked boundary owns the compile
            return jitted(*args, **kwargs)
        reg = registry if registry is not None else get_metrics()
        reg.counter("runtime.tracked_calls").inc()
        if sig in seen:
            return jitted(*args, **kwargs)
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        seconds = time.perf_counter() - t0
        seen.add(sig)
        trk.record(
            label, _format_signature(sig), seconds,
            registry=reg, bus=bus,
        )
        return out

    def lower(*args: Any, **kwargs: Any):
        """AOT path (``fn.lower(...).compile()``): the returned proxy
        times ``compile()`` and feeds the same ledger, so ahead-of-time
        compiles (FusedBOHB's executable cache) journal like JIT ones."""
        lowered = jitted.lower(*args, **kwargs)
        try:
            sig_str = _format_signature(_abstract_signature(args, kwargs))
        # best-effort label: an exotic pytree only costs the signature
        # string, never the lowering it annotates
        except Exception:  # graftlint: disable=swallowed-exception — signature is cosmetic here; the compile proceeds either way
            sig_str = "<unhashable>"
        return _TrackedLowered(
            lowered, label, sig_str, trk,
            registry if registry is not None else None, bus,
        )

    wrapper.__name__ = getattr(fn, "__name__", "tracked_jit")
    wrapper.__doc__ = getattr(fn, "__doc__", None)
    wrapper.__wrapped__ = fn
    #: the underlying jitted callable (AOT lowering, cache introspection)
    wrapper.jitted = jitted
    wrapper.label = label
    wrapper.lower = lower
    return wrapper


class _TrackedLowered:
    """Proxy over ``jax.stages.Lowered`` that records ``compile()`` time
    into the compile ledger; every other attribute forwards verbatim."""

    def __init__(self, lowered, label, signature, tracker, registry, bus):
        self._lowered = lowered
        self._label = label
        self._signature = signature
        self._tracker = tracker
        self._registry = registry
        self._bus = bus

    def compile(self, *args: Any, **kwargs: Any):
        if not E._ENABLED:
            return self._lowered.compile(*args, **kwargs)
        t0 = time.perf_counter()
        exe = self._lowered.compile(*args, **kwargs)
        self._tracker.record(
            self._label, self._signature, time.perf_counter() - t0,
            registry=self._registry, bus=self._bus,
            cost=_extract_cost(exe),
        )
        return exe

    def __getattr__(self, name: str) -> Any:
        return getattr(self._lowered, name)


def _extract_cost(compiled: Any) -> Optional[Dict[str, float]]:
    """The compiled program's XLA cost analysis, normalized to the ledger
    schema (``flops`` / ``bytes_accessed``). Best-effort: a backend
    without cost analysis returns None and the compile is still tracked
    — the roofline table just has no row for it."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # graftlint: disable=swallowed-exception — backends without cost analysis are expected; absence of a roofline row is the answer, the compile is still ledgered
        return None
    if not isinstance(ca, dict):
        return None
    out: Dict[str, float] = {}
    for src, dst in (("flops", "flops"), ("bytes accessed", "bytes_accessed")):
        v = ca.get(src)
        if isinstance(v, (int, float)) and not isinstance(v, bool) and v == v and v >= 0:
            out[dst] = float(v)
    return out or None


# ---------------------------------------------------------- transfer counters
def note_transfer(
    direction: str,
    nbytes: int,
    buffers: int = 1,
    registry: Optional[MetricsRegistry] = None,
) -> None:
    """Count one host<->device transfer at a repo choke point.

    ``direction`` is ``"h2d"`` or ``"d2h"``. jax exposes no portable
    transfer counters, so the repo counts its OWN transfer sites — the
    fused dispatch/unpack pair and the batched backend's upload/fetch —
    which is exactly the set whose round-trips dominate on high-latency
    links (see ops/fused.py's packing rationale).
    """
    if direction not in ("h2d", "d2h"):
        raise ValueError(f"direction must be 'h2d' or 'd2h', not {direction!r}")
    reg = registry if registry is not None else get_metrics()
    reg.counter(f"runtime.transfers_{direction}").inc(int(buffers))
    reg.counter(f"runtime.transfer_bytes_{direction}").inc(max(int(nbytes), 0))


#: the four process-lifetime host-link counters :func:`note_transfer`
#: advances — the ONE name list shared by the per-sweep snapshot/diff
#: below and anything else that wants to read the link bill
_TRANSFER_COUNTER_KEYS = (
    "transfers_h2d", "transfers_d2h",
    "transfer_bytes_h2d", "transfer_bytes_d2h",
)


def transfer_counters(
    registry: Optional[MetricsRegistry] = None,
) -> Dict[str, int]:
    """Current values of the host-link transfer counters (0 where never
    advanced) — snapshot one before a sweep, diff with
    :func:`publish_sweep_transfers` after."""
    reg = registry if registry is not None else get_metrics()
    counters = reg.snapshot().get("counters") or {}
    return {
        k: int(counters.get(f"runtime.{k}", 0) or 0)
        for k in _TRANSFER_COUNTER_KEYS
    }


def publish_sweep_transfers(
    before: Dict[str, int],
    registry: Optional[MetricsRegistry] = None,
) -> Dict[str, int]:
    """Per-sweep device<->host byte accounting: diff the transfer
    counters against a :func:`transfer_counters` snapshot taken at sweep
    start, publish the result as gauges, and return the deltas.

    Gauges (exported to Prometheus as ``sweep_transfer_bytes{direction=}``
    and ``hpbandster_sweep_host_syncs`` via ``obs/export.py``):

    * ``sweep.transfer_bytes.h2d`` / ``sweep.transfer_bytes.d2h`` — bytes
      the host link carried for the LAST sweep. The resident sweep's
      flatness claim lives here: in incumbent-only mode d2h must not
      scale with config count (one vector + one scalar per sweep);
    * ``sweep.host_syncs`` — transferred-BUFFER count (both directions,
      the unit every ``note_transfer`` site counts in: a fetch of one
      4-leaf payload counts 4): the sweep's host-surface bill, which
      ``tests/test_program_counts.py`` pins constant in config count
      (``test_resident_link_is_flat_in_the_configuration_count``).

    Counts only the repo's own :func:`note_transfer` choke points — the
    set whose round-trips dominate on high-latency links.
    """
    reg = registry if registry is not None else get_metrics()
    now = transfer_counters(reg)
    delta = {k: now[k] - int(before.get(k, 0)) for k in now}
    reg.gauge("sweep.transfer_bytes.h2d").set(
        float(delta["transfer_bytes_h2d"])
    )
    reg.gauge("sweep.transfer_bytes.d2h").set(
        float(delta["transfer_bytes_d2h"])
    )
    reg.gauge("sweep.host_syncs").set(
        float(delta["transfers_h2d"] + delta["transfers_d2h"])
    )
    return delta


# ------------------------------------------------------------- device sampler
class DeviceSampler:
    """Periodic per-device memory / live-buffer census -> gauges.

    ``sample()`` runs one census (tests call it directly); ``start()``
    spawns a daemon thread sampling every ``interval_s`` until ``stop()``.
    Gauges published per device index ``i``:

    * ``runtime.device.<i>.bytes_in_use`` / ``.bytes_limit`` — from
      ``Device.memory_stats()`` where the backend provides it;
    * ``runtime.device.<i>.live_buffers`` / ``.live_bytes`` — from
      ``jax.live_arrays()``, a sharded array contributing one buffer and
      its per-shard byte share to each device it lives on;

    plus ``runtime.device_count``. Sampling initializes the jax backend
    on first use, so only start a sampler in processes that run device
    work anyway (the health endpoint reads the LAST census, it never
    samples on demand).
    """

    def __init__(
        self,
        interval_s: float = 10.0,
        registry: Optional[MetricsRegistry] = None,
    ):
        self.interval_s = max(float(interval_s), 0.05)
        self._registry = registry
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------- sampling
    def sample(self) -> Dict[str, Any]:
        """One census; returns (and retains) the JSON-serializable result."""
        import jax

        reg = self._registry if self._registry is not None else get_metrics()
        devices = jax.devices()
        per_dev: Dict[int, Dict[str, Any]] = {
            int(d.id): {"kind": str(d.device_kind), "platform": str(d.platform)}
            for d in devices
        }
        live_buffers: Dict[int, int] = {i: 0 for i in per_dev}
        live_bytes: Dict[int, int] = {i: 0 for i in per_dev}
        try:
            for arr in jax.live_arrays():
                devs = list(getattr(arr, "devices", lambda: [])())
                if not devs:
                    continue
                share = int(getattr(arr, "nbytes", 0)) // len(devs)
                for d in devs:
                    i = int(d.id)
                    if i in live_buffers:
                        live_buffers[i] += 1
                        live_bytes[i] += share
        except Exception:
            # live_arrays is best-effort introspection; a backend that
            # cannot enumerate must not kill the sampler thread
            logger.exception("device sampler live_arrays census failed")
        for d in devices:
            i = int(d.id)
            slot = per_dev[i]
            slot["live_buffers"] = live_buffers[i]
            slot["live_bytes"] = live_bytes[i]
            reg.gauge(f"runtime.device.{i}.live_buffers").set(live_buffers[i])
            reg.gauge(f"runtime.device.{i}.live_bytes").set(live_bytes[i])
            try:
                stats = d.memory_stats()
            # best-effort: CPU and older backends raise (or return None)
            # here — absent memory stats are the answer, not an error
            except Exception:  # graftlint: disable=swallowed-exception — backend without memory introspection; absence is the answer
                stats = None
            if stats:
                in_use = stats.get("bytes_in_use")
                limit = stats.get("bytes_limit")
                if isinstance(in_use, (int, float)):
                    slot["bytes_in_use"] = int(in_use)
                    reg.gauge(f"runtime.device.{i}.bytes_in_use").set(in_use)
                if isinstance(limit, (int, float)):
                    slot["bytes_limit"] = int(limit)
                    reg.gauge(f"runtime.device.{i}.bytes_limit").set(limit)
        reg.gauge("runtime.device_count").set(len(devices))
        census = {
            "t_wall": time.time(),
            "device_count": len(devices),
            "devices": {str(i): per_dev[i] for i in sorted(per_dev)},
        }
        with self._lock:
            self._last = census
        return census

    def last_sample(self) -> Optional[Dict[str, Any]]:
        """The newest census, or None before the first sample."""
        with self._lock:
            return dict(self._last) if self._last is not None else None

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "DeviceSampler":
        """Spawn the daemon sampling thread (idempotent)."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="obs-device-sampler"
            )
            self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.sample()
            except Exception:
                # telemetry must never kill its host process's thread pool
                logger.exception("device sampler pass failed")
            if self._stop.wait(self.interval_s):
                return

    def stop(self) -> None:
        """Stop the sampling thread (idempotent; safe if never started)."""
        self._stop.set()
        with self._lock:
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=5.0)


#: the sampler started via start_device_sampler, for runtime_snapshot()
_SAMPLER: Optional[DeviceSampler] = None
_SAMPLER_LOCK = threading.Lock()


def start_device_sampler(
    interval_s: float = 10.0,
    registry: Optional[MetricsRegistry] = None,
) -> DeviceSampler:
    """Start (or return) the process-wide device sampler. The returned
    sampler's ``stop()`` halts it; ``obs.configure(device_sampler=...)``
    wires this into the standard sink lifecycle."""
    global _SAMPLER
    with _SAMPLER_LOCK:
        if _SAMPLER is None:
            _SAMPLER = DeviceSampler(interval_s=interval_s, registry=registry)
            _SAMPLER.start()
        return _SAMPLER


def _clear_device_sampler(sampler: DeviceSampler) -> None:
    """Forget the process-wide sampler if it is ``sampler`` (close path)."""
    global _SAMPLER
    with _SAMPLER_LOCK:
        if _SAMPLER is sampler:
            _SAMPLER = None


def compile_stats_from_records(
    records: List[Dict[str, Any]],
    window_s: float,
    top_k: int = 5,
) -> Dict[str, Any]:
    """Offline aggregation of ``xla_compile`` journal records — the ONE
    definition behind both the summarize CLI's "xla runtime" block and
    the report CLI's runtime section (they must agree or the two views
    of the same journal drift): per-fn compile counts/seconds, the
    compile-time share of the journal's wall-clock window, and the
    ``top_k`` recompilers. Deterministic: content-only, stable sort."""
    import math

    per_fn: Dict[str, Dict[str, float]] = {}
    for rec in records:
        if rec.get("event") != E.XLA_COMPILE:
            continue
        fn = str(rec.get("fn") or "?")
        slot = per_fn.setdefault(fn, {"compiles": 0, "compile_s": 0.0})
        slot["compiles"] += 1
        cs = rec.get("compile_s")
        if (
            isinstance(cs, (int, float))
            and not isinstance(cs, bool)
            and math.isfinite(cs)
        ):
            slot["compile_s"] += float(cs)
    total = sum(s["compiles"] for s in per_fn.values())
    total_s = sum(s["compile_s"] for s in per_fn.values())
    return {
        "compiles": int(total),
        "compile_s": round(total_s, 6),
        # compile-time share of the journal's wall-clock window: the
        # number that says whether XLA ate the sweep (a recompile storm
        # pushes this toward 1 even when every job "succeeded")
        "compile_share_of_wall": (
            round(min(total_s / window_s, 1.0), 4)
            if window_s > 0 and total else None
        ),
        "top_recompilers": [
            {
                "fn": fn,
                "compiles": int(slot["compiles"]),
                "compile_s": round(slot["compile_s"], 6),
                "recompiles": int(slot["compiles"]) - 1,
            }
            for fn, slot in sorted(
                per_fn.items(),
                key=lambda kv: (-kv[1]["compiles"], -kv[1]["compile_s"], kv[0]),
            )[:top_k]
        ],
    }


def runtime_snapshot() -> Dict[str, Any]:
    """The ``runtime`` section of ``obs_snapshot`` (health.py): the
    compile ledger plus the newest device census (None until a
    :class:`DeviceSampler` has run — this never touches jax itself, so a
    health RPC cannot initialize a backend as a side effect)."""
    with _SAMPLER_LOCK:
        sampler = _SAMPLER
    return {
        "compile": _TRACKER.snapshot(),
        "devices": sampler.last_sample() if sampler is not None else None,
    }
