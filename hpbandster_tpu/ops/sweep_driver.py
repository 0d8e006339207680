"""The host driver of the fused sweep program (``ops/sweep.py``).

``FusedBOHB.run``, ``FusedBOHB.run_incumbent`` and
``parallel.multihost.run_sharded_fused_sweep`` run the same device program;
:class:`SweepDriver` is the one implementation of "run these brackets'
plans as one device chunk" under all three. It owns what lies between the
plans and the outputs: buffer capacities, argument staging, the executable
key, the process-wide executable cache and the ahead-of-time compile,
dispatch and fetch, the split of ``(outputs, metrics, state)``, and the
transfer and telemetry accounting. A caller keeps what is its own: its
plans, its seeds, its replay or its fold, its result.

It sits beside the program, below both callers: ``optimizers/`` imports
``parallel/`` and never the other way, so neither of those could hold it.
Internal to the three entry points; nothing here is part of the package's
interface.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from hpbandster_tpu import obs
from hpbandster_tpu.ops.sweep import (
    make_fused_sweep_fn,
    plan_additions,
    pow2_capacities,
)
from hpbandster_tpu.utils.lru import LRUCache

__all__: List[str] = []

#: process-wide compiled-sweep cache (same policy as the fused-bracket and
#: batch caches: one compile per (objective, schedule, space, knobs, mesh)).
#: Values are AOT-compiled executables — cache hits skip retracing AND
#: recompiling on repeated runs of the same schedule, whichever entry point
#: asks (a repeated sharded sweep must not recompile: the compile counts
#: of ``tests/test_program_counts.py`` are per PROCESS, not per call).
_SWEEP_EXE_CACHE: LRUCache = LRUCache(maxsize=16)

#: objectives that have passed ``FusedBOHB.__init__``'s admission check,
#: keyed on the three things the check reads: ``(evaluation object, space
#: dimension, lowest budget)``. The check is a whole Python trace of the
#: objective, and its verdict on one object cannot change from one
#: construction to the next. Bounded as the executable cache, which holds
#: the same objects strongly in its keys.
_ADMITTED: LRUCache = LRUCache(maxsize=_SWEEP_EXE_CACHE.maxsize)

#: program options the key cannot hash; the caller's ``space_sig`` stands
#: for them
_KEYED_BY_SPACE_SIG = ("active_mask_fn", "forbidden_fn", "fallback_vector")


def _note_device_refits(decoded: Dict[str, Any]) -> None:
    """Surface device-side TPE fits to the event plane: a fused sweep
    fits its models in-trace, so the host-side ``kde_refit`` emit in
    models/bohb_kde.py never fires and the model-freshness consumers
    (the kde_refit_stall anomaly rule, the kde_refit_staleness SLO in
    obs/slo.py) would read a healthy fused run as permanently stale.
    One event per telemetry fold that recorded any fits."""
    fits = decoded.get("model_fits")
    if (
        isinstance(fits, (int, float)) and fits > 0
        and obs.get_bus().active
    ):
        obs.emit(obs.KDE_REFIT, source="device", fits=int(fits))


def check_once(key: Tuple[Any, int, float], check: Callable[[], None]) -> bool:
    """Run ``check()`` unless ``key`` has passed it before; True where it
    ran. Only a check that returned is remembered: one that raised raises
    again at the next call. A key that cannot be hashed is checked every
    time."""
    try:
        if _ADMITTED.get(key):
            return False
    except TypeError:
        check()
        return True
    check()
    _ADMITTED[key] = True
    return True


def stream_warm_buffers(warm_v, warm_l, caps, d, mesh, axis,
                        replicate_indivisible=False):
    """Warm observation buffers for a single-process MESH run, built
    per shard slice through ``jax.make_array_from_callback``.

    The plain path allocates each budget's full-capacity buffer on
    host before upload — at the 1M-config scale that is the one place
    a driver materializes O(total configs) host memory in a single
    piece. Here the callback only ever holds ONE shard's slice
    (capacity / shard count rows), so peak host RSS is bounded by a
    slice regardless of sweep size. Shardings match the sweep's in-trace
    state pins (``ops/sweep.py`` ``pin_state_shards``): the AOT
    executable sees identical input shardings whether the state
    arrives streamed (chunk 0 / after a capacity doubling) or as the
    previous chunk's threaded device state. A sweep with no
    observations yet is the ``n = 0`` case: every slice is the fill.

    A capacity the axis does not divide is replicated under
    ``replicate_indivisible`` and refused otherwise. Returns
    ``((warm_v, warm_l, warm_n), host_bytes)``: the bytes the host link
    actually carries, so the transfer ledger measures the warm upload
    instead of asserting it.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from hpbandster_tpu.parallel.mesh import batch_sharding, shard_count

    n_shards = shard_count(mesh, axis)
    shard = batch_sharding(mesh, axis)
    out_v, out_l, out_n = {}, {}, {}
    host_bytes = 0
    for b, cap in caps.items():
        sharding = shard
        if cap % n_shards:
            if not replicate_indivisible:
                # a differently-sharded streamed input would violate the
                # AOT sharding-stability contract above — fail loudly
                # rather than silently falling back to replication
                raise ValueError(
                    f"streamed warm upload needs capacities divisible by "
                    f"the {n_shards}-way '{axis}' axis, got {cap} for "
                    f"budget {b}"
                )
            sharding = NamedSharding(mesh, PartitionSpec())
        src_v, src_l = warm_v.get(b), warm_l.get(b)
        n = 0 if src_v is None else len(src_v)

        def streamed(shape, fill_value, src, n=n, sharding=sharding):
            # the callback runs once a shard and holds that slice alone
            def fill(idx):
                start, stop, _ = idx[0].indices(shape[0])
                buf = np.full((stop - start,) + shape[1:], fill_value,
                              np.float32)
                if src is not None and start < n:
                    take = src[start:min(stop, n)]
                    buf[: len(take)] = take
                return buf

            return jax.make_array_from_callback(shape, sharding, fill)

        out_v[b] = streamed((cap, d), 0.0, src_v)
        out_l[b] = streamed((cap,), np.inf, src_l)
        out_n[b] = np.int32(n)
        host_bytes += cap * d * 4 + cap * 4 + 4
    return (out_v, out_l, out_n), host_bytes


class SweepDriver:
    """One call's chunks of one sweep program, run one after another.

    ``eval_fn``, ``codec`` and ``options`` are ``make_fused_sweep_fn``'s
    objective, space codec and the keyword options that hold for the whole
    call (``stateful_eval``, ``mesh``, the model's knobs, ...); ``space_sig``
    is what the executable key holds in place of the options it cannot hash
    (the condition and forbidden clauses a caller compiled). The mode —
    ``dynamic`` counts, ``resident``, ``incumbent_only``, ``device_metrics``
    (``None``: ``HPB_DEVICE_METRICS``, resolved here once) — is the
    program's too; ``thread_state`` says the program returns the updated
    observation state, and the next chunk takes it device to device.

    ``warm_v`` / ``warm_l`` are the caller's host observations by budget,
    read afresh at every chunk (``FusedBOHB`` folds each chunk's into
    them). ``cold`` is the form a dynamic chunk's observation buffers take
    when no device state can be threaded, which each entry point brings
    with it (the forms disagree, and which wins is a chip measurement, not
    a refactor):

    * ``"host"``: capacity buffers padded on the host;
    * ``"stream_all"``: streamed per shard slice when the mesh divides
      EVERY capacity — exactly the cases where the sweep pins the state's
      boundary shardings over the config axis (``ops/sweep.py``
      ``pin_state_shards`` + ``shard_rows``'s divisible-widths policy), so
      streamed inputs and threaded device state always agree on sharding —
      and padded on the host otherwise;
    * ``"stream_each"``: streamed always, each capacity sharded where the
      mesh divides it and replicated where not;
    * ``"seed"``: nothing but the seed while there are no observations —
      the dynamic init zeroes the buffers IN-TRACE (``ops/sweep.py``
      ``init_obs_state``'s absent-budget branch), so h2d is flat in config
      count, like the incumbent-only d2h.

    A multi-process mesh never streams: host-identical arguments become
    replicated global arrays, the program's ``in_shardings`` there.

    ``trace`` stamps the spans and journal records; ``profile_dir``
    captures a ``jax.profiler`` trace of each chunk's device window.
    """

    def __init__(self, eval_fn, codec, options: Dict[str, Any], space_sig=(),
                 *, dynamic: bool, resident: bool = False,
                 incumbent_only: bool = False, thread_state: bool = False,
                 device_metrics: Optional[bool] = None, cold: str = "host",
                 warm_v=None, warm_l=None, trace=None,
                 profile_dir: Optional[str] = None):
        from hpbandster_tpu.obs.device_metrics import device_metrics_default
        from hpbandster_tpu.obs.runtime import transfer_counters
        from hpbandster_tpu.parallel.mesh import is_multiprocess_mesh

        self.eval_fn, self.codec, self.options = eval_fn, codec, options
        self.space_sig = space_sig
        self.dynamic, self.resident = bool(dynamic), bool(resident)
        self.incumbent_only = bool(incumbent_only)
        self.thread_state = bool(thread_state)
        # telemetry changes the compiled program, so the default is
        # explicit, never inferred from the ambient bus
        self.device_metrics = (
            device_metrics_default()
            if device_metrics is None else bool(device_metrics)
        )
        self.cold = cold
        self.warm_v = {} if warm_v is None else warm_v
        self.warm_l = {} if warm_l is None else warm_l
        self.trace, self.profile_dir = trace, profile_dir
        self.mesh = options.get("mesh")
        self.axis = options.get("axis", "config")
        self.multiprocess = is_multiprocess_mesh(self.mesh)
        #: the AOT-compiled executable of the last chunk dispatched
        self.last_executable = None
        #: device-resident observation state threaded between dynamic
        #: chunks (the return_state/donation contract, ops/sweep.py): the
        #: previous chunk's returned (obs_v, obs_l, counts) pytrees feed
        #: the next call directly — donated, so XLA updates the buffers in
        #: place and the warm state never round-trips through the host.
        #: Invalidated when a capacity bucket doubles (shapes changed);
        #: the host observations then rebuild identical values.
        self._state = None
        self._state_caps = None
        #: fetched per-chunk metrics pytrees + their bracket schedules —
        #: decoded once at the end of the call into ONE telemetry record
        self._dm_parts: List[Any] = []
        self._dm_execute_s = 0.0
        self._link0 = transfer_counters()
        self._chunks = 0

    # ------------------------------------------------------------ program
    def build(self, plans, caps=None):
        """The jitted program of ``plans`` (``caps``: the dynamic tier's
        buffer capacities), uncompiled."""
        return make_fused_sweep_fn(
            self.eval_fn, plans, self.codec,
            warm_counts={b: len(l) for b, l in self.warm_l.items()},
            dynamic_counts=self.dynamic,
            capacities=caps,
            return_state=self.thread_state,
            resident=self.resident,
            incumbent_only=self.incumbent_only,
            device_metrics=self.device_metrics,
            **self.options,
        )

    def _key(self, plans, caps):
        if self.dynamic:
            # the whole point of the dynamic tier: observation counts are
            # traced inputs, so they must NOT key the executable — only the
            # buffer capacities (shapes) do
            obs_term = ("dynamic", tuple(sorted(caps.items())),
                        self.resident, self.thread_state)
        else:
            obs_term = tuple(sorted((b, len(l)) for b, l in self.warm_l.items()))
        return (
            self.eval_fn,
            tuple((p.num_configs, p.budgets) for p in plans),
            self.codec.signature,
            self.space_sig,
            # every option by name: stateless and stateful objectives, the
            # mesh, the model's knobs, the scorer, shard_sampling, and the
            # ledger label (program_name is part of what the caller asked
            # for: a relabeled request must not serve an executable tracked
            # under the old name — roofline attribution would lie)
            tuple(sorted(
                (k, v) for k, v in self.options.items()
                if k not in _KEYED_BY_SPACE_SIG
            )),
            obs_term,
            self.incumbent_only,
            # telemetry changes the traced program (extra outputs), so
            # metrics-on and metrics-off executables must never collide
            self.device_metrics,
        )

    def _compiled(self, plans, args, caps, span):
        """AOT-compiled sweep executable + honest timing attribution:
        returns ``(compiled, build_compile_seconds, cache_hit)``. Ahead-of-
        time ``lower().compile()`` separates compile from execute time (the
        jit dispatch path can't), and the cached executable skips retracing
        on repeated runs of the same schedule. ``build_compile_seconds`` is
        the time THIS call paid — 0.0 on a cache hit, so summing it across
        artifacts never double-counts a compile. On a miss the two halves
        are spans of their own (``compile.trace_lower``: Python trace and
        lowering; ``compile.compile``: XLA, or the persistent cache's
        load), and their seconds are added to the gauges
        ``sweep.build.trace_lower_s`` / ``sweep.build.compile_s``: sums over
        the process's builds, which a hit does not touch."""
        key = self._key(plans, caps)
        compiled = _SWEEP_EXE_CACHE.get(key)
        hit, dt = compiled is not None, 0.0
        if not hit:
            from hpbandster_tpu.obs.timeline import COMPILE
            from hpbandster_tpu.utils.compile_cache import (
                enable_persistent_compile_cache,
            )

            # before the first compile: a second process (or the next chip
            # call, where the machine keeps the directory) loads the program
            enable_persistent_compile_cache()
            t0 = time.perf_counter()
            with span("compile.trace_lower", COMPILE):
                lowered = self.build(plans, caps).lower(*args)
            t1 = time.perf_counter()
            with span("compile.compile", COMPILE):
                compiled = lowered.compile()
            t2 = time.perf_counter()
            dt = t2 - t0
            _SWEEP_EXE_CACHE[key] = compiled
            gauge = obs.get_metrics().gauge
            gauge("sweep.build.trace_lower_s").inc(t1 - t0)
            gauge("sweep.build.compile_s").inc(t2 - t1)
        self.last_executable = compiled
        return compiled, dt, hit

    # ------------------------------------------------------------ staging
    def _capacities(self, plans) -> Dict[float, int]:
        # PAST-ONLY capacities, pow2-bucketed with a generous floor: the
        # host observations at this chunk boundary + the additions of
        # ``plans``, rounded up. Two runs that agree on history agree on
        # every chunk's buffer shapes regardless of how much schedule lies
        # ahead (the resume guarantee), and consecutive chunks reuse one
        # executable until a bucket doubles. The 256 floor makes doublings
        # RARE: any run under 256 observations per budget is one compile
        # total, and a 10k-config sweep crosses ~6 boundaries — where a
        # floor-of-8 bucket spent the whole small-run regime in
        # doubling-dense territory and recompiled almost every chunk
        # (measured: 8 compiles/9 chunks). Masked model math over >=256
        # rows is trivial device work next to that.
        counts = {float(b): len(l) for b, l in self.warm_l.items()}
        for b, k in plan_additions(plans).items():
            counts[b] = counts.get(b, 0) + k
        return pow2_capacities(counts)

    def _streams(self, caps) -> bool:
        if (self.cold not in ("stream_all", "stream_each")
                or self.mesh is None or self.multiprocess):
            return False
        if self.cold == "stream_each":
            return True
        from hpbandster_tpu.parallel.mesh import shard_count

        n_shards = shard_count(self.mesh, self.axis)
        return n_shards > 1 and all(c % n_shards == 0 for c in caps.values())

    def _stage(self, seed, caps):
        """``(args, upload_bytes)`` of one chunk."""
        import jax

        d = int(self.codec.kind.shape[0])
        streamed_bytes = None
        if not self.dynamic:
            args = (seed, self.warm_v, self.warm_l) if self.warm_l else (seed,)
        elif self._state is not None and caps == self._state_caps:
            # same buffer shapes: hand the previous chunk's device state
            # straight back — zero warm-state bytes cross the host link
            args = (seed,) + self._state
        elif self.cold == "seed" and not self.warm_l:
            args = (seed,)
        elif self._streams(caps):
            # sharded mesh: warm buffers stream up PER SHARD SLICE — the
            # full-capacity array (1M+ rows at the 2^20 scale) never
            # materializes on host in one piece (ISSUE 10: bounded peak
            # host RSS)
            buffers, streamed_bytes = stream_warm_buffers(
                self.warm_v, self.warm_l, caps, d, self.mesh, self.axis,
                replicate_indivisible=self.cold == "stream_each",
            )
            args = (seed,) + buffers
        else:
            warm_v_pad, warm_l_pad, warm_n = {}, {}, {}
            for b, cap in caps.items():
                v = self.warm_v.get(b)
                n = 0 if v is None else len(v)
                buf_v = np.zeros((cap, d), np.float32)
                buf_l = np.full(cap, np.inf, np.float32)
                if n:
                    buf_v[:n] = v
                    buf_l[:n] = self.warm_l[b]
                warm_v_pad[b] = buf_v
                warm_l_pad[b] = buf_l
                warm_n[b] = np.int32(n)
            args = (seed, warm_v_pad, warm_l_pad, warm_n)
        # the budget gate's transfer ledger: bytes the host link actually
        # carries this chunk — measured BEFORE any to_global conversion
        # below wraps the numpy leaves in jax Arrays (measuring after would
        # read 0 on the DCN tier). Device-resident state leaves cost
        # nothing: that is the state-threading win. Streamed buffers are
        # jax Arrays already, so their streamer counted them (and their
        # counts).
        if streamed_bytes is not None:
            upload_bytes = int(seed.nbytes) + streamed_bytes
        else:
            upload_bytes = sum(
                int(getattr(l, "nbytes", 0))
                for l in jax.tree_util.tree_leaves(args)
                if not isinstance(l, jax.Array)
            )
        if self.multiprocess:
            # DCN tier: host-local numpy args become GLOBAL replicated
            # arrays (every rank holds identical values — the SPMD
            # drivers run the same deterministic control flow), matching
            # the sweep executable's replicated in_shardings. Leaves
            # that are already jax Arrays (the threaded device state)
            # pass through untouched — they carry the right sharding
            # from the previous call's out_shardings.
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(self.mesh, PartitionSpec())

            def to_global(x):
                if isinstance(x, jax.Array):
                    return x
                arr = np.asarray(x)
                return jax.make_array_from_callback(
                    arr.shape, rep, lambda idx: arr[idx]
                )

            args = jax.tree.map(to_global, args)
        return args, upload_bytes

    # -------------------------------------------------------------- chunk
    def run_chunk(self, plans: Sequence, seed, phase_s: Dict[str, float],
                  first_bracket: int = 0,
                  sized_by: Optional[Sequence] = None,
                  while_device_runs: Optional[Callable[[], None]] = None,
                  ) -> Tuple[Any, Dict[str, Any]]:
        """Stage, compile (or find), dispatch and fetch one chunk of
        ``plans``, the brackets from ``first_bracket`` on; the spans'
        seconds go to ``phase_s``. Returns the fetched outputs (per-bracket
        records, or the incumbent payload) and the chunk's stat row, which
        the caller extends and hands to :meth:`journal`. ``sized_by`` are
        the plans whose additions size a dynamic chunk's buffers, when
        they are not the chunk's own: a caller that keeps no observations
        on the host sizes them for its whole schedule once, so that every
        chunk shares buffer shapes, the run is one executable and the
        threaded state never re-uploads. ``while_device_runs`` is called
        between dispatch and fetch: host work hidden in the device's
        window."""
        import jax

        from hpbandster_tpu.obs.runtime import note_transfer
        from hpbandster_tpu.obs.timeline import (
            COMPILE,
            RUNG_COMPUTE,
            TRANSFER,
            sweep_span,
        )
        from hpbandster_tpu.obs.trace import use_trace
        from hpbandster_tpu.utils.profiling import trace

        span = functools.partial(sweep_span, totals=phase_s, trace=self.trace)
        # the staging window: warm-buffer padding / streaming,
        # transfer-ledger accounting, replicated-array wrapping -- the
        # host cost of putting this chunk's inputs on the device link (the
        # flight recorder's h2d counterpart of telemetry_fetch)
        with span("chunk_staging", TRANSFER):
            caps = (
                self._capacities(plans if sized_by is None else sized_by)
                if self.dynamic else None
            )
            args, upload_bytes = self._stage(seed, caps)
            note_transfer("h2d", upload_bytes)
        with trace(self.profile_dir), use_trace(self.trace):
            # on a ledger miss this window is the real trace+build wall
            # (also reported as compile_s on the chunk record); on a hit,
            # the lookup itself
            with span("compile_lookup", COMPILE):
                compiled, compile_s, cache_hit = self._compiled(
                    tuple(plans), args, caps, span
                )
            t_exec = time.perf_counter()
            # arguments up and the program enqueued: returns before the
            # device has finished (async dispatch)
            with span("dispatch", TRANSFER):
                raw = compiled(*args)
            # (outputs[, metrics][, state]) by mode. The updated
            # observation state stays ON DEVICE for the next chunk; only
            # the outputs (and the O(schedule) metrics pytree) are fetched
            parts = raw if (self.device_metrics or self.thread_state) else (raw,)
            dm_dev = parts[1] if self.device_metrics else None
            if self.thread_state:
                self._state, self._state_caps = parts[-1], caps
            if while_device_runs is not None:
                while_device_runs()
            # the host blocked on the device: what is left of the
            # program's run, then the outputs' d2h
            with span("fetch", RUNG_COMPUTE):
                outputs = jax.device_get(parts[0])
            dm_host = None
            if dm_dev is not None:
                # outputs already synced above, so this fetch is pure d2h
                # of the O(schedule) telemetry pytree — the one
                # transfer-phase slice the fused journal can measure
                # honestly
                with span("telemetry_fetch", TRANSFER):
                    dm_host = jax.device_get(dm_dev)
            # span of the device phase (dispatch -> fetch complete). When
            # overlapped host work outlasts the device work this
            # OVERSTATES device-busy seconds, so derived MFU reads
            # conservative; replay_overlap_s makes it attributable.
            execute_s = time.perf_counter() - t_exec
        leaves = jax.tree_util.tree_leaves(outputs)
        if dm_host is not None:
            self._dm_parts.append(
                (dm_host, [(p.num_configs, p.budgets) for p in plans])
            )
            self._dm_execute_s += execute_s
            # the telemetry rides the same final d2h; its bill is
            # O(schedule), measured here rather than asserted
            leaves = leaves + jax.tree_util.tree_leaves(dm_host)
        d2h_bytes = sum(int(np.asarray(l).nbytes) for l in leaves)
        # sweep.host_syncs counts buffers: an incumbent payload is billed
        # by the buffer (its flat bill is what the resident tiers pin), a
        # chunk's whole per-bracket output as the one fetch it is
        note_transfer("d2h", d2h_bytes,
                      buffers=len(leaves) if self.incumbent_only else 1)
        self._chunks += 1
        return outputs, {
            "brackets": list(range(first_bracket, first_bracket + len(plans))),
            "evaluations": int(sum(sum(p.num_configs) for p in plans)),
            # seconds THIS call paid to trace and compile: 0.0 on a hit
            "build_compile_s": round(compile_s, 4),
            "compile_cache_hit": cache_hit,
            "execute_fetch_s": round(execute_s, 4),
            # where this chunk's warm observations came from: the seed's 4
            # bytes = the donated device thread carried them
            "warm_upload_bytes": int(upload_bytes),
            "d2h_bytes": d2h_bytes,
        }

    def journal(self, stat: Dict[str, Any], seq: int,
                phase_s: Dict[str, float]) -> None:
        """One span-shaped ``sweep_chunk`` event per device chunk: the
        journal's view of the fused tier (duration = dispatch -> fetch;
        compile split out; h2d/d2h byte fields feed the summarize host-link
        section; the flight recorder lays the decoded per-rung sections
        onto its interval). A sink write: the caller charges it to a span
        of its own."""
        from hpbandster_tpu.obs.trace import use_trace

        with use_trace(self.trace):
            obs.emit(
                "sweep_chunk",
                duration_s=stat["execute_fetch_s"],
                compile_s=stat["build_compile_s"],
                compile_cache_hit=stat["compile_cache_hit"],
                evaluations=stat["evaluations"],
                brackets=stat["brackets"],
                seq=seq,
                h2d_bytes=stat["warm_upload_bytes"],
                d2h_bytes=stat["d2h_bytes"],
                # the phases that have closed by now; the later ones are
                # journal events of their own
                phase_s=dict(phase_s),
            )

    def finish(self):
        """After the last chunk: ``(transfers, telemetry)``. ``transfers``
        are the call's host-link deltas, published as the per-sweep gauges
        (``sweep.transfer_bytes.{h2d,d2h}``, ``sweep.host_syncs``); None
        when no chunk ran. ``telemetry`` folds every chunk's device
        telemetry into ONE decoded record — gauges for the scraper, a
        ``device_telemetry`` journal record for summarize/report/anomaly:
        the obs pipeline's view of work that never surfaced to host per
        bracket — or None with the metrics plane off. On a pod every rank
        publishes its own copy: SPMD values are identical on all ranks."""
        link = decoded = None
        if self._chunks:
            from hpbandster_tpu.obs.runtime import publish_sweep_transfers

            link = publish_sweep_transfers(self._link0)
        if self._dm_parts:
            from hpbandster_tpu.obs.device_metrics import (
                decode_device_metrics,
                emit_device_telemetry,
                publish_device_metrics,
            )
            from hpbandster_tpu.obs.trace import use_trace

            decoded = decode_device_metrics(
                self._dm_parts, execute_s=self._dm_execute_s
            )
            publish_device_metrics(decoded)
            # journaled under the sweep's trace: the device loop's rung
            # sections join the same per-trace timeline as the host-side
            # chunk spans (summarize trace_timelines / obs timeline)
            with use_trace(self.trace):
                emit_device_telemetry(decoded)
                _note_device_refits(decoded)
        return link, decoded
