"""``hpbandster_tpu.obs`` — structured events, metrics, and run journal.

The telemetry substrate the master/dispatcher/worker/optimizer layers
emit into (see docs/observability.md):

* :mod:`~hpbandster_tpu.obs.metrics` — thread-safe counters / gauges /
  fixed-bucket histograms with an atomic :meth:`MetricsRegistry.snapshot`;
* :mod:`~hpbandster_tpu.obs.events` — the typed event bus
  (``job_submitted`` ... ``unknown_result``) plus monotonic-clock
  :func:`span` regions (host-only; the fused driver's
  :func:`sweep_span` in ``obs/timeline.py`` adds the profiler's clock);
* :mod:`~hpbandster_tpu.obs.journal` — rotating JSONL run journal +
  in-memory ring buffer for post-mortems, identity-stamped via
  ``static_fields`` / ``configure(identity=...)``;
* :mod:`~hpbandster_tpu.obs.trace` — per-job trace context propagated
  across RPC hops (the ``_obs`` envelope in ``parallel/rpc.py``), stamped
  onto every event as ``trace_id``;
* :mod:`~hpbandster_tpu.obs.health` — the ``obs_snapshot`` fleet-health
  RPC endpoint (+ latency quantiles) + :func:`install_crash_dump`
  forensics;
* :mod:`~hpbandster_tpu.obs.audit` — the optimizer decision audit:
  ``config_sampled`` / ``promotion_decision`` records (why BOHB sampled
  a config, what a rung promotion decided) + :func:`config_lineage`;
* :mod:`~hpbandster_tpu.obs.anomaly` — streaming anomaly detection
  (stragglers, flapping workers, NaN bursts, KDE-refit stalls,
  recompile storms) emitting ``alert`` events + counters;
* :mod:`~hpbandster_tpu.obs.slo` / :mod:`~hpbandster_tpu.obs.alerts` —
  declarative SLOs with multi-window multi-burn-rate evaluation
  (page 5m/1h, ticket 6h/3d) and the pending → firing → resolved alert
  lifecycle: journaled ``slo_alert`` transitions,
  ``slo.<name>.{burn_rate,budget_remaining,state}`` gauges, and a
  byte-identical offline replay (``obs slo <journal>``);
* :mod:`~hpbandster_tpu.obs.runtime` — XLA runtime telemetry: the
  :func:`tracked_jit` compile ledger (``xla_compile`` events, per-fn
  recompile counters), the periodic :class:`DeviceSampler` memory /
  live-buffer gauges, and :func:`note_transfer` host<->device counters;
* :mod:`~hpbandster_tpu.obs.export` — the Prometheus-compatible
  exporter: strict text exposition rendering of any registry snapshot,
  a round-trip parser, the ``metrics_text`` health-RPC mount, and the
  ``python -m hpbandster_tpu.obs export`` HTTP bridge;
* :mod:`~hpbandster_tpu.obs.collector` — the fleet observatory:
  :class:`FleetCollector` polls every ``obs_snapshot`` endpoint into a
  rotating series file + derived fleet gauges (device balance, worker
  churn, queue trend, compile rate) feeding the ``fleet_imbalance`` /
  ``worker_churn`` anomaly rules and the ``obs top`` dashboard;
* :mod:`~hpbandster_tpu.obs.profile` — on-demand deep profiling:
  :class:`ProfileSession` behind the ``start_profile``/``stop_profile``
  health RPCs, plus :func:`roofline_report` over the AOT compile
  ledger's cost analysis (FLOPs/bytes per bucketed program);
* ``python -m hpbandster_tpu.obs summarize <journal> [<journal> ...]`` —
  per-stage latency percentiles, worker utilization, failure tallies, and
  merged cross-host per-trace timelines; ``report`` renders the
  deterministic optimizer story (incumbent trajectory, model-vs-random
  win rate, promotion regret, alert digest); ``watch <journal>`` tails a
  live run (``watch --snapshot host:port`` polls a health RPC instead).

Everything here is stdlib-only and costs ~nothing when no sink is
attached, so the instrumentation stays on permanently — attach sinks to
look.

Quick start::

    from hpbandster_tpu import obs

    handle = obs.configure(journal_path="run/journal.jsonl")
    try:
        ...  # any optimizer run; events stream into the journal
    finally:
        handle.close()
    # then: python -m hpbandster_tpu.obs summarize run/journal.jsonl
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Union

from hpbandster_tpu.obs import events as _events
from hpbandster_tpu.obs import metrics as _metrics
from hpbandster_tpu.obs.anomaly import (  # noqa: F401
    AnomalyDetector,
    AnomalyRules,
    scan_records,
)
from hpbandster_tpu.obs.alerts import (  # noqa: F401
    AlertManager,
    scan_slo_records,
)
from hpbandster_tpu.obs.slo import (  # noqa: F401
    BurnWindow,
    DEFAULT_WINDOWS,
    Selector,
    SLOEvaluator,
    SLOSpec,
    default_slo_pack,
)
from hpbandster_tpu.obs.collector import (  # noqa: F401
    FleetCollector,
    derive_fleet,
    format_fleet_table,
    read_series,
)
from hpbandster_tpu.obs.device_metrics import (  # noqa: F401
    budget_cost_from_obs,
    decode_device_metrics,
    device_metrics_default,
    emit_device_telemetry,
    publish_device_metrics,
)
from hpbandster_tpu.obs.audit import (  # noqa: F401
    AUDIT_EVENTS,
    AUDIT_RULE_FIELDS,
    config_lineage,
    drain_stragglers,
    emit_bracket_created,
    emit_bracket_promotion,
    emit_config_sampled,
    emit_promotion_decision,
    emit_sweep_incumbent,
    note_straggler,
)
from hpbandster_tpu.obs.events import (  # noqa: F401
    ALERT,
    BRACKET_PROMOTION,
    CHAOS_FAULT,
    CHECKPOINT_WRITTEN,
    CONFIG_SAMPLED,
    DEVICE_TELEMETRY,
    DUPLICATE_RESULT,
    EVENT_TYPES,
    FLEET_SAMPLE,
    JOB_FAILED,
    JOB_FINISHED,
    JOB_REQUEUED,
    JOB_STARTED,
    JOB_SUBMITTED,
    KDE_REFIT,
    PROMOTION_DECISION,
    RESULT_DELIVERED,
    RESULT_REPLAYED,
    RPC_CLIENT_CALL,
    RPC_RETRY,
    SLO_ALERT,
    SWEEP_INCUMBENT,
    UNKNOWN_RESULT,
    WORKER_DISCOVERED,
    WORKER_DROPPED,
    WORKER_QUARANTINED,
    XLA_COMPILE,
    Event,
    EventBus,
    emit,
    get_bus,
    make_event,
    span,
)
from hpbandster_tpu.obs.export import (  # noqa: F401
    parse_prometheus_text,
    render_registry,
    render_snapshot,
)
from hpbandster_tpu.obs.health import (  # noqa: F401
    HealthEndpoint,
    install_crash_dump,
)
from hpbandster_tpu.obs.journal import (  # noqa: F401
    JsonlJournal,
    RingBuffer,
    process_identity,
    read_journal,
)
from hpbandster_tpu.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_metrics,
)
from hpbandster_tpu.obs.profile import (  # noqa: F401
    ProfileSession,
    device_peaks,
    device_phase_map,
    format_roofline,
    get_profile_session,
    roofline_report,
)
from hpbandster_tpu.obs.runtime import (  # noqa: F401
    CompileTracker,
    DeviceSampler,
    get_compile_tracker,
    note_transfer,
    publish_sweep_transfers,
    runtime_snapshot,
    start_device_sampler,
    tracked_jit,
    transfer_counters,
)
# KDE_REFIT deliberately not re-imported: the phase constant shares its
# value with the already-exported event name (both "kde_refit")
from hpbandster_tpu.obs.timeline import (  # noqa: F401
    ADMISSION,
    COMPILE,
    DEVICE_SCOPES,
    LANE_SCOPES,
    PHASES,
    PROMOTION,
    RPC,
    RUNG_COMPUTE,
    TRANSFER,
    TimelineRecorder,
    align_clocks,
    build_timeline,
    critical_path,
    format_critical_path,
    mark,
    phase_span,
    sweep_span,
    to_chrome_trace,
)
from hpbandster_tpu.obs.trace import (  # noqa: F401
    DEFAULT_TENANT,
    TraceContext,
    current_run,
    current_tenant,
    current_trace,
    current_wire,
    extract_tenant,
    extract_wire,
    new_trace,
    use_run,
    use_tenant,
    use_trace,
)

__all__ = [
    "Event", "EventBus", "emit", "make_event", "get_bus", "span",
    "JsonlJournal", "RingBuffer", "read_journal", "process_identity",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_metrics",
    "TraceContext", "new_trace", "current_trace", "use_trace",
    "current_wire", "extract_wire",
    "DEFAULT_TENANT", "current_tenant", "use_tenant", "extract_tenant",
    "current_run", "use_run",
    "HealthEndpoint", "install_crash_dump",
    "AnomalyDetector", "AnomalyRules", "scan_records",
    "AlertManager", "scan_slo_records", "SLOSpec", "SLOEvaluator",
    "Selector", "BurnWindow", "DEFAULT_WINDOWS", "default_slo_pack",
    "SLO_ALERT",
    "AUDIT_EVENTS", "AUDIT_RULE_FIELDS", "config_lineage",
    "emit_bracket_created", "emit_bracket_promotion",
    "emit_config_sampled", "emit_promotion_decision",
    "emit_sweep_incumbent",
    "note_straggler", "drain_stragglers",
    "decode_device_metrics", "publish_device_metrics",
    "emit_device_telemetry", "budget_cost_from_obs",
    "device_metrics_default",
    "CompileTracker", "DeviceSampler", "get_compile_tracker",
    "note_transfer", "publish_sweep_transfers", "transfer_counters",
    "runtime_snapshot", "start_device_sampler",
    "tracked_jit",
    "FleetCollector", "derive_fleet", "format_fleet_table", "read_series",
    "ProfileSession", "get_profile_session", "device_peaks",
    "roofline_report", "format_roofline",
    "device_phase_map",
    "render_snapshot", "render_registry", "parse_prometheus_text",
    "configure", "set_enabled", "enabled",
    "EVENT_TYPES", "JOB_SUBMITTED", "JOB_STARTED", "JOB_FINISHED",
    "JOB_FAILED", "WORKER_DISCOVERED", "WORKER_DROPPED",
    "BRACKET_PROMOTION", "KDE_REFIT", "RPC_RETRY", "RESULT_DELIVERED",
    "CHECKPOINT_WRITTEN", "UNKNOWN_RESULT",
    "CONFIG_SAMPLED", "PROMOTION_DECISION", "ALERT", "XLA_COMPILE",
    "FLEET_SAMPLE",
    "JOB_REQUEUED", "RESULT_REPLAYED", "DUPLICATE_RESULT",
    "WORKER_QUARANTINED", "CHAOS_FAULT", "SWEEP_INCUMBENT",
    "DEVICE_TELEMETRY", "RPC_CLIENT_CALL",
    "PHASES", "ADMISSION", "COMPILE", "TRANSFER", "RUNG_COMPUTE",
    "PROMOTION", "RPC",
    "DEVICE_SCOPES", "LANE_SCOPES", "sweep_span",
    "phase_span", "mark", "TimelineRecorder", "align_clocks",
    "build_timeline", "to_chrome_trace", "critical_path",
    "format_critical_path",
]


def set_enabled(flag: bool) -> None:
    """Process-wide kill switch: ``False`` turns every emit / counter /
    span into a single-boolean-check no-op."""
    _events._set_enabled(flag)
    _metrics._set_enabled(flag)


def enabled() -> bool:
    return _events._ENABLED


class ObsHandle:
    """What :func:`configure` returns: the attached sinks + one close()."""

    def __init__(self, detachers: List[Callable[[], None]],
                 journal: Optional[JsonlJournal], ring: Optional[RingBuffer],
                 anomaly: Optional[AnomalyDetector] = None,
                 sampler: Optional[DeviceSampler] = None,
                 slo: Optional[AlertManager] = None):
        self._detachers = detachers
        self.journal = journal
        self.ring = ring
        self.anomaly = anomaly
        self.sampler = sampler
        self.slo = slo

    def close(self) -> None:
        """Detach every sink and close the journal file (idempotent)."""
        for detach in self._detachers:
            detach()
        self._detachers = []
        if self.sampler is not None:
            from hpbandster_tpu.obs.runtime import _clear_device_sampler

            self.sampler.stop()
            _clear_device_sampler(self.sampler)
            self.sampler = None
        if self.journal is not None:
            self.journal.close()

    def __enter__(self) -> "ObsHandle":
        return self

    def __exit__(self, *exc: object) -> None:
        self.close()


def configure(
    journal_path: Optional[str] = None,
    journal_max_bytes: int = 16 * 1024 * 1024,
    journal_max_files: int = 3,
    ring_capacity: int = 0,
    identity: Union[bool, Dict[str, Any], None] = None,
    bus: Optional[EventBus] = None,
    anomaly: Union[bool, AnomalyRules, None] = None,
    device_sampler: Union[bool, float, None] = None,
    slo: Union[bool, List["SLOSpec"], None] = None,
) -> ObsHandle:
    """Attach the standard sinks to ``bus`` (default: the process bus).

    ``journal_path`` enables the rotating JSONL journal; ``ring_capacity
    > 0`` additionally keeps the newest events in memory for post-mortems.
    ``identity`` stamps every journal record with this process's identity:
    ``True`` for the automatic ``{host, pid}`` pair, or a dict of extra
    fields (``{"worker_id": ...}``) merged over it — the stamp that lets
    ``summarize a.jsonl b.jsonl`` attribute merged cross-host records.
    ``anomaly`` attaches a streaming :class:`AnomalyDetector` (``True``
    for default :class:`AnomalyRules`, or pass tuned rules); its ``alert``
    events land in the same journal and its tally is on the handle as
    ``handle.anomaly``. ``slo`` attaches an :class:`AlertManager`
    (``True`` for :func:`default_slo_pack`, or pass a list of
    :class:`SLOSpec`); its ``slo_alert`` transitions land in the same
    journal (replayable via ``obs slo``) and the manager is on the
    handle as ``handle.slo``. ``device_sampler`` starts the periodic per-device
    memory / live-buffer gauge sampler (``True`` for the default 10 s
    cadence, or a number of seconds) — only in processes that run device
    work, since the first sample initializes the jax backend. Returns an
    :class:`ObsHandle` — close it to detach (tests and multi-run
    processes must, or sinks accumulate)."""
    bus = bus if bus is not None else get_bus()
    detachers: List[Callable[[], None]] = []
    journal = None
    ring = None
    detector = None
    if journal_path is not None:
        static = None
        if identity:
            static = process_identity(
                **(identity if isinstance(identity, dict) else {})
            )
        journal = JsonlJournal(
            journal_path, max_bytes=journal_max_bytes,
            max_files=journal_max_files, static_fields=static,
        )
        detachers.append(bus.subscribe(journal))
    if ring_capacity > 0:
        ring = RingBuffer(ring_capacity)
        detachers.append(bus.subscribe(ring))
    if anomaly:
        detector = AnomalyDetector(
            rules=anomaly if isinstance(anomaly, AnomalyRules) else None,
            bus=bus,
        )
        detachers.append(bus.subscribe(detector))
    manager = None
    if slo:
        manager = AlertManager(
            specs=slo if isinstance(slo, (list, tuple)) else None,
            bus=bus,
        )
        detachers.append(bus.subscribe(manager))
    sampler = None
    if device_sampler:
        sampler = start_device_sampler(
            interval_s=10.0 if device_sampler is True else float(device_sampler)
        )
    return ObsHandle(detachers, journal, ring, detector, sampler, manager)
