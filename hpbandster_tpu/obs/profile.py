"""On-demand deep profiling: remote trace capture + roofline attribution.

Before this module, capturing a ``jax.profiler`` trace of a fleet
process meant deciding at *construction* time (``FusedBOHB(profile_dir=
...)`` wraps the whole sweep) — there was no way to ask an already-hot
worker "show me the next thirty seconds". Two pieces fix that:

* :class:`ProfileSession` — a thread-safe wrapper over
  ``jax.profiler.start_trace`` / ``stop_trace`` with a process-wide
  default instance. Every :class:`~hpbandster_tpu.obs.health
  .HealthEndpoint` registers it as ``start_profile`` / ``stop_profile``
  / ``profile_status`` RPCs, so any fleet peer can be told to capture a
  trace *now*, remotely, and report where the files landed. Errors come
  back as ``{"ok": False, "error": ...}`` dicts, never as exceptions —
  a profiling request must not be able to take a serving process down.

* :func:`roofline_report` — walks the AOT compile ledger
  (:class:`~hpbandster_tpu.obs.runtime.CompileTracker`), whose
  ``_TrackedLowered`` proxy now records each compiled program's
  ``cost_analysis()`` (FLOPs + bytes accessed), and attributes
  arithmetic intensity per bucketed program: which programs are
  compute-bound vs memory-bound on this chip, and — given measured
  execution seconds — achieved-vs-peak utilization. Peak FLOP/s comes
  from ``workloads/flops.py``'s per-chip table; HBM bandwidth from the
  table below. **CPU caveat** (docs/observability.md): XLA's CPU
  backend still reports FLOPs/bytes, but there are no peak numbers for
  arbitrary host CPUs, so ``bound``/``utilization`` are None there —
  the intensities themselves remain exact and portable.

jax loads lazily inside the functions that need it (the standard obs
rule); importing this module costs nothing.
"""

from __future__ import annotations

import os
import re
import tempfile
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

from hpbandster_tpu.obs.metrics import get_metrics

__all__ = [
    "ProfileSession",
    "get_profile_session",
    "device_peaks",
    "ProgramText",
    "adopted_phase_map",
    "device_kind_map",
    "device_phase_map",
    "hlo_module_name",
    "parse_program_text",
    "roofline_report",
    "format_roofline",
    "transfer_summary",
]

#: per-chip HBM bandwidth (bytes/s) by ``device.device_kind`` prefix —
#: the memory edge of the roofline (peak FLOP/s lives in
#: workloads/flops.py). v5e: 819 GB/s; v5p: 2765; v4: 1228; v3: 900;
#: v6e: 1640. Unknown kinds (CPU included) return None.
_PEAK_HBM_BYTES_S = {
    "TPU v6 lite": 1640e9,
    "TPU v5 lite": 819e9,
    "TPU v5p": 2765e9,
    "TPU v4": 1228e9,
    "TPU v3": 900e9,
}


class ProfileSession:
    """One process's on-demand ``jax.profiler`` capture state.

    At most one trace is live at a time (jax's own constraint); a second
    ``start`` reports the active capture instead of raising. All methods
    return JSON-serializable dicts — this is an RPC surface first.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._log_dir: Optional[str] = None
        self._t0_mono: Optional[float] = None
        self._captures = 0

    def start(self, log_dir: Optional[str] = None) -> Dict[str, Any]:
        """Begin capturing a trace into ``log_dir`` (a fresh temp dir by
        default, reported back so the caller can fetch/inspect it)."""
        with self._lock:
            if self._log_dir is not None:
                return {
                    "ok": False,
                    "error": "profile already active",
                    "log_dir": self._log_dir,
                }
            if log_dir is None:
                log_dir = tempfile.mkdtemp(prefix="hpb_profile_")
            try:
                import jax

                os.makedirs(log_dir, exist_ok=True)
                jax.profiler.start_trace(log_dir)
            except Exception as e:
                # the profiler failing must never look like the process
                # failing — report and keep serving
                return {"ok": False, "error": f"{type(e).__name__}: {e}"}
            self._log_dir = log_dir
            self._t0_mono = time.monotonic()
            get_metrics().counter("profile.captures_started").inc()
            return {"ok": True, "log_dir": log_dir}

    def stop(self) -> Dict[str, Any]:
        """End the live capture; reports the trace dir and duration."""
        with self._lock:
            if self._log_dir is None:
                return {"ok": False, "error": "no profile active"}
            log_dir = self._log_dir
            t0 = self._t0_mono
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:
                # keep the session marked active: jax's profiler may
                # still hold its trace open, and clearing our state here
                # would wedge profiling for the life of the process (no
                # later start can succeed, no later stop would retry)
                return {
                    "ok": False,
                    "error": f"{type(e).__name__}: {e}",
                    "log_dir": log_dir,
                }
            self._log_dir = None
            self._t0_mono = None
            self._captures += 1
            get_metrics().counter("profile.captures_completed").inc()
            return {
                "ok": True,
                "log_dir": log_dir,
                "duration_s": (
                    round(time.monotonic() - t0, 3) if t0 is not None else None
                ),
                "files": _count_trace_files(log_dir),
            }

    def status(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "active": self._log_dir is not None,
                "log_dir": self._log_dir,
                "elapsed_s": (
                    round(time.monotonic() - self._t0_mono, 3)
                    if self._t0_mono is not None else None
                ),
                "captures_completed": self._captures,
            }


def _count_trace_files(log_dir: str) -> int:
    n = 0
    for _dirpath, _dirnames, filenames in os.walk(log_dir):
        n += len(filenames)
    return n


_SESSION = ProfileSession()


def get_profile_session() -> ProfileSession:
    """The process-wide session every health endpoint exposes."""
    return _SESSION


# ------------------------------------------------------------------ roofline
def device_peaks(device: Any = None) -> Dict[str, Optional[float]]:
    """``{"flops_per_s", "bytes_per_s", "ridge_flops_per_byte", "kind"}``
    for one device (default: ``jax.devices()[0]``); values are None for
    chips without table entries — CPU most prominently."""
    if device is None:
        import jax

        device = jax.devices()[0]
    from hpbandster_tpu.workloads.flops import peak_bf16_flops

    kind = str(getattr(device, "device_kind", ""))
    flops = peak_bf16_flops(device)
    bw = None
    for prefix, v in _PEAK_HBM_BYTES_S.items():
        if kind.startswith(prefix):
            bw = v
            break
    return {
        "kind": kind,
        "flops_per_s": flops,
        "bytes_per_s": bw,
        "ridge_flops_per_byte": (flops / bw) if flops and bw else None,
    }


def transfer_summary(
    registry: Any = None,
) -> Optional[Dict[str, Any]]:
    """Host-link transfer view for the roofline report: process-lifetime
    byte/buffer counters (``obs.runtime.note_transfer``) plus the
    last-sweep gauges (``obs.runtime.publish_sweep_transfers``), or None
    when the process never counted a transfer. Registry-read only —
    never initializes jax."""
    from hpbandster_tpu.obs.metrics import get_metrics

    reg = registry if registry is not None else get_metrics()
    snap = reg.snapshot()
    counters = snap.get("counters") or {}
    gauges = snap.get("gauges") or {}
    total = {
        k: int(counters.get(f"runtime.{k}", 0) or 0)
        for k in ("transfer_bytes_h2d", "transfer_bytes_d2h",
                  "transfers_h2d", "transfers_d2h")
    }
    if not any(total.values()) and "sweep.transfer_bytes.d2h" not in gauges:
        return None
    out: Dict[str, Any] = {"process_total": total}
    last_sweep = {
        "h2d_bytes": gauges.get("sweep.transfer_bytes.h2d"),
        "d2h_bytes": gauges.get("sweep.transfer_bytes.d2h"),
        "host_syncs": gauges.get("sweep.host_syncs"),
    }
    if any(v is not None for v in last_sweep.values()):
        out["last_sweep"] = last_sweep
    return out


def roofline_report(
    tracker: Any = None,
    peaks: Optional[Dict[str, Optional[float]]] = None,
    seconds_by_program: Optional[Dict[str, float]] = None,
    transfers: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Attribute FLOPs/bytes per compiled program in the compile ledger.

    Covers every ledger program that recorded a ``cost_analysis`` (the
    AOT path: ``fn.lower(...).compile()`` through ``_TrackedLowered`` —
    exactly the bucket ledger's programs). ``peaks`` defaults to
    :func:`device_peaks` of the first local device, but never initializes
    jax when the ledger is empty. ``seconds_by_program`` maps
    ``"fn"`` or ``"fn@signature"`` to measured execution seconds — when
    given, the program's achieved FLOP/s and utilization-vs-peak are
    estimated (the *measured* half of the roofline; without it only the
    analytic half renders).

    Deterministic: programs sort by (fn, signature); content-only.
    """
    from hpbandster_tpu.obs.runtime import get_compile_tracker

    trk = tracker if tracker is not None else get_compile_tracker()
    costed = trk.program_costs()
    if peaks is None and costed:
        try:
            peaks = device_peaks()
        except Exception:  # graftlint: disable=swallowed-exception — no usable device is an expected state (CPU CI, no backend); the report renders with a caveat instead
            peaks = None
    peaks = peaks or {
        "kind": None, "flops_per_s": None, "bytes_per_s": None,
        "ridge_flops_per_byte": None,
    }
    peak_f = peaks.get("flops_per_s")
    peak_b = peaks.get("bytes_per_s")
    ridge = peaks.get("ridge_flops_per_byte")
    programs: List[Dict[str, Any]] = []
    for entry in costed:
        flops = entry.get("flops")
        nbytes = entry.get("bytes_accessed")
        intensity = (
            round(flops / nbytes, 4) if flops and nbytes else None
        )
        bound = None
        if intensity is not None and ridge:
            bound = "compute" if intensity >= ridge else "memory"
        # the floor execution time the chip's rooflines allow — what the
        # measured seconds are judged against
        floor_s = None
        if flops is not None and peak_f:
            floor_s = flops / peak_f
        if nbytes is not None and peak_b:
            mem_s = nbytes / peak_b
            floor_s = mem_s if floor_s is None else max(floor_s, mem_s)
        row = {
            "fn": entry["fn"],
            "signature": entry.get("signature"),
            "compiles": entry.get("compiles"),
            "compile_s": entry.get("compile_s"),
            "flops": flops,
            "bytes_accessed": nbytes,
            "intensity_flops_per_byte": intensity,
            "bound": bound,
            "roofline_floor_s": (
                round(floor_s, 9) if floor_s is not None else None
            ),
        }
        seconds = None
        if seconds_by_program:
            key = f"{entry['fn']}@{entry.get('signature')}"
            seconds = seconds_by_program.get(key)
            if seconds is None:
                seconds = seconds_by_program.get(entry["fn"])
        if seconds and flops:
            achieved = flops / seconds
            row["measured_s"] = round(float(seconds), 6)
            row["achieved_flops_per_s"] = round(achieved, 2)
            if peak_f:
                row["utilization_vs_peak"] = round(achieved / peak_f, 4)
        programs.append(row)
    programs.sort(key=lambda r: (r["fn"], str(r["signature"])))
    if transfers is None:
        transfers = transfer_summary()
    return {
        "peak": peaks,
        "programs": programs,
        "program_count": len(programs),
        # the host-link half of the roofline story: FLOPs/bytes above are
        # what the device did; this is what crossed the host link doing it
        "transfers": transfers,
        "caveats": [] if peak_f else [
            "no peak FLOP/s table entry for this device kind "
            "(CPU backends especially): intensities are exact, but "
            "bound/utilization columns cannot be computed"
        ],
    }


def format_roofline(report: Dict[str, Any]) -> str:
    """Human rendering of :func:`roofline_report` (the ``obs roofline``
    CLI body)."""
    peak = report.get("peak") or {}
    lines = [
        "roofline — device: {} (peak {} FLOP/s, {} B/s, ridge {} FLOP/B)".format(
            peak.get("kind") or "?",
            _si(peak.get("flops_per_s")), _si(peak.get("bytes_per_s")),
            _fmtnum(peak.get("ridge_flops_per_byte")),
        )
    ]
    header = (
        f"{'program':<38} {'flops':>10} {'bytes':>10} {'FLOP/B':>8} "
        f"{'bound':<8} {'floor_s':>11} {'util':>6}"
    )
    lines.append(header)
    lines.append("-" * len(header))
    for row in report.get("programs") or []:
        name = row["fn"]
        sig = row.get("signature")
        if sig:
            name = f"{name}[{sig}]"
        util = row.get("utilization_vs_peak")
        lines.append(
            f"{name[:38]:<38} {_si(row.get('flops')):>10} "
            f"{_si(row.get('bytes_accessed')):>10} "
            f"{_fmtnum(row.get('intensity_flops_per_byte')):>8} "
            f"{str(row.get('bound') or '-'):<8} "
            f"{_fmtnum(row.get('roofline_floor_s')):>11} "
            f"{(f'{100 * util:.1f}%' if util is not None else '-'):>6}"
        )
    if not report.get("programs"):
        lines.append("(no costed programs in the compile ledger — run an "
                     "AOT-compiled path first, e.g. a bucketed schedule)")
    transfers = report.get("transfers")
    if transfers:
        total = transfers.get("process_total") or {}
        lines.append(
            "host link (process): h2d {} / {} buffers, d2h {} / {} buffers".format(
                _si(total.get("transfer_bytes_h2d")),
                _si(total.get("transfers_h2d")),
                _si(total.get("transfer_bytes_d2h")),
                _si(total.get("transfers_d2h")),
            )
        )
        last = transfers.get("last_sweep")
        if last:
            lines.append(
                "host link (last sweep): h2d {}, d2h {}, {} host sync(s)".format(
                    _si(last.get("h2d_bytes")), _si(last.get("d2h_bytes")),
                    _si(last.get("host_syncs")),
                )
            )
    for c in report.get("caveats") or []:
        lines.append(f"note: {c}")
    return "\n".join(lines)


def _si(v: Any) -> str:
    if not isinstance(v, (int, float)) or isinstance(v, bool):
        return "-"
    v = float(v)
    for div, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(v) >= div:
            return f"{v / div:.2f}{suffix}"
    return f"{v:.0f}"


def _fmtnum(v: Any) -> str:
    if not isinstance(v, (int, float)) or isinstance(v, bool):
        return "-"
    return f"{float(v):.3g}"


# ---------------------------------------------------- device phase map
_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_HLO_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
#: the opcode stands after the shape, which may be a tuple's with spaces
#: inside it: the first lower-case word that a ``(`` follows directly (in a
#: shape a ``(`` follows ``T``, ``S`` or another bracket, never a space
#: and a lower-case word)
_HLO_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
#: where the operands of an instruction end: the ``)`` that an attribute
#: (``, calls=``) or the line's end follows; a ``)`` inside an operand's
#: printed shape is followed by neither
_HLO_OPERANDS_END = re.compile(r"\)(?:, [a-z_\-]+=|$)")
_HLO_REFERENCE = re.compile(r"%([\w.\-]+)")
#: the one fact more that three opcodes carry: a parameter's number, the
#: element a ``get-tuple-element`` takes, a custom call's target
_HLO_DETAIL = {
    "parameter": (re.compile(r"parameter\((\d+)\)"), int),
    "get-tuple-element": (re.compile(r", index=(\d+)"), int),
    "custom-call": (re.compile(r'custom_call_target="([^"]*)"'), str),
}
#: a loop's state is a tuple of hundreds of shapes: its beginning is kept
_SHAPE_KEPT = 64
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLEES = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)"
)
_HLO_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_SCOPE_NAME = re.compile(r"[a-z]+\.[a-z_]+")
#: a transformation's wrapper around one name of an ``op_name``, innermost
#: first: ``jvp(lane.gqa)``, ``vmap()``, then ``transpose(...)`` around it
_WRAPPED = re.compile(r"[\w.\-]*\([^()]*\)")
#: what ``jax.checkpoint`` calls the forward it computes again inside a
#: backward pass (jax 0.9.0: ``.../checkpoint/rematted_computation/...``)
_REMATTED = "rematted_computation"


class ProgramText(NamedTuple):
    """What :func:`device_phase_map` reads of one program's text, whatever
    the family of names it is asked for (:func:`parse_program_text`)."""

    #: ``jit_hpb_sweep`` of ``HloModule jit_hpb_sweep, ...``
    module: str
    #: the ``ENTRY`` computation's name
    entry: str
    #: ``{computation: [(instruction, its op_name or None, the
    #: computations it calls)]}``, names without their ``%``
    computations: Dict[str, List[Tuple[str, Optional[str], List[str]]]]
    #: ``{instruction: (opcode, the instructions it reads in the order of
    #: its operands, detail, shape)}``: what :func:`device_kind_map` and
    #: :func:`adopted_phase_map` read. ``detail`` is a ``parameter``'s
    #: number, the element a ``get-tuple-element`` takes, a
    #: ``custom-call``'s target, else ``None``; ``shape`` the first
    #: ``_SHAPE_KEPT`` characters of the result's shape and layout, for a
    #: table a person reads. An operand is found by its ``%``, as
    #: ``compiled.as_text()`` prints it
    instructions: Dict[str, Tuple[str, Tuple[str, ...], Any, str]]
    #: ``{computation: its ROOT instruction}``
    roots: Dict[str, str]


def _hlo_text(compiled: Any) -> str:
    """A compiled executable's optimized HLO, or the text itself."""
    return compiled if isinstance(compiled, str) else compiled.as_text()


def hlo_module_name(compiled: Any) -> str:
    """``jit_hpb_sweep`` of ``HloModule jit_hpb_sweep, ...``: the name a profiler
    trace's ``XLA Modules`` events carry (there followed by ``(<id>)``)."""
    match = _HLO_MODULE.match(_hlo_text(compiled))
    if match is None:
        raise ValueError("not the text of an HLO module")
    return match.group(1)


def parse_program_text(compiled: Any) -> ProgramText:
    """One pass over a compiled program's optimized HLO text
    (``compiled.as_text()``; the text itself is taken too): the
    computations' call graph, every instruction's ``op_name``, its opcode
    and the instructions it reads. None depends on a family of scope names,
    so one parse serves them all (``optimizers.sweep_phase_maps`` keeps it
    by executable). Seconds for a large program."""
    text = _hlo_text(compiled)
    computations: Dict[str, List[Tuple[str, Optional[str], List[str]]]] = {}
    instructions: Dict[str, Tuple[str, Tuple[str, ...], Any, str]] = {}
    roots: Dict[str, str] = {}
    shared: Dict[str, str] = {}     # one string a distinct name, op_name, opcode
    entry, current, computation = None, None, None
    for line in text.splitlines():
        header = _HLO_COMPUTATION.match(line)
        if header is not None:
            computation = header.group(2)
            current = computations.setdefault(computation, [])
            if header.group(1):
                entry = computation
            continue
        instruction = _HLO_INSTRUCTION.match(line)
        if instruction is None or current is None:
            continue
        name = instruction.group(2)
        name = shared.setdefault(name, name)
        if instruction.group(1):
            roots[computation] = name
        op_name = _HLO_OP_NAME.search(line)
        callees = _HLO_CALLEES.findall(line)
        for group in _HLO_BRANCHES.findall(line):
            callees += [c.strip().lstrip("%") for c in group.split(",")]
        current.append((
            name,
            shared.setdefault(op_name.group(1), op_name.group(1)) if op_name else None,
            callees,
        ))
        opcode = _HLO_OPCODE.search(line, instruction.end() - 1)
        if opcode is None:      # no line of the compiler's: nothing is known of it
            instructions[name] = ("", (), None, "")
            continue
        end = _HLO_OPERANDS_END.search(line, opcode.end())
        detail = None
        if opcode.group(1) in _HLO_DETAIL:
            pattern, convert = _HLO_DETAIL[opcode.group(1)]
            stated = pattern.search(line, opcode.start())
            detail = convert(stated.group(1)) if stated else None
        instructions[name] = (
            shared.setdefault(opcode.group(1), opcode.group(1)),
            tuple(shared.setdefault(r, r) for r in _HLO_REFERENCE.findall(
                line, opcode.end(), end.start() if end else len(line))),
            detail,
            line[instruction.end():min(opcode.start(), instruction.end() + _SHAPE_KEPT)],
        )
    if entry is None:
        raise ValueError("the compiled text has no ENTRY computation")
    return ProgramText(hlo_module_name(text), entry, computations, instructions, roots)


def _program(compiled: Any) -> ProgramText:
    """A compiled program's parse: made here, or the one handed in."""
    return (compiled if isinstance(compiled, ProgramText)
            else parse_program_text(compiled))


def _outside_wrappers(op_name: str) -> str:
    """``op_name`` without what its transformations wrap:
    ``pass.backward/transpose(pass.recompute)/jvp(lane.moe)/lane.moe/mul``
    -> ``pass.backward///lane.moe/mul``."""
    while True:
        stripped = _WRAPPED.sub("", op_name)
        if stripped == op_name:
            return stripped
        op_name = stripped


def device_phase_map(
    compiled: Any, scopes: Optional[Sequence[str]] = None
) -> Dict[str, str]:
    """``{instruction name: phase}`` of one compiled program, read off its
    optimized HLO text (``compiled.as_text()``; the text itself and a
    :class:`ProgramText` parsed before are taken too).

    A profiler trace prints device operations by the compiler's
    instruction names (``multiply_subtract_fusion.546``), which change with
    every edit and carry no metadata there. The same names are in the
    compiled text, each with the ``op_name`` its ``jax.named_scope`` left:
    an instruction's phase is the scope of
    :data:`~hpbandster_tpu.obs.timeline.DEVICE_SCOPES` found in it
    (``jit(sweep)/vmap(hpb.train)/while/body/...`` is ``hpb.train``; the
    scopes are flat, so there is at most one, and the last would win).
    ``scopes`` names another closed list to read by
    (:data:`~hpbandster_tpu.obs.timeline.LANE_SCOPES`, the parts of a
    lane; :data:`~hpbandster_tpu.obs.timeline.MOE_SCOPES`, the pieces of
    its expert layer); names of one list are not seen when reading by
    another. An instruction without one inside a nested computation — a
    loop's body, a fusion, a reducer — inherits its caller's. Instructions
    that stay without a phase are left out. Names lose their ``%``.

    **The passes** (:data:`~hpbandster_tpu.obs.timeline.PASS_SCOPES`) are
    read by a rule of their own: a name counts only where it stands outside
    every wrapper of the ``op_name``; of those, the last wins. A
    transformation writes the scope that was ambient where its primal was
    traced back into the name, wrapped: the backward rule of a
    ``custom_vjp`` traced under ``pass.recompute`` and pulled back under
    ``pass.backward`` is ``pass.backward/transpose(pass.recompute)/
    jvp(lane.moe)/lane.moe/dot_general``, a loop's backward pass
    ``pass.backward/transpose(jvp(lane.gqa))/vmap(pass.recompute)/...``:
    both are the backward pass's, where "the last name found" would say the
    recomputation's. A recomputation inside a backward rule stands outside
    (``pass.backward/.../lane.moe/pass.recompute/jvp()/...``) and wins, and
    so does what ``jax.checkpoint`` computes again in a backward pass
    (``.../checkpoint/rematted_computation/...``): ``pass.recompute``. A
    part keeps its rule: ``transpose(jvp(lane.gqa))`` is ``lane.gqa``'s.

    Parsing a large program's text takes seconds: call this on demand,
    never on a sweep's path."""
    from hpbandster_tpu.obs.timeline import DEVICE_SCOPES, PASS_SCOPES

    scopes = frozenset(DEVICE_SCOPES if scopes is None else scopes)
    by_pass = scopes == frozenset(PASS_SCOPES)
    program = _program(compiled)

    found: Dict[Optional[str], Optional[str]] = {None: None}

    def scope_of(op_name: str) -> Optional[str]:
        if by_pass:
            names = [
                "pass.recompute" if s == _REMATTED else s
                for s in _outside_wrappers(op_name).split("/")
                if s in scopes or s == _REMATTED
            ]
        else:
            names = [s for s in _SCOPE_NAME.findall(op_name) if s in scopes]
        return names[-1] if names else None

    phases: Dict[str, str] = {}
    inherited: Dict[str, Optional[str]] = {program.entry: None}
    queue = [program.entry]
    while queue:
        name = queue.pop()
        for instruction, op_name, callees in program.computations.get(name, ()):
            if op_name not in found:
                found[op_name] = scope_of(op_name)
            phase = found[op_name] or inherited[name]
            if phase is not None:
                phases[instruction] = phase
            for callee in callees:
                if callee not in inherited:
                    inherited[callee] = phase
                    queue.append(callee)
    return phases


# ------------------------------------------- kinds, and adoption by reader
#: what a fused computation holds beside its operations: a fusion's kind
#: leaves these out
_NO_OPERATION = frozenset((
    "parameter", "constant", "bitcast", "tuple", "get-tuple-element"))
_COPY_OPCODES = frozenset(("copy", "copy-start", "copy-done", "transpose"))
_CAST_SLICE_OPCODES = frozenset((
    "convert", "slice", "dynamic-slice", "slice-start", "slice-done",
    "dynamic-update-slice", "concatenate", "pad"))
_FILL_OPCODES = frozenset(("broadcast", "iota"))
#: a custom call into a Mosaic kernel: the Pallas kernels, and what the
#: chip's compiler makes of ``jax.lax.ragged_dot`` (``ragged-dot-none.3``
#: and its ``ragged-dot-metadata``); the compiler's bookkeeping calls
#: (``AllocateBuffer``, ``ConcatBitcast``) take no time and are no kernels
_KERNEL_TARGETS = frozenset(("tpu_custom_call",))
#: judged by the opcodes of the computation it wraps: a fusion, and the
#: beginning of an operation the chip runs beside others (``slice-start.3 =
#: async-start(...), calls=%async_computation.3``, a ``slice`` inside)
_WRAPPERS = frozenset(("fusion", "async-start"))
#: ... whose later steps (``slice-done.3 = async-done(%slice-start.3)``)
#: are of the kind of what they finish
_FINISHERS = frozenset(("async-update", "async-done"))
#: the compiler's own joining of buffers that lie side by side: a change of
#: layout that takes no time, booked with the copies so that the walk
#: below passes through it (ISSUE 52 books every call that is no kernel's
#: as ``compute``)
_LAYOUT_TARGETS = frozenset(("ConcatBitcast",))
#: how many instructions the walk of :func:`adopted_phase_map` may pass
#: before it gives up: a cast lifted out of two loops is eight away from
#: the product that reads it (tuple, while, parameter, element, twice)
_ADOPTION_DEPTH = 8


def device_kind_map(compiled: Any) -> Dict[str, str]:
    """``{instruction name: kind}`` of one compiled program (its text, or a
    :class:`ProgramText`), for every instruction one of
    :data:`~hpbandster_tpu.obs.timeline.OP_KINDS`, decided by opcode alone:

    * ``kernel``: a ``custom-call`` whose target is ``tpu_custom_call`` (a
      Pallas kernel, and what the chip's compiler makes of ``ragged_dot``);
    * ``copy``: ``copy``, ``copy-start`` / ``-done``, ``transpose``, the
      compiler's ``ConcatBitcast``, and a fusion of nothing else;
    * ``cast_slice``: ``convert``, ``slice``, ``dynamic-slice``,
      ``slice-start`` / ``-done``, ``dynamic-update-slice``,
      ``concatenate``, ``pad``, and a fusion of these and of copies alone;
    * ``fill``: a ``broadcast`` or ``iota``, or a fusion, none of whose
      operands is anything but a constant;
    * ``compute``: everything else.

    A fusion is judged by the opcodes of its fused computation, leaving out
    ``parameter``, ``constant``, ``bitcast``, ``tuple`` and
    ``get-tuple-element``; so is an ``async-start`` by the computation it
    wraps (on the chip a slice into the fast memory is ``slice-start.3 =
    async-start(...), calls=%async_computation.3``), and the ``async-done``
    that finishes it is of its kind."""
    program = _program(compiled)
    instructions = program.instructions
    both = _COPY_OPCODES | _CAST_SLICE_OPCODES

    def constants_alone(operands: Tuple[str, ...]) -> bool:
        return all(instructions.get(o, ("",))[0] == "constant" for o in operands)

    kinds: Dict[str, str] = {}
    for rows in program.computations.values():
        for name, _, callees in rows:
            opcode, operands, detail, _ = instructions[name]
            if opcode == "custom-call":
                kind = ("kernel" if detail in _KERNEL_TARGETS
                        else "copy" if detail in _LAYOUT_TARGETS else "compute")
            elif opcode in _WRAPPERS:
                inside = {
                    instructions[i][0]
                    for callee in callees
                    for i, _, _ in program.computations.get(callee, ())
                } - _NO_OPERATION
                kind = ("fill" if constants_alone(operands)
                        else "copy" if inside <= _COPY_OPCODES
                        else "cast_slice" if inside <= both else "compute")
            elif opcode in _FINISHERS:
                # begun by its operand, further up the same computation
                kind = kinds.get(operands[0] if operands else "", "compute")
            elif opcode in _COPY_OPCODES:
                kind = "copy"
            elif opcode in _CAST_SLICE_OPCODES:
                kind = "cast_slice"
            elif opcode in _FILL_OPCODES and constants_alone(operands):
                kind = "fill"
            else:
                kind = "compute"
            kinds[name] = kind
    return kinds


def adopted_phase_map(
    compiled: Any, scopes: Optional[Sequence[str]] = None
) -> Dict[str, str]:
    """``{instruction name: phase}`` for instructions that
    :func:`device_phase_map` leaves out of the same list of ``scopes``: an
    instruction with no phase by name takes the phase of what reads it.

    One rule. Walk from the instruction to its readers. A reader that has a
    phase by name ends its branch with that phase. One that has none is
    passed through if it only moves what it reads (a ``bitcast``, ``tuple``
    or ``get-tuple-element``, or an instruction of kind ``copy`` or
    ``cast_slice``: :func:`device_kind_map`), and crossed by operand
    position if it is a ``while``, ``call`` or ``conditional``: operand
    ``i`` of a call is the callee's ``parameter(i)``, operand ``j + 1`` of a
    conditional its branch ``j``'s parameter, a loop's state its body's and
    its condition's, and element ``k`` of the tuple stays element ``k``,
    also from the body's ROOT to the next turn's parameter. Any other
    reader ends its branch with nothing, and no branch is longer than
    ``_ADOPTION_DEPTH`` instructions. If every phase found is the same,
    that is the instruction's; if none was found, the same walk is made
    the other way, over what the instruction reads; if two were found, or
    nothing, the instruction stays out: an orphan. No vote, no weighting,
    and a name is never overridden: what :func:`device_phase_map` returns
    is not touched.

    Built on demand from the parse that serves every list; never on a
    sweep's path."""
    program = _program(compiled)
    return _adopted(program, device_phase_map(program, scopes), device_kind_map(program))


def _adopted(
    program: ProgramText, phases: Dict[str, str], kinds: Dict[str, str]
) -> Dict[str, str]:
    """:func:`adopted_phase_map` over maps made before."""
    instructions = program.instructions

    readers: Dict[str, List[Tuple[str, int]]] = {}
    parameters: Dict[Tuple[str, int], str] = {}
    home: Dict[str, str] = {}                   # a parameter's computation
    callees_of: Dict[str, List[str]] = {}       # of a while, call, conditional
    callers: Dict[str, List[str]] = {}          # the same, from the callee
    for computation, rows in program.computations.items():
        for name, _, callees in rows:
            opcode, operands, detail, _ = instructions[name]
            for position, operand in enumerate(operands):
                readers.setdefault(operand, []).append((name, position))
            if opcode == "parameter":
                parameters[computation, detail] = name
                home[name] = computation
            elif opcode in ("while", "call", "conditional"):
                callees_of[name] = callees
                for callee in callees:
                    callers.setdefault(callee, []).append(name)
    #: a loop body's ROOT tuple -> the body's parameter, the next turn's state
    next_turn = {
        program.roots[body]: parameters[body, 0]
        for loop, callees in callees_of.items() if instructions[loop][0] == "while"
        for body in callees if (body, 0) in parameters
        and instructions.get(program.roots.get(body), ("",))[0] == "tuple"}

    def passed(name: str) -> bool:
        return (instructions[name][0] == "bitcast"
                or kinds[name] in ("copy", "cast_slice"))

    def entered(reader: str, position: int) -> List[str]:
        """The parameters that stand for operand ``position`` of ``reader``
        in the computations it calls."""
        opcode, callees = instructions[reader][0], callees_of[reader]
        if opcode == "while":
            inside = [(callee, 0) for callee in callees]
        elif opcode == "call":
            inside = [(callee, position) for callee in callees[:1]]
        else:
            inside = [(callees[position - 1], 0)] if 0 < position <= len(callees) else []
        return [parameters[key] for key in inside if key in parameters]

    def read_by(name: str, element: Optional[int], depth: int, found: set) -> None:
        """The phases of the nearest named readers of ``name``, of its
        element ``element`` where it is a tuple that the walk built."""
        if depth == 0:
            return
        for reader, position in readers.get(name, ()):
            opcode, _, detail, _ = instructions[reader]
            if opcode == "get-tuple-element" and element not in (None, detail):
                continue    # another element of the tuple
            if reader in phases:
                found.add(phases[reader])
            elif reader in callees_of:
                for parameter in entered(reader, position):
                    read_by(parameter, element, depth - 1, found)
            elif opcode == "get-tuple-element":
                read_by(reader, None, depth - 1, found)
            elif element is not None:
                continue    # a tuple inside a tuple is followed no further
            elif reader in next_turn:
                read_by(next_turn[reader], position, depth - 1, found)
            elif opcode == "tuple":
                read_by(reader, position, depth - 1, found)
            elif passed(reader):
                read_by(reader, None, depth - 1, found)

    def handed(parameter: str) -> List[str]:
        """What the one caller of a parameter's computation hands it."""
        computation = home[parameter]
        entering = callers.get(computation, ())
        if len(entering) != 1:
            return []
        opcode, operands = instructions[entering[0]][:2]
        position = (instructions[parameter][2] if opcode == "call"
                    else 1 + callees_of[entering[0]].index(computation)
                    if opcode == "conditional" else 0)
        return list(operands[position:position + 1])

    def read_from(name: str, element: Optional[int], depth: int, found: set,
                  first: bool = False) -> None:
        """The same walk the other way: the phases of the nearest named
        instructions that ``name`` (its element ``element``) is made of."""
        if depth == 0:
            return
        if not first and name in phases:
            found.add(phases[name])
            return
        opcode, operands, detail, _ = instructions.get(name, ("", (), None, ""))
        if opcode == "get-tuple-element":
            sources = [(o, detail) for o in operands] if element is None else []
        elif opcode == "tuple":
            sources = ([(o, None) for o in operands[element:element + 1]]
                       if element is not None else
                       [(o, None) for o in operands] if first else [])
        elif opcode == "parameter":
            sources = [(o, element) for o in handed(name)]
        elif opcode == "while":
            # a loop's result: what its last turn left, and what it began with
            sources = [(o, element) for o in operands] + [
                (program.roots[body], element) for body in callees_of.get(name, ())
                if body in program.roots]
        elif element is None and (first or passed(name)):
            sources = [(o, None) for o in operands]
        else:
            sources = []
        for source, which in sources:
            read_from(source, which, depth - 1, found)

    adopted: Dict[str, str] = {}
    for name in instructions:
        if name in phases:
            continue
        found: set = set()
        read_by(name, None, _ADOPTION_DEPTH, found)
        if not found:
            read_from(name, None, _ADOPTION_DEPTH, found, first=True)
        if len(found) == 1:
            adopted[name] = found.pop()
    return adopted
