"""obs-emit-in-jit — event emission inside traced JAX code.

``hpbandster_tpu.obs`` emission (``emit``/``span``/``get_bus().emit``) is
host work: it reads host clocks, takes host locks, and may write files.
Inside a ``jit``/``vmap``/``pmap``-ed body it either runs once at TRACE
time (the event fires at compile, silently never again — telemetry that
lies) or, under callback-style escapes, forces a host round-trip per
device step. The supported pattern is emitting AROUND the jit boundary:
the caller opens a span, the traced function stays pure (exactly how
``parallel/batched_worker.py`` wraps ``backend.evaluate``).

Detection reuses jit-host-sync's traced-function discovery (decorated
with, or passed into, a jit/vmap/pmap wrapper in this module). Inside a
traced body it flags:

* calls resolving through the import map into ``hpbandster_tpu.obs``
  (``emit(...)``, ``span(...)``, ``obs.emit(...)``, the timeline span
  API ``phase_span(...)``/``sweep_span(...)``/``mark(...)``, aliased
  imports);
* ``.emit(...)``, ``.phase_span(...)``, ``.sweep_span(...)`` and
  ``.mark(...)`` method calls —
  including on the result of ``get_bus()`` — but only in modules that
  import ``hpbandster_tpu.obs`` at all, so unrelated APIs elsewhere
  stay unflagged.
"""

from __future__ import annotations

import ast
from typing import List

from hpbandster_tpu.analysis.core import Finding, Rule, SourceModule, register
from hpbandster_tpu.analysis.rules._util import ImportMap, import_map_for
from hpbandster_tpu.analysis.rules.jit_purity import traced_functions_for

_OBS_PREFIX = "hpbandster_tpu.obs"

#: emission-shaped attribute calls flagged in obs-importing modules:
#: the bus API (``.emit``) and the timeline span API
#: (``obs/timeline.py`` ``phase_span``/``sweep_span``/``mark``) — both
#: are host clock reads + sink dispatch, equally wrong inside a traced body
_EMIT_ATTRS = frozenset({"emit", "phase_span", "sweep_span", "mark"})


def _module_imports_obs(imports: ImportMap) -> bool:
    return any(v.startswith(_OBS_PREFIX) or v == "hpbandster_tpu"
               for v in imports.aliases.values())


def _resolves_to_obs(node: ast.expr, imports: ImportMap) -> bool:
    resolved = imports.resolve(node) or ""
    # `from hpbandster_tpu import obs` resolves `obs.emit` to
    # "hpbandster_tpu.obs.emit"; `from hpbandster_tpu.obs import emit`
    # resolves `emit` to "hpbandster_tpu.obs.emit"
    return resolved.startswith(_OBS_PREFIX)


@register
class ObsEmitInJitRule(Rule):
    name = "obs-emit-in-jit"
    description = (
        "obs event emission (emit/span/bus.emit or the timeline span API "
        "phase_span/mark) inside a jit/vmap/pmap-ed body — fires at trace "
        "time, not per execution; emit around the jit boundary instead"
    )

    def check(self, module: SourceModule) -> List[Finding]:
        # sound prefilter: both a trace wrapper and an obs mention required
        if "obs" not in module.text or not any(
            t in module.text for t in ("jit", "pmap", "vmap", "vectorize")
        ):
            return []
        imports = import_map_for(module)
        imports_obs = _module_imports_obs(imports)
        findings: List[Finding] = []
        for fn in traced_functions_for(module):
            for node in module.subtree(fn):
                if not isinstance(node, ast.Call):
                    continue
                if _resolves_to_obs(node.func, imports):
                    what = ast.unparse(node.func)
                    findings.append(self._flag(module, node, fn, what))
                elif (
                    imports_obs
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _EMIT_ATTRS
                ):
                    findings.append(
                        self._flag(module, node, fn, f".{node.func.attr}()")
                    )
        return findings

    def _flag(
        self, module: SourceModule, node: ast.Call, fn: ast.FunctionDef, what: str
    ) -> Finding:
        return self.finding(
            module, node,
            f"{what} inside traced function {fn.name!r} runs at trace time "
            "(once per compile), not per execution — move the emission "
            "outside the jit boundary",
        )
