"""jit-donation — sharded jit call sites must take an explicit donation
stance.

``jax.jit`` / ``tracked_jit`` call sites that pass ``in_shardings`` /
``out_shardings`` are, by construction, the repo's LARGE-buffer program
boundaries: sharding only exists because the arrays are big enough to
spread over a mesh. Exactly there, buffer donation is the difference
between XLA updating state in place (the fused sweep's warm-buffer
thread, ops/sweep.py) and a dead copy round-tripping the host link — the
compile/transfer tax the runtime telemetry (PR 5) measures and the
ceilings of ``tests/test_program_counts.py`` enforce.

Donation is not always RIGHT, though: a buffer whose outputs cannot alias
it (shape/dtype mismatch) gains nothing, and donating a caller-reused
array is a correctness bug. So the rule does not demand donation — it
demands a DECISION: every sharded jit call site must carry an explicit
``donate_argnums=`` / ``donate_argnames=`` keyword. ``donate_argnums=()``
is a valid stance ("considered, declined" — pair it with a rationale
comment, see docs/perf_notes.md "Buffer donation contract"). A ``**kwargs``
splat passes too (the decision lives wherever the dict is built — static
analysis cannot see into it).

``pjit`` call sites are sharded BY CONSTRUCTION (the mesh-sharded sweep
arc's pjit/NamedSharding pattern): a call resolving to
``jax.experimental.pjit.pjit`` is flagged even without a spelled
sharding kwarg; a bare ``pjit`` name (no jax import in the module — a
local helper) is only flagged when it passes a sharding kwarg, like the
other bare wrapper names.

Not flagged:

* unsharded jit sites — small/host-shaped programs where the donation
  question is usually moot (and the noise would drown the signal);
* ``jax.vmap``/transform calls — no compile boundary, nothing to donate.
"""

from __future__ import annotations

import ast
from typing import List

from hpbandster_tpu.analysis.core import Finding, Rule, SourceModule, register
from hpbandster_tpu.analysis.rules._util import ImportMap, import_map_for

#: wrappers that compile device programs and accept donate_argnums
_JIT_WRAPPERS = {
    "jax.jit",
    "jit",
    "jax.pmap",
    "pmap",
    "tracked_jit",
    "hpbandster_tpu.obs.tracked_jit",
    "hpbandster_tpu.obs.runtime.tracked_jit",
    # bare pjit: only flagged when it spells a sharding kwarg (the
    # unconditional pjit check lives in _SHARDED_WRAPPERS and requires
    # the fully-qualified jax import)
    "pjit",
}

#: wrappers that are sharded BY CONSTRUCTION — a pjit site is a
#: large-buffer program boundary whether or not it spells a sharding
#: kwarg (the mesh-sharded sweep arc's pjit/NamedSharding pattern), so
#: the donation stance is demanded unconditionally there. Fully-qualified
#: ONLY: a bare `pjit` that resolves to no jax import is a module-local
#: name (ImportMap returns the head unchanged then) — flagging it would
#: report any local helper named pjit as a jax boundary. A bare-named
#: genuine pjit call still gets the kwarg-triggered check via
#: _JIT_WRAPPERS below.
_SHARDED_WRAPPERS = {
    "jax.experimental.pjit.pjit",
}

_SHARDING_KWARGS = {"in_shardings", "out_shardings"}
_DONATION_KWARGS = {"donate_argnums", "donate_argnames"}


@register
class JitDonationRule(Rule):
    name = "jit-donation"
    description = (
        "sharded jit call site (in_shardings/out_shardings) without an "
        "explicit donate_argnums/donate_argnames — large-buffer program "
        "boundaries must take a donation stance (donate_argnums=() = "
        "considered and declined)"
    )

    def check(self, module: SourceModule) -> List[Finding]:
        # sound prefilter: a flaggable call must spell a sharding kwarg or
        # name a sharded-by-construction wrapper
        if not (
            any(t in module.text for t in _SHARDING_KWARGS)
            or "pjit" in module.text
        ):
            return []
        imports = import_map_for(module)
        findings: List[Finding] = []
        for node in module.walk():
            if not isinstance(node, ast.Call):
                continue
            resolved = imports.resolve(node.func) or ""
            always_sharded = resolved in _SHARDED_WRAPPERS
            if resolved not in _JIT_WRAPPERS and not always_sharded:
                continue
            kw_names = {kw.arg for kw in node.keywords if kw.arg is not None}
            if not always_sharded and not (kw_names & _SHARDING_KWARGS):
                continue
            if kw_names & _DONATION_KWARGS:
                continue
            if any(kw.arg is None for kw in node.keywords):
                # **splat: the decision may live in the dict — unanalyzable,
                # treated as an explicit stance
                continue
            via = (
                f"passes {sorted(kw_names & _SHARDING_KWARGS)}"
                if kw_names & _SHARDING_KWARGS
                else "is a pjit boundary (sharded by construction)"
            )
            findings.append(
                self.finding(
                    module, node,
                    f"{resolved}(...) {via} but no "
                    "donate_argnums/donate_argnames — sharded call sites "
                    "move large buffers; state the donation decision "
                    "explicitly (donate_argnums=() with a rationale "
                    "comment to decline)",
                )
            )
        return findings
