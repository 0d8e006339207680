"""Imports ``hpbandster_tpu``: the calls into the program for what it says
of a lane's passes and of its expert layer's pieces, and for the two
halves of a build; nothing else.

Beside its parts (``program_lane_parts.py``) the trainer names the pass an
instruction belongs to (``pass.forward``, ``pass.recompute``,
``pass.backward``: ``obs.timeline.PASS_SCOPES``) and the expert layer its
pieces (``moe.router`` ... ``moe.shared``: ``MOE_SCOPES``), and the program
offers the same join under each list; it reads an executable's text once
for all of them. What building a sweep's program took, it adds to two
gauges, ``sweep.build.trace_lower_s`` (Python tracing and lowering) and
``sweep.build.compile_s`` (the compiler, or the compile cache's load). A
program that has none of this (the commits before PR 38) gives ``None``.
"""

FAMILIES = {"passes": "PASS_SCOPES", "pieces": "MOE_SCOPES"}
BUILD_GAUGE_PREFIX = "sweep.build."


def family_maps(family):
    """``{module name: {instruction name: name of the family}}`` for
    ``family`` one of :data:`FAMILIES`, or ``None`` where the program has no
    such list or none of its executables names any of it."""
    try:
        from hpbandster_tpu.obs import timeline
        from hpbandster_tpu.optimizers import sweep_phase_maps
    except ImportError:
        return None
    scopes = getattr(timeline, FAMILIES[family], None)
    if scopes is None:
        return None
    return sweep_phase_maps(scopes) or None


def build_gauges():
    """``{"trace_lower_s": seconds, "compile_s": seconds}`` summed over the
    sweep programs this process built, or ``None``."""
    try:
        from hpbandster_tpu.obs import get_metrics
    except ImportError:
        return None
    gauges = get_metrics().snapshot()["gauges"]
    found = {name[len(BUILD_GAUGE_PREFIX):]: value for name, value in gauges.items()
             if name.startswith(BUILD_GAUGE_PREFIX)}
    return found or None
