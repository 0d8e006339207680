"""Imports ``hpbandster_tpu``: the calls into the program for what it says
of one lane's parts, and nothing else.

A workload whose lane has layers of several kinds names them by
``jax.named_scope`` inside the trainer (``lane.kda``, ``lane.moe``, ...) and
the program offers the same join as for its phases (``program_phases.py``)
under that second list of names. What a lane counts on the device beside
its loss, the program publishes as gauges ``sweep.lane.<name>`` after every
sweep. A program that has neither (the commits before PR 28) gives ``None``.
"""

GAUGE_PREFIX = "sweep.lane."


def lane_maps():
    """``{module name: {instruction name: lane part}}`` or ``None``."""
    try:
        from hpbandster_tpu.obs.timeline import LANE_SCOPES
        from hpbandster_tpu.optimizers import sweep_phase_maps
    except ImportError:
        return None
    return sweep_phase_maps(LANE_SCOPES)


def lane_gauges():
    """``{name: value}`` of the last sweep's lane accounting, or ``None``."""
    try:
        from hpbandster_tpu.obs import get_metrics
    except ImportError:
        return None
    gauges = get_metrics().snapshot()["gauges"]
    found = {name[len(GAUGE_PREFIX):]: value for name, value in gauges.items()
             if name.startswith(GAUGE_PREFIX)}
    return found or None
