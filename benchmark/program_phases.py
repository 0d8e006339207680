"""Imports ``hpbandster_tpu``: the one call into the program for its phase
map, and nothing else.

The program names its device work by ``jax.named_scope`` (``hpb.train``,
``hpb.promote``, ...). A profiler trace prints the compiler's instruction
names without them; the compiled programs' own text has both, and the
program offers the join for every sweep executable the process holds.
"""


def phase_maps():
    """``{module name: {instruction name: phase}}``, or ``None`` from a
    program that has no such map (the commits before PR 25)."""
    try:
        from hpbandster_tpu.optimizers import sweep_phase_maps
    except ImportError:
        return None
    return sweep_phase_maps()
