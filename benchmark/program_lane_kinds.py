"""Imports ``hpbandster_tpu``: the one call into the program for what it
says of every instruction of its sweep executables, and nothing else.

Beside the maps from instruction to lane part, pass and piece
(``program_lane_parts.py``, ``program_lane_pieces.py``) the program says of
each instruction its opcode, what kind of operation that is (``kernel``,
``copy``, ``cast_slice``, ``fill``, ``compute``: ``obs.timeline.OP_KINDS``),
whether its own line carries an ``op_name``, and, where it lies in no lane
part by name, the part of what reads it (``adopted``, or ``None``: an
orphan). It reads nothing that the maps have not read already. A program
that has none of this (the commits before PR 52) gives ``None``.
"""


def instruction_facts():
    """``{module name: {instruction name: {"opcode", "kind", "named",
    "adopted", ...}}}`` read by the lane's parts, or ``None`` where the
    program offers no such call or none of its executables names a part."""
    try:
        from hpbandster_tpu.obs.timeline import LANE_SCOPES
        from hpbandster_tpu.optimizers import sweep_instruction_facts
    except ImportError:
        return None
    return sweep_instruction_facts(LANE_SCOPES) or None
