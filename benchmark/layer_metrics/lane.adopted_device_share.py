"""Lane: percent of the device's busy seconds in instructions that have no
``op_name``, lie in no part of the lane by name, and are read by one part
alone (the program's ``adopted``: a cast lifted out of a loop is its
mixer's). What the parts' own shares are short of; the rest of the nameless
seconds are orphans (``lane_kinds.py`` prints both)."""

import lane_kinds


def read(ctx):
    return lane_kinds.adopted_share(ctx)
