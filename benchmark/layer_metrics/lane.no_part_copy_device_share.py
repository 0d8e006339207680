"""Lane: percent of the device's busy seconds in no part of the lane, in an
instruction the compiler made itself (no ``op_name``) of kind ``copy``: a
copy, a change of layout, a move into the fast memory. One of the five
shares that add up to ``lane.no_part_device_share`` (``lane_kinds.py``)."""

import lane_kinds


def read(ctx):
    return lane_kinds.no_part_share(ctx, "copy")
