"""Lane: percent of the device's busy seconds in no part of the lane though
the instruction has an ``op_name``: the program's own code outside every
lane scope (the scalings of the held draw, the sweep's phases). A scope can
name it. One of the five shares that add up to
``lane.no_part_device_share`` (``lane_kinds.py``)."""

import lane_kinds


def read(ctx):
    return lane_kinds.no_part_share(ctx, "named")
