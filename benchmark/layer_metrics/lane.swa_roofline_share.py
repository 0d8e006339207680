"""Lane: the window layers' attention against its roofline while it runs:
the least seconds the chip could take for the traced sweeps' window-layer
mixers (``lane_counts_mellum2.py``: four projections and the band's pairs
exactly, ``W S - W (W - 1) / 2`` a head; compute-bound at these shapes) over
the device's busy seconds in ``lane.swa``. What a kernel for banded
attention would be judged by."""

import lane_counts_mellum2


def read(ctx):
    return lane_counts_mellum2.roofline_share(ctx, "swa")
