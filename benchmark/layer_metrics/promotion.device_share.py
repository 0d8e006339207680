"""Bracket kernel: percent of the device's busy seconds in the program's
phases ``hpb.promote`` (rank key, masked top-k, gather of the survivors'
state) and ``hpb.obs_update`` (folding results into the buffers)."""

import span_reduce


def read(ctx):
    return span_reduce.phase_share(span_reduce.of(ctx), "hpb.promote", "hpb.obs_update")
