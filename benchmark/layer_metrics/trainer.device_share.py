"""Trainer: percent of the device's busy seconds in the program's phases
``hpb.train`` and ``hpb.validate``, from the trace joined with the
program's map from instruction to phase."""

import span_reduce


def read(ctx):
    return span_reduce.phase_share(span_reduce.of(ctx), "hpb.train", "hpb.validate")
