"""Host replay: seconds in ``hpb:replay.runs`` (a ``Job``, the journal
record, the result logger and ``register_result`` for every run of a
bracket) per 1,000 evaluations of the schedule, over the traced sweeps."""

import span_reduce


def read(ctx):
    return span_reduce.per_keval(ctx, "replay.runs")
