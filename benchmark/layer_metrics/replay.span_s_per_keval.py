"""Host replay: seconds of the program's spans after the fetch
(``hpb:chunk_accounting``, ``hpb:obs_fold``, ``hpb:bracket_replay``,
``hpb:result``) per 1,000 evaluations of the schedule, over the traced
sweeps. Unlike ``replay.host_s_per_keval`` it holds no construction."""

import span_reduce

SPANS = ("chunk_accounting", "obs_fold", "bracket_replay", "result")


def read(ctx):
    return span_reduce.per_keval(ctx, *SPANS)
