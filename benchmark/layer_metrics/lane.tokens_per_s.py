"""Lane: training tokens a second over the window: the schedule's lane-steps
(a stateless evaluation trains its whole budget) times the tokens of a step,
times the window's sweeps, over window seconds and chips."""

import lane_counts


def read(ctx):
    steps, _ = lane_counts.schedule_passes(ctx["plans"])
    tokens = steps * ctx["config"]["train"]["seq_len"] * len(ctx["sweeps"])
    return tokens / ctx["window_s"] / ctx["chips"]
