"""Device: 1 - union of device-operation intervals over the traced window
(a few whole sweeps, construction included), averaged over the chips."""


def read(ctx):
    trace = ctx["trace"]
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
