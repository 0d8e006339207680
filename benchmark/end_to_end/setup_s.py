"""Process start to the end of the second warm-up sweep: what every cold
start costs."""


def read(ctx):
    return ctx["setup_s"]
