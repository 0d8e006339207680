"""Compile cache: entries in the cache directory after the run minus
before; a run that found every program adds none."""


def read(ctx):
    return ctx["cache_new_entries"]
