"""Device: ``memory_stats()["peak_bytes_in_use"]`` after the window, the
highest over the cell's chips."""


def read(ctx):
    return ctx["memory_peak_bytes"]
