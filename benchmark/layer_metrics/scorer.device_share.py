"""Model: device time of the Pallas scorer's kernel over device busy time,
from the trace. The kernel is found by the name the trace prints for it,
that of its function in ``ops/pallas_kde.py``."""

KERNEL = "_logpdf_padded"


def read(ctx):
    trace = ctx["trace"]
    kernel_s = sum(s for name, s in trace["op_s"].items() if name.startswith(KERNEL))
    return 100.0 * kernel_s / trace["busy_s"]
