"""Share of the traced window in which no operation ran on the device: 1 -
the union of the device's op intervals over the window, averaged over the
chips used.  Moves ``tokens_per_s``."""

from lib import trace


def read(ctx):
    lo, hi = ctx.trace.window
    busy = sum(trace.busy_ns(ctx.trace.ops[p], lo, hi) for p in ctx.planes)
    return 100.0 * (1.0 - busy / len(ctx.planes) / (hi - lo))
