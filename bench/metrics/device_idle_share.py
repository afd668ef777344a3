"""device_idle_share: share of the traced window in which no operation ran on the
device, in % (1 - busy / window; ``bench.tracing``)."""

from bench import tracing


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace_span
    return 100.0 * (1.0 - tracing.busy_ns(ctx.trace, lo, hi) / (hi - lo))
