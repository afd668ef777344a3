"""p99_ms: 99th percentile latency of every request due in the window, from the time
it was due to the time its answer arrived; a failed request counts as slower than
all (it reads as the longest wait a run allows, window plus grace)."""

import math

from bench.drive import GRACE_S
from bench.stats import percentile


def read(ctx):
    v = percentile(ctx.window.latency_ms(), 99)
    return v if math.isfinite(v) else (ctx.window.seconds + GRACE_S) * 1e3
