"""Percentiles over every request of a window.

A latency percentile is taken over all requests that were due in the window; a
request that failed or never came counts as slower than any that did (``inf``), so
failures can only raise a tail. Percentiles interpolate linearly between order
statistics (numpy's default method, as ``repro.serve.engine.ServeStats`` uses).
"""

from __future__ import annotations

import math

import numpy as np


def percentile(values, p: float) -> float:
    """The ``p``-th percentile (0..100) of ``values``; ``inf`` entries sort last."""
    a = np.sort(np.asarray(values, np.float64))
    if a.size == 0:
        raise ValueError("percentile of no values")
    pos = (a.size - 1) * p / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or a[lo] == a[hi]:
        return float(a[lo])
    if math.isinf(a[hi]):
        return math.inf
    return float(a[lo] + (a[hi] - a[lo]) * (pos - lo))
