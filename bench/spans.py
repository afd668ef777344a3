"""The device's idle time split by what the serving engine's worker was doing, from
the program's own spans (``serve/engine.py``) on the clock of the device trace.

  idle inside   device time inside [lo, hi) in which no operation ran and at least
                one host event named in ``names`` was open, on any thread (their
                intervals merged first), averaged over the device planes as
                ``tracing.busy_ns`` is
  parts         ``collect``: the worker waiting for requests and the batching
                window (``serve.collect``); ``host``: its host work on a batch
                (``serve.pad``, ``serve.dispatch``, ``serve.resolve``); ``fetch``: a
                batch's outputs copied to the host (``serve.fetch``);
                ``unattributed``: in none of these. One worker runs its stages one
                at a time, so the four parts sum to the idle time
"""

from __future__ import annotations

from bench.tracing import busy_ns, device_ops, is_device, union

COLLECT = frozenset({"serve.collect"})
HOST = frozenset({"serve.pad", "serve.dispatch", "serve.resolve"})
FETCH = frozenset({"serve.fetch"})


def _host_intervals(planes, names) -> list:
    return [(e.start_ns, e.end_ns) for p in planes if not is_device(p)
            for ln in p.lines for e in ln.events if e.name in names]


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_inside(planes, lo: float, hi: float, names) -> float:
    """Device idle time inside [lo, hi) while a host event named in ``names`` was
    open, averaged over the device planes."""
    devs = [p for p in planes if is_device(p)]
    if not devs:
        raise ValueError("the trace has no device plane")
    spans = union(_host_intervals(planes, names), lo, hi)
    open_ns = sum(e - s for s, e in spans)
    tot = [open_ns - _overlap(spans, union(((o.start_ns, o.end_ns) for o in device_ops(p)),
                                           lo, hi))
           for p in devs]
    return sum(tot) / len(tot)


def idle_share(planes, lo: float, hi: float, names):
    """``idle_inside`` as a share of the window, in %; None where the trace holds no
    event named in ``names`` (a program without the spans)."""
    if not _host_intervals(planes, names):
        return None
    return 100.0 * idle_inside(planes, lo, hi, names) / (hi - lo)


def idle_parts(planes, lo: float, hi: float) -> dict:
    """The idle time inside [lo, hi), in ns averaged over the devices, by part."""
    idle = (hi - lo) - busy_ns(planes, lo, hi)
    parts = {"collect": idle_inside(planes, lo, hi, COLLECT),
             "host": idle_inside(planes, lo, hi, HOST),
             "fetch": idle_inside(planes, lo, hi, FETCH)}
    parts["unattributed"] = idle - idle_inside(planes, lo, hi, COLLECT | HOST | FETCH)
    return parts
