"""From a profiler trace (``.xplane.pb``) to device busy time, kernel time and the
idle gaps, on plain Python records so that the reduction is tested on hand-made
traces.

  device plane   a plane named ``/device:TPU:<n>``; its operations are the events of
                 its ``XLA Ops`` line
  busy           the union of a device's operation intervals inside the window,
                 averaged over the devices
  op name        an operation's HLO instruction name: the trace names a TPU
                 operation by its whole HLO text (``%doc_score_fwd.3 = f32[...]
                 custom-call(...)``), of which the name is the part before " = "
  kernel time    the summed durations of the operations whose name, with the
                 ``.<n>`` suffix the compiler adds removed, is the kernel's name
  idle gap       an interval of the window in which no operation ran, labelled with
                 the innermost host event running at its middle (thread, enclosing
                 event > event), else the one that overlaps it most; events of the thread that
                 sends the load (the one holding the ``bench.window`` span) label a
                 gap only where no other thread's do
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"\.\d+$")


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


def load(path) -> list:
    """The planes of one ``.xplane.pb`` file, or of the newest one under a
    directory."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = max(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    data = ProfileData.from_file(str(path))
    planes = []
    for p in data.planes:
        lines = []
        for ln in p.lines:
            evs = [Event(e.name, float(e.start_ns), float(e.duration_ns),
                         {k: v for k, v in e.stats}) for e in ln.events]
            lines.append(Line(ln.name, evs))
        planes.append(Plane(p.name, lines))
    return planes


def is_device(plane: Plane) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", plane.name) is not None


def device_ops(plane: Plane) -> list:
    return [e for ln in plane.lines if ln.name == OPS_LINE for e in ln.events]


def op_name(name: str) -> str:
    """``%fusion.4 = s32[...] fusion(...)`` -> ``fusion.4``; a bare name stays."""
    return name.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    return _SUFFIX.sub("", op_name(name))


def union(intervals, lo: float = float("-inf"), hi: float = float("inf")) -> list:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_ns(planes) -> tuple:
    """(start, end) of the benchmark's ``bench.window`` span on the host."""
    for p in planes:
        if is_device(p):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name == WINDOW_SPAN:
                    return e.start_ns, e.end_ns
    raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")


def busy_ns(planes, lo: float, hi: float) -> float:
    """Busy time per device inside [lo, hi), averaged over the device planes."""
    devs = [p for p in planes if is_device(p)]
    if not devs:
        raise ValueError("the trace has no device plane")
    tot = [sum(e - s for s, e in union(((o.start_ns, o.end_ns) for o in device_ops(p)), lo, hi))
           for p in devs]
    return sum(tot) / len(tot)


def op_times_ns(planes, lo: float, hi: float) -> dict:
    """Summed duration per op name of the device operations that start in [lo, hi),
    over all devices and programs."""
    out: dict = {}
    for p in planes:
        if is_device(p):
            for o in device_ops(p):
                if lo <= o.start_ns < hi:
                    n = op_name(o.name)
                    out[n] = out.get(n, 0.0) + o.dur_ns
    return out


def kernel_ns(planes, names, lo: float, hi: float):
    """Summed device time of the operations whose base name is in ``names``; None
    where the trace holds none of them."""
    hit = [t for n, t in op_times_ns(planes, lo, hi).items() if base_name(n) in names]
    return sum(hit) if hit else None


def idle_gaps(planes, lo: float, hi: float, top: int = 10) -> list:
    """The ``top`` longest idle intervals of the first device inside [lo, hi), as
    [label, seconds], longest first."""
    import numpy as np

    dev = next(p for p in planes if is_device(p))
    busy = union(((o.start_ns, o.end_ns) for o in device_ops(dev)), lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    lines = [(i, ln) for p in planes if not is_device(p) for i, ln in enumerate(p.lines)]
    sender = {id(ln) for _, ln in lines if any(e.name == WINDOW_SPAN for e in ln.events)}
    host = [(f"{ln.name}[{i}]", id(ln) not in sender, e) for i, ln in lines
            for e in ln.events if e.name != WINDOW_SPAN]
    starts = np.array([e.start_ns for _, _, e in host])
    ends = np.array([e.end_ns for _, _, e in host])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        best, best_key = None, None
        for j in np.flatnonzero((starts < e) & (ends > s)):
            thread, other, ev = host[j]
            covers = ev.start_ns <= mid < ev.end_ns
            ov = min(e, ev.end_ns) - max(s, ev.start_ns)
            key = (other, covers, -ev.dur_ns if covers else ov)
            if best_key is None or key > best_key:
                best, best_key = (thread, ev), key
        out.append([_label(host, starts, ends, *best) if best else "no host event",
                    (e - s) / 1e9])
    return out


def _label(host, starts, ends, thread: str, ev: Event) -> str:
    """``thread: parent > event``, the parent being the shortest event of the same
    thread that encloses ``ev``."""
    import numpy as np

    outer = [host[j][2] for j in np.flatnonzero((starts <= ev.start_ns) & (ends >= ev.end_ns))
             if host[j][0] == thread and host[j][2] is not ev and host[j][2].dur_ns > ev.dur_ns]
    if not outer:
        return f"{thread}: {ev.name}"
    return f"{thread}: {min(outer, key=lambda o: o.dur_ns).name} > {ev.name}"


def top_ops(planes, lo: float, hi: float, top: int = 10) -> list:
    """The ``top`` device operations by summed time, as [name, seconds]."""
    times = op_times_ns(planes, lo, hi)
    return [[n, t / 1e9] for n, t in sorted(times.items(), key=lambda x: -x[1])[:top]]
