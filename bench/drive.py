"""The measured window: requests sent to a ``RetrievalEngine`` and their answers.

Open loop: request i is due ``offsets[i]`` seconds after the window opens and is
sent then (or as soon as the sender can, if it runs late); its latency runs from the
time it was due to the time its future resolved, so a stall also delays the requests
queued behind it. Closed loop: each client sends its next query from the stream as
soon as its previous answer arrives. Either way, when the window closes no more
requests are sent, and those in flight are waited for, up to ``GRACE_S``; one that
does not come by then has failed.

A stall watch (``faulthandler``'s watchdog, which runs in C without the interpreter
lock) writes the stack of every thread to a file when the sender is more than
``STALL_S`` late, or a closed loop waits ``CLOSED_STALL_S`` for an answer: a
diagnostic of stalls in the window, read after it, never a metric.
"""

from __future__ import annotations

import faulthandler
import os
import queue
import threading
import time
from dataclasses import dataclass, field

import numpy as np

GRACE_S = 60.0
STALL_S = 0.5
CLOSED_STALL_S = 2.0
_STALL_LOG_MAX = 64 << 10  # stop dumping once the file holds this many bytes


class _StallWatch:
    def __init__(self, path):
        self.f = None
        if path is not None:
            path.parent.mkdir(parents=True, exist_ok=True)
            self.f = open(path, "w")

    def arm(self, seconds: float) -> None:
        """Dump every thread's stack unless re-armed within ``seconds``."""
        if self.f is not None and os.fstat(self.f.fileno()).st_size < _STALL_LOG_MAX:
            faulthandler.dump_traceback_later(seconds, file=self.f)

    def close(self) -> None:
        if self.f is not None:
            faulthandler.cancel_dump_traceback_later()
            self.f.close()


@dataclass
class Window:
    """What happened in one window. Times are ``time.monotonic()`` seconds."""

    t0: float
    seconds: float
    due: np.ndarray  # float64 [n] when each stream request was due (nan: never sent)
    done: np.ndarray  # float64 [n] when its future resolved (nan: not resolved)
    responses: list  # SearchResponse or None, by stream position
    errors: list = field(default_factory=list)  # (position, repr) of failed futures
    late_s: np.ndarray = None  # send time - due time, per request sent

    @property
    def sent(self) -> np.ndarray:
        return ~np.isnan(self.due)

    def latency_ms(self) -> np.ndarray:
        """Per request sent: due -> resolved, inf where it failed or never came."""
        ok = np.array([r is not None for r in self.responses]) & ~np.isnan(self.done)
        lat = np.where(ok, (self.done - self.due) * 1e3, np.inf)
        return lat[self.sent]

    def finished_in_window(self) -> np.ndarray:
        return ~np.isnan(self.done) & (self.done <= self.t0 + self.seconds)


class _Recorder:
    def __init__(self, n: int):
        self.due = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.responses = [None] * n
        self.errors = []
        self.lock = threading.Lock()
        self.pending = 0
        self.idle = threading.Condition(self.lock)

    def watch(self, i: int, fut, on_done=None) -> None:
        with self.lock:
            self.pending += 1

        def cb(f, i=i):
            t = time.monotonic()
            try:
                r = f.result()
            except BaseException as e:  # noqa: BLE001 - every failure is recorded
                r = None
                err = repr(e)
            with self.lock:
                self.done[i] = t
                self.responses[i] = r
                if r is None:
                    self.errors.append((i, err))
                self.pending -= 1
                if self.pending == 0:
                    self.idle.notify_all()
            if on_done is not None:
                on_done(i)

        fut.add_done_callback(cb)

    def wait_all(self, timeout: float) -> None:
        end = time.monotonic() + timeout
        with self.lock:
            while self.pending and time.monotonic() < end:
                self.idle.wait(timeout=max(0.0, end - time.monotonic()))


def _send(engine, request, rec: _Recorder, i: int, on_done=None) -> None:
    try:
        fut = engine.search(request)
    except Exception as e:  # noqa: BLE001 - a refused request is a failed one
        with rec.lock:
            rec.errors.append((i, repr(e)))
        return
    rec.watch(i, fut, on_done)


def open_loop(engine, requests: list, offsets: np.ndarray, seconds: float,
              stall_log=None) -> Window:
    rec = _Recorder(len(requests))
    late = []
    watch = _StallWatch(stall_log)
    t0 = time.monotonic()
    try:
        for i, (req, off) in enumerate(zip(requests, offsets)):
            due = t0 + off
            now = time.monotonic()
            watch.arm(max(due - now, 0.0) + STALL_S)
            if due > now:
                time.sleep(due - now)
                now = time.monotonic()
            rec.due[i] = due
            late.append(now - due)
            _send(engine, req, rec, i)
    finally:
        watch.close()
    close = t0 + seconds
    if time.monotonic() < close:
        time.sleep(close - time.monotonic())
    rec.wait_all(max(0.0, close + GRACE_S - time.monotonic()))
    return Window(t0, seconds, rec.due, rec.done, list(rec.responses), list(rec.errors),
                  np.asarray(late))


def closed_loop(engine, requests: list, clients: int, seconds: float,
                stall_log=None) -> Window:
    rec = _Recorder(len(requests))
    watch = _StallWatch(stall_log)
    free: queue.SimpleQueue = queue.SimpleQueue()
    t0 = time.monotonic()
    close = t0 + seconds
    try:
        nxt = 0
        for _ in range(min(clients, len(requests))):
            rec.due[nxt] = time.monotonic()
            _send(engine, requests[nxt], rec, nxt, free.put)
            nxt += 1
        while True:
            left = close - time.monotonic()
            if left <= 0:
                break
            watch.arm(CLOSED_STALL_S)
            try:
                free.get(timeout=left)
            except queue.Empty:
                break
            if time.monotonic() >= close:
                break
            if nxt == len(requests):
                raise RuntimeError(f"the closed loop used up its {len(requests)} queries before "
                                   f"the window closed; raise the mix's pool_per_second")
            rec.due[nxt] = time.monotonic()
            _send(engine, requests[nxt], rec, nxt, free.put)
            nxt += 1
    finally:
        watch.close()
    rec.wait_all(max(0.0, close + GRACE_S - time.monotonic()))
    return Window(t0, seconds, rec.due, rec.done, list(rec.responses), list(rec.errors),
                  np.zeros(0))
