"""The window driver's stall watch: a sender held up past ``STALL_S`` leaves every
thread's stack in the stall log, and a window without a stall leaves it empty."""

import time
from concurrent.futures import Future

import numpy as np

from bench import drive


class _Engine:
    """Answers at once; ``search`` blocks for ``hold_s`` on request ``hold_at``."""

    def __init__(self, hold_at=-1, hold_s=0.0):
        self.n, self.hold_at, self.hold_s = 0, hold_at, hold_s

    def search(self, request):
        if self.n == self.hold_at:
            time.sleep(self.hold_s)
        self.n += 1
        fut = Future()
        fut.set_result(request)
        return fut


def _window(engine, log):
    offsets = np.linspace(0.0, 0.4, 5)
    return drive.open_loop(engine, list(range(5)), offsets, 0.5, log)


def test_stalled_sender_dumps_every_thread(tmp_path):
    log = tmp_path / "stall.txt"
    win = _window(_Engine(hold_at=2, hold_s=drive.STALL_S + 0.4), log)
    assert win.late_s.max() > drive.STALL_S
    text = log.read_text()
    assert "Thread" in text and "test_drive.py" in text


def test_steady_sender_leaves_no_dump(tmp_path):
    log = tmp_path / "stall.txt"
    win = _window(_Engine(), log)
    assert win.late_s.max() < drive.STALL_S and log.read_text() == ""
    assert all(r is not None for r in win.responses)
