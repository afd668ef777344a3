"""The split of the device's idle time by the engine's spans, on small hand-made
traces, and the readers of the engine layer's span and counter metrics."""

from types import SimpleNamespace

import pytest

from bench import spans, spec, tracing
from bench.tracing import Event, Line, Plane


def _trace():
    # window 0..100 ns; device 0 runs ops at [10,30) [60,70), device 1 at [0,50).
    # The worker collects (from before the window) to 12, then serves one batch to
    # 80 (pad, dispatch, fetch, resolve), collects again to 90 and is in no stage
    # after; a caller thread is admitted at [5,8)
    dev0 = Plane("/device:TPU:0", [Line("XLA Ops", [Event("%fusion.1 = f32[8] fusion()", 10, 20),
                                                   Event("%sbmax.1 = f32[8] custom-call()", 60, 10)])])
    dev1 = Plane("/device:TPU:1", [Line("XLA Ops", [Event("doc_score_flat", 0, 50)])])
    worker = Line("python3", [
        Event("serve.collect", -20, 32), Event("serve.batch", 12, 68, {"batch_id": 0}),
        Event("serve.pad", 12, 3), Event("serve.dispatch", 15, 5), Event("serve.fetch", 20, 45),
        Event("$numpy asarray", 21, 40), Event("serve.resolve", 65, 15),
        Event("serve.collect", 80, 10)])
    caller = Line("python3", [Event("bench.window", 0, 100), Event("serve.admit", 5, 3)])
    return [Plane("/host:CPU", [caller, worker]), dev0, dev1]


def _only(ops, host):
    return [Plane("/host:CPU", [Line("python3", [Event(n, s, d) for n, s, d in host])]),
            Plane("/device:TPU:0", [Line("XLA Ops", [Event("op", s, d) for s, d in ops])])]


def test_idle_inside_clips_to_the_window():
    planes = _only([(40, 20)], [("serve.fetch", -50, 100), ("serve.fetch", 90, 50)])
    # fetch open [0,50) and [90,100) inside the window; the device runs [40,60)
    assert spans.idle_inside(planes, 0, 100, spans.FETCH) == 40 + 10
    assert spans.idle_inside(planes, 10, 45, spans.FETCH) == 30


def test_idle_inside_merges_spans_across_threads():
    host = Plane("/host:CPU", [Line("python3", [Event("serve.pad", 10, 30)]),
                               Line("python3", [Event("serve.resolve", 20, 40)])])
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [Event("op", 45, 5)])])
    # open [10,60) once, not 30 + 40; the device ran [45,50) of it
    assert spans.idle_inside([host, dev], 0, 100, spans.HOST) == 45


def test_idle_outside_the_named_spans_is_not_counted():
    planes = _only([(0, 10)], [("serve.fetch", 20, 10), ("serve.pad", 50, 10),
                               ("time sleep", 70, 30)])
    assert spans.idle_inside(planes, 0, 100, spans.FETCH) == 10
    assert spans.idle_inside(planes, 0, 100, spans.HOST) == 10
    assert spans.idle_inside(planes, 0, 100, {"serve.nothing"}) == 0
    assert spans.idle_inside(_only([(20, 10)], [("serve.fetch", 20, 10)]), 0, 100,
                             spans.FETCH) == 0


def test_four_parts_sum_to_the_idle_share():
    planes = _trace()
    lo, hi = tracing.window_ns(planes)
    parts = spans.idle_parts(planes, lo, hi)
    # device 0 idles [0,10) [30,60) [70,100), device 1 [50,100): averaged over both
    assert parts == {"collect": (20 + 10) / 2, "host": (10 + 15) / 2,
                     "fetch": (30 + 15) / 2, "unattributed": (10 + 10) / 2}
    idle = 1 - tracing.busy_ns(planes, lo, hi) / (hi - lo)
    assert sum(parts.values()) / (hi - lo) == pytest.approx(idle)


def test_idle_share_is_nothing_without_the_spans():
    planes = _trace()
    assert spans.idle_share(planes, 0, 100, spans.FETCH) == pytest.approx((30 + 15) / 2)
    assert spans.idle_share(_only([(0, 10)], [("time sleep", 0, 100)]), 0, 100,
                            spans.FETCH) is None
    with pytest.raises(ValueError):
        spans.idle_inside(planes[:1], 0, 100, spans.FETCH)


@pytest.mark.parametrize("metric,expected", [("fetch_idle_share", (30 + 15) / 2),
                                             ("host_idle_share", (10 + 15) / 2)])
def test_span_readers(metric, expected):
    read = spec.load_reader(metric)
    planes = _trace()
    assert read(SimpleNamespace(trace=planes, trace_span=(0, 100))) == pytest.approx(expected)
    assert read(SimpleNamespace(trace=None, trace_span=None)) is None
    untraced = [p for p in planes if tracing.is_device(p)] + [
        Plane("/host:CPU", [Line("python3", [Event("bench.window", 0, 100)])])]
    assert read(SimpleNamespace(trace=untraced, trace_span=(0, 100))) is None


def test_queue_wait_reader():
    read = spec.load_reader("queue_wait_ms.interactive")
    before = {"queue_wait_ms_total": 500.0, "queue_waits": 10}
    after = {"queue_wait_ms_total": 2500.0, "queue_waits": 30}
    assert read(SimpleNamespace(stats_before=before, stats_after=after)) == 100.0
    assert read(SimpleNamespace(stats_before=before, stats_after=before)) is None
    assert read(SimpleNamespace(stats_before={}, stats_after={"requests": 3})) is None
