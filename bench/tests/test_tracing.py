"""The trace reduction on a small hand-made trace."""

import pytest

from bench import tracing
from bench.tracing import Event, Line, Plane


def _trace():
    # window 0..100 ns on the host; device 0 runs ops at [10,30) [20,40) [60,70),
    # device 1 at [0,50); one op starts outside the window
    dev0 = Plane("/device:TPU:0", [
        Line("XLA Ops", [Event("%doc_score_fwd.3 = f32[16,16,4096] custom-call(s32[16,4000] %a)", 10, 20),
                         Event("%sbmax.3 = f32[16,2,8,128] custom-call(%x)", 20, 20),
                         Event("%fusion.1 = s32[64] fusion(%doc_score_fwd.3)", 60, 10),
                         Event("%doc_score_fwd.2 = f32[16,16,512] custom-call()", 120, 5)]),
        Line("Async XLA Ops", [Event("%copy-start.4 = (f32[8]) copy-start(%y)", 0, 100)]),
        Line("XLA Modules", [Event("jit_fn", 0, 100)]),
    ])
    dev1 = Plane("/device:TPU:1", [Line("XLA Ops", [Event("doc_score_flat", 0, 50)])])
    host = Plane("/host:CPU", [
        Line("python3", [Event("bench.window", 0, 100), Event("drive.py open_loop", 0, 100),
                         Event("time sleep", 40, 20)]),
        Line("python3", [Event("make_query_batch", 42, 10), Event("long_wait", 0, 100)]),
    ])
    return [host, dev0, dev1]


def test_union_merges_and_clips():
    assert tracing.union([(10, 30), (20, 40), (60, 70)]) == [[10, 40], [60, 70]]
    assert tracing.union([(10, 30), (20, 40), (60, 70)], 25, 65) == [[25, 40], [60, 65]]
    assert tracing.union([(5, 5), (50, 40)]) == []


def test_window_and_busy_average_over_devices():
    planes = _trace()
    lo, hi = tracing.window_ns(planes)
    assert (lo, hi) == (0, 100)
    # device 0: [10,40) + [60,70) = 40; device 1: 50 -> mean 45
    assert tracing.busy_ns(planes, lo, hi) == 45
    idle = 1 - tracing.busy_ns(planes, lo, hi) / (hi - lo)
    assert idle == pytest.approx(0.55)


def test_kernel_time_by_name_inside_window():
    planes = _trace()
    assert tracing.kernel_ns(planes, ["doc_score_fwd", "doc_score_flat"], 0, 100) == 70
    assert tracing.kernel_ns(planes, ["sbmax"], 0, 100) == 20
    assert tracing.kernel_ns(planes, ["doc_score_fwd"], 0, 200) == 25
    assert tracing.kernel_ns(planes, ["boundsum_gather"], 0, 100) is None
    assert tracing.base_name("fusion.12") == "fusion" and tracing.base_name("a.b") == "a.b"
    assert tracing.op_name("%fusion.4 = s32[8] fusion(%a.1), calls=%f") == "fusion.4"


def test_top_ops_and_idle_gaps():
    planes = _trace()
    ops = dict((n, s) for n, s in tracing.top_ops(planes, 0, 100))
    assert ops == {"doc_score_flat": 50e-9, "doc_score_fwd.3": 20e-9, "sbmax.3": 20e-9,
                   "fusion.1": 10e-9}
    gaps = tracing.idle_gaps(planes, 0, 100)
    # device 0 idles [0,10) [40,60) [70,100), longest first; each is named by the
    # innermost host event at its middle, off the thread that sends the load
    assert [round(s * 1e9) for _, s in gaps] == [30, 20, 10]
    assert gaps[0][0] == "python3[1]: long_wait"
    assert gaps[1][0] == "python3[1]: long_wait > make_query_batch"
    only_sender = [p for p in planes if p.name != "/host:CPU"] + [
        Plane("/host:CPU", [Line("python3", [Event("bench.window", 0, 100),
                                             Event("time sleep", 40, 20)])])]
    assert tracing.idle_gaps(only_sender, 0, 100)[1][0] == "python3[0]: time sleep"
    assert tracing.idle_gaps(planes[1:], 0, 100)[0][0] == "no host event"


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tracing.busy_ns([Plane("/host:CPU", [])], 0, 1)
    with pytest.raises(ValueError):
        tracing.window_ns([Plane("/host:CPU", [Line("python", [])])])
