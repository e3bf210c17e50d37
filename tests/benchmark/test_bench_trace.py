"""The reduction from a profiler trace to device numbers: on a small
hand-made trace whose numbers are known, and on a trace recorded on a
TPU v5e in a traced run of ``northstar-1k.fresh``."""

import json
import os

import pytest

from benchmark.trace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


def ev(plane, line, name, start_ms, dur_ms):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_ms * 1e6, "dur_ns": dur_ms * 1e6}


@pytest.fixture
def handmade():
    return Trace([
        ev("/host:CPU", "python3", "bench.window", 100, 1000),
        ev("/host:CPU", "python3", "bench.submit", 150, 500),
        ev("/host:CPU", "python3", "bench.settle", 650, 400),
        # ops: [50,120) clipped to [100,120); [300,400) and [350,450)
        # overlap -> [300,450); [1050,1200) clipped to [1050,1100)
        ev(DEV, "XLA Ops", "fusion.1", 50, 70),
        ev(DEV, "XLA Ops", "fusion.2", 300, 100),
        ev(DEV, "XLA Ops", "fusion.1", 350, 100),
        ev(DEV, "XLA Ops", "copy.3", 1050, 150),
        ev(DEV, "XLA Modules", "jit__solve_drain_packed(7)", 300, 150),
        ev(DEV, "XLA Modules", "jit_other(2)", 1050, 150),
    ])


def test_handmade_busy_idle_and_kernel(handmade):
    t = handmade
    assert t.window_s == pytest.approx(1.0)
    assert t.busy_s() == pytest.approx((20 + 150 + 50) / 1e3)
    assert t.module_s("solve_drain") == pytest.approx(0.150)
    assert t.module_s("not_there") == 0.0
    ops = dict((k, v) for k, v in t.device_ops())
    assert ops["fusion.1"] == pytest.approx((20 + 100) / 1e3)
    assert ops["copy.3"] == pytest.approx(0.050)
    gaps = t.idle_gaps()
    # idle: [120,300) in submit, [450,1050) mostly in settle; the longest first
    assert gaps[0] == ["bench.settle", pytest.approx(0.600)]
    assert gaps[1] == ["bench.submit", pytest.approx(0.180)]


def test_trace_without_window_is_refused():
    with pytest.raises(ValueError):
        Trace([ev(DEV, "XLA Ops", "x", 0, 1)])


def _sweep_busy_s(events, w0, w1):
    """Busy seconds by a plain sweep over interval ends, independent of
    ``Trace``'s merge."""
    pts = []
    for e in events:
        if e["line"] != "XLA Ops":
            continue
        a, b = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
        if b > a:
            pts += [(a, 1), (b, -1)]
    pts.sort(key=lambda p: (p[0], -p[1]))
    depth, busy, last = 0, 0.0, None
    for x, d in pts:
        if depth > 0:
            busy += x - last
        depth, last = depth + d, x
    return busy / 1e9


def test_recorded_v5e_trace():
    """A traced 5 s run of ``northstar-1k.fresh`` recorded on a TPU v5e
    (one pass of the window): the benchmark's host spans and the first
    3,000 device events, which hold the two per-cycle solves
    (``jit__solve_cycle_segmented_packed``) and nothing of the drain."""
    with open(os.path.join(HERE, "data", "v5e_fresh_trace.json")) as f:
        events = json.load(f)
    t = Trace(events)
    assert t.devices == ["/device:TPU:0"]
    assert t.window_s == pytest.approx(6.216456883)
    assert t.busy_s() == pytest.approx(0.042328499)
    assert t.busy_s() == pytest.approx(_sweep_busy_s(events, t.w0, t.w1))
    assert t.module_s("solve_cycle") == pytest.approx(0.042354301)
    assert t.module_s("solve_drain") == 0.0
    idle_pct = 100 * (1 - t.busy_s() / t.window_s)
    assert idle_pct == pytest.approx(99.3191, abs=1e-3)
    assert t.device_ops(1) == [["%while.6", pytest.approx(0.02236612)]]
    # the longest idle stretches: the loop after the kept events end, and
    # the batch POST's ingest before its auto-reconcile reaches a solve
    gaps = t.idle_gaps(2)
    assert gaps[0] == ["bench.run_until_idle", pytest.approx(4.599326483)]
    assert gaps[1] == ["bench.submit", pytest.approx(1.234874789)]
