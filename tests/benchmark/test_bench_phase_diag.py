"""The phase diagnostic (``python3 -m benchmark.phase_diag``): idle time
named by the program's ``kueue.*`` spans on a hand-made trace, the
collector's pauses by region, the switches for the collector and the
annotations, and a traced run of a cell at a size a test can hold."""

import gc

import pytest

from bench_tiny import tiny_registry
from benchmark import harness, phase_diag
from benchmark.phase_diag import GcProbe, idle_analysis, segments


def ev(name, start, dur, plane="/host:CPU", line="py"):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def op(start, dur):
    return ev("fusion", start, dur, plane="/device:TPU:0", line="XLA Ops")


def handmade():
    """Window [0, 1000] ns; the device busy over [100, 200] and [600,
    700]; a fingerprint over [0, 300] with a fetch nested in it over [250,
    280], and a batch apply over [700, 900]."""
    return [
        ev("bench.window", 0, 1000),
        ev("bench.submit", 650, 300),
        op(100, 100), op(600, 100),
        ev("kueue.loop.fingerprint", 0, 300),
        ev("kueue.cycle.fetch", 250, 30),
        ev("kueue.ingest.apply", 700, 200),
    ]


def test_segments_name_the_innermost_span():
    segs = segments([e for e in handmade() if e["name"].startswith("kueue.")])
    assert segs == [
        (0.0, 250.0, "kueue.loop.fingerprint"),
        (250.0, 280.0, "kueue.cycle.fetch"),
        (280.0, 300.0, "kueue.loop.fingerprint"),
        (700.0, 900.0, "kueue.ingest.apply"),
    ]


def test_idle_time_named_by_the_program_spans():
    out = idle_analysis(handmade())
    # idle gaps [0, 100], [200, 600] and [700, 1000]: 800 ns, of which
    # 100 + (50 + 30 + 20) + 200 lie under a kueue.* span
    assert out["idle_s"] == pytest.approx(800e-9)
    assert out["idle_unattributed_pct"] == pytest.approx(50.0)
    assert out["idle_by_innermost_s"] == pytest.approx({
        "kueue.loop.fingerprint": 170e-9,
        "kueue.cycle.fetch": 30e-9,
        "kueue.ingest.apply": 200e-9,
    })
    # longest first, each named by the span over its middle
    assert [g[0] for g in out["idle_gaps_program"]] == [
        "outside kueue spans", "kueue.ingest.apply", "kueue.loop.fingerprint"]
    assert [g[1] for g in out["idle_gaps_program"]] == pytest.approx(
        [400e-9, 300e-9, 100e-9])
    assert out["kueue_span_s"]["kueue.loop.fingerprint"] == pytest.approx(300e-9)


def test_idle_analysis_without_a_window_or_program_spans():
    assert idle_analysis([op(0, 10)]) is None
    bare = [e for e in handmade() if not e["name"].startswith("kueue.")]
    out = idle_analysis(bare)
    assert out["idle_unattributed_pct"] == pytest.approx(100.0)
    assert out["kueue_span_s"] == {}


def test_gc_pauses_land_in_the_current_region():
    probe = GcProbe()

    def collect(gen):
        probe("start", {"generation": gen})
        probe("stop", {"generation": gen})

    with probe.within("loop"):
        collect(0)
        with probe.within("cycle"):
            with probe.within("nominate"):
                collect(2)
        probe.end_cycle("device")
        with probe.within("cycle"):
            with probe.within("nominate"):
                collect(1)
        probe.end_cycle("host")
    collect(0)
    snap = probe.snapshot()
    assert snap["collections"] == [2, 1, 1]
    paused = {k for k, v in snap["pauseMs"].items() if v > 0}
    assert paused == {"loop", "nominate.device", "nominate.other", "off"}
    assert probe.region == "off"


def test_switches_are_undone_after_a_failed_run(monkeypatch):
    from kueue_tpu.tracing import phases

    seen = {}

    def fake_run_cell(*args, system_factory=None, **kwargs):
        seen["annotation"] = phases._annotation()
        seen["gc"] = gc.isenabled()
        raise RuntimeError("the run failed")

    monkeypatch.setattr(harness, "run_cell", fake_run_cell)
    with pytest.raises(RuntimeError, match="the run failed"):
        phase_diag.run("northstar-1k.fresh", 1, 0.1, gc_on=False,
                       annotations=False)
    assert seen["annotation"] is phase_diag._NoAnnotation
    assert seen["gc"]  # the collector goes off only when the window starts
    assert phases._annotation() is not phase_diag._NoAnnotation
    assert gc.isenabled()
    assert not [c for c in gc.callbacks if isinstance(c, GcProbe)]


def test_traced_tiny_run_keeps_the_program_per_pass(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    reg = tiny_registry(tmp_path)
    out = phase_diag.run("northstar-1k.fresh", 2**31 + 29, 0.3, gc_on=False,
                         registry=reg, require_tpu=False)
    assert gc.isenabled()
    assert out["result"]["correct"], out["result"]["checks"]
    passes = out["program_per_pass"]
    assert passes
    for p in passes:
        assert p["spansMs"]["loop.fingerprint"] > 0
        assert p["counts"]["ingest.objects"] > 0
        assert p["gcCollections"] == [0, 0, 0]  # the collector was off
        assert p["tracerSelfMs"] > 0
        assert p["cycles"] and all("subMs" in c for c in p["cycles"])
    # the capture holds the program's phases; no device plane on the CPU
    assert "kueue.loop.fingerprint" in out["idle"]["kueue_span_s"]
