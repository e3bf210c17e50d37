"""The readers of the program's cycle sub-phases and counts: each on a
hand-made record, the benchmark's earlier readers unchanged by the new
fields, the program's ``kueue.*`` profiler annotations kept out of the
trace reduction, and a traced run of a cell at a size a test can hold."""

import copy

import pytest

from bench_tiny import tiny_registry
from benchmark import harness
from benchmark.harness import run_cell
from benchmark.registry import Registry

NEW = ["host_walk_ms", "cycle_park_ms", "solve_launch_ms", "solve_fetch_ms",
       "heads_returned_pct"]
OLD = ["admissions_per_s", "setup_s", "ingest_ms_per_1k", "release_ms_per_1k",
       "loop_overhead_ms", "cycle_host_ms", "device_decided_pct",
       "cycle_nominate_ms", "cycle_kernel_ms", "device_idle_pct"]


def cycle(resolution, admitted, spans, sub=None, counts=None, total=None):
    c = {"resolution": resolution, "admitted": admitted, "spansMs": spans,
         "totalMs": total if total is not None else sum(spans.values())}
    if sub is not None:
        c["subMs"] = sub
    if counts is not None:
        c["counts"] = counts
    return c


def record():
    """Two passes: a device cycle that admits, a launched cycle whose
    heads all come back and are walked and parked on the host, and a
    cycle that never launched (its heads below the solver's
    threshold)."""
    def one_pass(k):
        return {
            "served_s": 2.0, "admitted": 10, "submitted": 40,
            "spans": {"ingest": 0.5, "loop": 1.5, "release": 0.1},
            "counts": {"ingested": 40, "released": 40, "reconciles": 2},
            "cycles": [
                cycle("device", 10,
                      {"heads": 1.0, "snapshot": 2.0, "nominate": 30.0,
                       "admit": 5.0},
                      sub={"prevalidate": 1.0, "encode": 4.0, "launch": 6.0,
                           "fetch": 15.0, "decode": 2.0, "park": 1.0 * k},
                      counts={"lowered": 20, "unrepresentable": 2,
                              "launches": 1, "returned": 0, "parked": 10}),
                cycle("host", 0,
                      {"heads": 1.0, "snapshot": 2.0, "nominate": 50.0,
                       "admit": 9.0},
                      sub={"prevalidate": 1.0, "encode": 3.0, "launch": 5.0,
                           "fetch": 10.0, "decode": 0.1, "assign": 20.0,
                           "victims": 8.0, "park": 7.0},
                      counts={"lowered": 20, "unrepresentable": 0,
                              "launches": 1, "returned": 20, "walked": 20,
                              "deferred": 20, "parked": 20}),
                cycle("host", 0,
                      {"heads": 1.0, "snapshot": 1.0, "nominate": 4.0,
                       "admit": 2.0},
                      sub={"prevalidate": 0.5, "assign": 3.0, "park": 1.0},
                      counts={"prevalidated": 5, "walked": 5, "parked": 5}),
            ],
            "cycles_lost": False,
        }
    return {"passes": [one_pass(1), one_pass(3)], "window_s": 4.0,
            "setup_s": 30.0, "trace": None}


def strip_new_fields(rec):
    rec = copy.deepcopy(rec)
    for p in rec["passes"]:
        for c in p["cycles"]:
            c.pop("subMs", None)
            c.pop("counts", None)
    return rec


@pytest.fixture(scope="module")
def reg():
    return Registry()


def test_new_readers_on_a_handmade_record(reg):
    rec = record()
    read = {m: reg.module("metrics", m).read(rec) for m in NEW}
    # assign + victims of every cycle, whatever its resolution
    assert read["host_walk_ms"] == pytest.approx(20.0 + 8.0 + 3.0)
    # pass 1 parks 1 + 7 + 1 ms, pass 2 3 + 7 + 1 ms
    assert read["cycle_park_ms"] == pytest.approx((9.0 + 11.0) / 2)
    # encode + launch of the two cycles that launched
    assert read["solve_launch_ms"] == pytest.approx(4.0 + 6.0 + 3.0 + 5.0)
    assert read["solve_fetch_ms"] == pytest.approx(15.0 + 10.0)
    # (2 unrepresentable + 20 returned) of 40 lowered, in each pass
    assert read["heads_returned_pct"] == pytest.approx(100.0 * 22 / 40)


def test_new_readers_read_nothing_without_the_new_fields(reg):
    bare = strip_new_fields(record())
    for m in NEW:
        assert reg.module("metrics", m).read(bare) is None, m


def test_launch_readers_read_nothing_where_no_cycle_launched(reg):
    rec = record()
    for p in rec["passes"]:
        p["cycles"] = p["cycles"][2:]
    assert reg.module("metrics", "solve_launch_ms").read(rec) is None
    assert reg.module("metrics", "solve_fetch_ms").read(rec) is None
    assert reg.module("metrics", "heads_returned_pct").read(rec) is None
    assert reg.module("metrics", "host_walk_ms").read(rec) == pytest.approx(3.0)


def test_earlier_readers_read_the_same_with_or_without_the_new_fields(reg):
    rec = record()
    bare = strip_new_fields(rec)
    for m in OLD:
        mod = reg.module("metrics", m)
        assert mod.read(rec) == mod.read(bare), m


def test_program_annotations_stay_out_of_the_trace_reduction(tmp_path):
    """A profiler capture of a process that runs the program's phases
    holds their ``kueue.*`` host events; the benchmark's reader keeps
    only its own ``bench.*`` spans and the device's, so busy time, idle
    gaps and their names read as they did before the program had
    phases."""
    import jax

    from benchmark.trace import Profiler, Trace, read_xplane
    from kueue_tpu.tracing import Phase, phase

    with Profiler(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.submit"):
            with phase(Phase("ingest.apply")):
                pass
            with phase(Phase("loop.fingerprint")):
                pass
    events = read_xplane(str(tmp_path))
    names = {e["name"] for e in events}
    assert {"bench.window", "bench.submit"} <= names
    assert not [n for n in names if n.startswith("kueue.")]
    t = Trace(events)
    assert [e["name"] for e in t.host_spans] == ["bench.submit"]


def test_traced_tiny_run_reports_the_new_metrics(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    reg = tiny_registry(tmp_path)
    res = run_cell("northstar-1k.fresh", 2**31 + 13, 0.3, True, registry=reg,
                   require_tpu=False)
    assert res["correct"], res["checks"]
    per_layer = {m["name"] for m in reg.cell("northstar-1k.fresh")["per_layer"]}
    assert set(NEW) <= per_layer
    assert set(res["metrics"]) <= per_layer
    # the tiny cell's cycles stay under the solver's threshold: the host
    # walks and parks every cycle, and nothing launches
    assert res["metrics"]["host_walk_ms"]["value"] > 0
    assert res["metrics"]["cycle_park_ms"]["value"] > 0
    assert res["metrics"]["host_walk_ms"]["unit"] == "ms/pass"
    for m in ("solve_launch_ms", "solve_fetch_ms", "heads_returned_pct"):
        assert m not in res["metrics"]
    assert "breakdown" in res
