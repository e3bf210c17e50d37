"""A traced run of one cell that also keeps what the result line does not
hold: per pass, the runtime's phase totals (``ClusterRuntime.
phase_totals()``), the cycles' traces, the tracer's self time and the
garbage collector's pauses by the region they land in; over the window, the device-idle time
named by the program's ``kueue.*`` profiler spans.

    python3 -m benchmark.phase_diag --workload northstar-1k.fresh \\
        --seed 3000000606 --seconds 45 --out diag.json

It prints the result line as ``python3 -m benchmark --trace 1`` does and
writes the rest to ``--out``. ``--gc off`` disables the collector for
the window; ``--annotations off`` makes the program's ``kueue.*``
profiler annotations no-ops, so its phase timers run without them. A
program without phase totals or annotations leaves those parts empty.

GC regions: ``nominate.device`` and ``nominate.other`` (a cycle's
``nominate`` phase, by the cycle's resolution, as ``cycle_nominate_ms``
splits them), ``cycle`` (the rest of a cycle), ``loop`` (``run_until_idle``
outside the cycles, what ``loop_overhead_ms`` reads), ``ingest`` (the
batch POST outside ``run_until_idle``), ``release`` (the in-process
deletes) and ``off`` (the benchmark's own work between passes).
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import benchmark.trace as btrace  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark.served import Served  # noqa: E402

REGIONS = ("nominate.device", "nominate.other", "cycle", "loop", "ingest",
           "release", "off")


class GcProbe:
    """Pause seconds and collections of the garbage collector, by the
    region that is current when each collection runs (a ``gc.callbacks``
    entry)."""

    def __init__(self):
        self.region = "off"
        self.pause_s: Dict[str, float] = {r: 0.0 for r in REGIONS}
        self.collections = [0, 0, 0]
        self._t: Optional[float] = None
        # a cycle's nominate pauses, until its resolution is known
        self.nominate_s = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
            return
        if self._t is None:
            return
        dt = time.perf_counter() - self._t
        self._t = None
        self.collections[info["generation"]] += 1
        if self.region == "nominate":
            self.nominate_s += dt
        else:
            self.pause_s[self.region] += dt

    @contextlib.contextmanager
    def within(self, region: str):
        outer, self.region = self.region, region
        try:
            yield
        finally:
            self.region = outer

    def end_cycle(self, resolution: str) -> None:
        key = "nominate.device" if resolution == "device" else "nominate.other"
        self.pause_s[key] += self.nominate_s
        self.nominate_s = 0.0

    def snapshot(self) -> dict:
        return {"pauseMs": {k: v * 1e3 for k, v in self.pause_s.items()},
                "collections": list(self.collections)}


def _delta(now: dict, mark: dict) -> dict:
    return {k: v - mark.get(k, 0) for k, v in now.items()}


class DiagServed(Served):
    """``Served`` that also snapshots the runtime's phase totals, the
    tracer's self time and the collector's pauses around each pass of
    the window."""

    def __init__(self, opts=None, probe: Optional[GcProbe] = None):
        super().__init__(opts)
        self.probe = probe or GcProbe()
        self.program: List[dict] = []
        self._mark = None
        rt, probe = self.rt, self.probe
        run, schedule = rt.run_until_idle, rt.scheduler.schedule
        nominate = rt.scheduler._nominate

        def run_until_idle(*args, **kwargs):
            with probe.within("loop"):
                return run(*args, **kwargs)

        def schedule_():
            with probe.within("cycle"):
                result = schedule()
            probe.end_cycle(result.resolution)
            return result

        def nominate_(*args, **kwargs):
            with probe.within("nominate"):
                return nominate(*args, **kwargs)

        rt.run_until_idle = run_until_idle
        rt.scheduler.schedule = schedule_
        rt.scheduler._nominate = nominate_

    def remove(self, keys):
        with self.probe.within("release"):
            return super().remove(keys)

    def submit(self, payload, count):
        with self.probe.within("ingest"):
            return super().submit(payload, count)

    def settle(self):
        with self.probe.within("ingest"):
            return super().settle()

    def _state(self) -> dict:
        totals = getattr(self.rt, "phase_totals", None)
        with self.srv.lock:
            return {"program": totals() if totals else None,
                    "tracer_s": self.rt.tracer.self_time_s,
                    "gc": self.probe.snapshot()}

    def reset_counters(self):
        super().reset_counters()
        self._mark = self._state()

    def admitted(self):
        out = super().admitted()
        if self._mark is not None:
            now, mark = self._state(), self._mark
            entry = {
                "tracerSelfMs": (now["tracer_s"] - mark["tracer_s"]) * 1e3,
                "gcPauseMs": _delta(now["gc"]["pauseMs"], mark["gc"]["pauseMs"]),
                "gcCollections": [a - b for a, b in zip(
                    now["gc"]["collections"], mark["gc"]["collections"])],
                "cycles": list(self.cycles),
            }
            if now["program"] is not None:
                entry["spansMs"] = _delta(now["program"]["spansMs"],
                                          mark["program"]["spansMs"])
                entry["counts"] = _delta(now["program"]["counts"],
                                         mark["program"]["counts"])
            self.program.append(entry)
            self._mark = None
        return out


class NoGcServed(DiagServed):
    """``DiagServed`` with the collector off from the window's first
    pass on."""

    def reset_counters(self):
        gc.disable()
        super().reset_counters()


def read_events(log_dir: str) -> List[dict]:
    """The capture's device ``XLA Ops`` and every host event named
    ``kueue.*`` or ``bench.*``, as ``benchmark.trace`` lays them out."""
    from jax.profiler import ProfileData

    out = []
    for path in sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True)):
        for plane in ProfileData.from_file(path).planes:
            device = plane.name.startswith("/device:")
            for line in plane.lines:
                if device and line.name != btrace.OPS:
                    continue
                for ev in line.events:
                    if not device and not ev.name.startswith(("kueue.", "bench.")):
                        continue
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name,
                                "start_ns": float(ev.start_ns),
                                "dur_ns": float(ev.duration_ns)})
    return out


def segments(spans: List[dict]) -> List[tuple]:
    """Elementary segments ``(start, end, innermost span name)`` of a set
    of nested spans, where innermost is the shortest span over it."""
    pts = sorted({x for e in spans
                  for x in (e["start_ns"], e["start_ns"] + e["dur_ns"])})
    starts = sorted(spans, key=lambda e: e["start_ns"])
    active: List[dict] = []
    i, out = 0, []
    for a, b in zip(pts, pts[1:]):
        while i < len(starts) and starts[i]["start_ns"] <= a:
            active.append(starts[i])
            i += 1
        active = [e for e in active if e["start_ns"] + e["dur_ns"] > a]
        if active:
            out.append((a, b, min(active, key=lambda e: e["dur_ns"])["name"]))
    return out


def idle_analysis(events: List[dict], n: int = 10) -> Optional[dict]:
    """Device-idle time of the ``bench.window`` by the innermost
    ``kueue.*`` span over it: the share no such span covers
    (``idle_unattributed_pct``), the ``n`` longest idle gaps named by the
    span over their middle, and each span's seconds in the window.
    Busy time is the first device's ``XLA Ops``."""
    try:
        tr = btrace.Trace(events)
    except ValueError:  # no window
        return None
    w0, w1 = tr.w0, tr.w1
    busy = tr.busy_intervals(tr.devices[0]) if tr.devices else []
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    prog = [e for e in events if not e["plane"].startswith("/device:")
            and e["name"].startswith("kueue.")]
    segs = segments(prog)
    idle_ns = sum(b - a for a, b in gaps)
    by: Dict[str, float] = {}
    i = 0
    for a, b in gaps:
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            lo, hi = max(a, segs[j][0]), min(b, segs[j][1])
            if hi > lo:
                by[segs[j][2]] = by.get(segs[j][2], 0.0) + (hi - lo) / 1e9
            j += 1
    starts = [s[0] for s in segs]

    def innermost(t: float) -> str:
        k = bisect.bisect_right(starts, t) - 1
        if k >= 0 and segs[k][0] <= t < segs[k][1]:
            return segs[k][2]
        return "outside kueue spans"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    per_name: Dict[str, float] = {}
    for e in prog:
        a, b = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
        if b > a:
            per_name[e["name"]] = per_name.get(e["name"], 0.0) + (b - a) / 1e9
    idle_s = idle_ns / 1e9
    return {
        "window_s": (w1 - w0) / 1e9,
        "idle_s": idle_s,
        "idle_unattributed_pct": (
            100.0 * (idle_s - sum(by.values())) / idle_s if idle_s else None
        ),
        "idle_gaps_program": [[innermost((a + b) / 2), (b - a) / 1e9]
                              for a, b in longest],
        "idle_by_innermost_s": dict(sorted(by.items(), key=lambda kv: -kv[1])),
        "kueue_span_s": dict(sorted(per_name.items(), key=lambda kv: -kv[1])),
    }


class _NoAnnotation:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def run(workload: str, seed: int, seconds: float, gc_on: bool = True,
        annotations: bool = True, registry=None, require_tpu: bool = True,
        t0: Optional[float] = None) -> dict:
    """One traced run of ``workload``; returns the result object and
    the diagnostics described in the module's docstring."""
    events: List[dict] = []
    read = btrace.read_xplane

    def read_and_keep(log_dir):
        events.extend(read_events(log_dir))
        return read(log_dir)

    probe = GcProbe()
    holder: Dict[str, DiagServed] = {}
    served = DiagServed if gc_on else NoGcServed

    def factory(opts):
        holder["s"] = served(opts, probe)
        return holder["s"]

    saved_annotation = None
    if not annotations:
        try:
            from kueue_tpu.tracing import phases
        except ImportError:  # a program without phases
            phases = None
        if phases is not None:
            saved_annotation = (phases, phases._annotation)
            phases._annotation = lambda: _NoAnnotation
    btrace.read_xplane = read_and_keep
    gc.callbacks.append(probe)
    try:
        res = harness.run_cell(workload, seed, seconds, True,
                               registry=registry, require_tpu=require_tpu,
                               t0=t0, system_factory=factory)
    finally:
        gc.callbacks.remove(probe)
        btrace.read_xplane = read
        gc.enable()
        if saved_annotation is not None:
            saved_annotation[0]._annotation = saved_annotation[1]
    return {"result": res, "gc": gc_on, "annotations": annotations,
            "program_per_pass": holder["s"].program,
            "idle": idle_analysis(events)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.phase_diag",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--gc", choices=("on", "off"), default="on")
    ap.add_argument("--annotations", choices=("on", "off"), default="on")
    args = ap.parse_args(argv)
    report = run(args.workload, args.seed, args.seconds,
                 gc_on=args.gc == "on", annotations=args.annotations == "on",
                 t0=_T0)
    with open(args.out, "w") as f:
        json.dump(report, f)
    print(json.dumps(report["result"]))
    return 0 if report["result"]["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
