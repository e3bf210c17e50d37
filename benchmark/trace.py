"""From a profiler trace to device numbers.

A traced run records the window with ``jax.profiler``. The reduction
works on a flat list of events, each ``{"plane", "line", "name",
"start_ns", "dur_ns"}``, read from the ``.xplane.pb`` with nothing but
JAX: the device planes (``/device:...``) and the benchmark's own host
spans (named ``bench.*``), which share the profiler's clock.

- busy: the union of the intervals of the device's ``XLA Ops`` events
  inside the ``bench.window`` span, averaged over the chips used;
- a kernel's time: the summed durations of the ``XLA Modules`` events
  whose name holds the kernel's jitted name;
- the breakdown: the device operations that took most time, and the
  longest idle gaps, each named by the innermost ``bench.*`` span that
  covers its middle (what the benchmark was driving then).
"""

from __future__ import annotations

import glob
import os
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS, MODULES = "XLA Ops", "XLA Modules"
WINDOW = "bench.window"


def read_xplane(log_dir: str) -> List[dict]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            device = plane.name.startswith("/device:")
            for line in plane.lines:
                if device and line.name not in (OPS, MODULES):
                    continue
                for ev in line.events:
                    if not device and not ev.name.startswith("bench."):
                        continue
                    out.append({
                        "plane": plane.name,
                        "line": line.name,
                        "name": ev.name,
                        "start_ns": float(ev.start_ns),
                        "dur_ns": float(ev.duration_ns),
                    })
    return out


def _merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


class Trace:
    def __init__(self, events: Sequence[dict]):
        self.events = list(events)
        windows = [e for e in self.events if e["name"] == WINDOW]
        if not windows:
            raise ValueError(f"trace has no {WINDOW} span")
        w = max(windows, key=lambda e: e["dur_ns"])
        self.w0, self.w1 = w["start_ns"], w["start_ns"] + w["dur_ns"]
        self.devices = sorted({
            e["plane"] for e in self.events if e["plane"].startswith("/device:")
        })
        self.host_spans = [
            e for e in self.events
            if not e["plane"].startswith("/device:") and e["name"] != WINDOW
        ]

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) / 1e9

    def _clip(self, e: dict) -> Optional[Tuple[float, float]]:
        a = max(e["start_ns"], self.w0)
        b = min(e["start_ns"] + e["dur_ns"], self.w1)
        return (a, b) if b > a else None

    def busy_intervals(self, device: str) -> List[List[float]]:
        return _merge(
            iv for e in self.events
            if e["plane"] == device and e["line"] == OPS
            for iv in [self._clip(e)] if iv is not None
        )

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        total = sum(
            b - a for d in self.devices for a, b in self.busy_intervals(d)
        )
        return total / len(self.devices) / 1e9

    def module_s(self, pattern: str) -> float:
        """Summed device seconds of the jitted programs whose name holds
        ``pattern``, averaged over devices."""
        if not self.devices:
            return 0.0
        total = 0.0
        for e in self.events:
            if e["line"] == MODULES and pattern in e["name"]:
                iv = self._clip(e)
                if iv is not None:
                    total += iv[1] - iv[0]
        return total / len(self.devices) / 1e9

    def device_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operations that ran longest, by HLO name (the text
        before `` = `` of the trace's op description)."""
        by_name: Dict[str, float] = {}
        for e in self.events:
            if e["line"] == OPS:
                iv = self._clip(e)
                if iv is not None:
                    name = e["name"].split(" = ", 1)[0]
                    by_name[name] = by_name.get(name, 0.0) + iv[1] - iv[0]
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / len(self.devices) / 1e9] for k, v in top]

    def _host_phase(self, t: float) -> str:
        best = None
        for e in self.host_spans:
            if e["start_ns"] <= t <= e["start_ns"] + e["dur_ns"]:
                if best is None or e["dur_ns"] < best["dur_ns"]:
                    best = e
        return best["name"] if best is not None else "outside bench spans"

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The ``n`` longest device-idle gaps of the first device."""
        if not self.devices:
            return [["no device events", self.window_s]]
        busy = self.busy_intervals(self.devices[0])
        gaps, t = [], self.w0
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.w1 > t:
            gaps.append((t, self.w1))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [
            [self._host_phase((a + b) / 2), (b - a) / 1e9] for a, b in gaps[:n]
        ]


class Profiler:
    """Records the window into ``log_dir`` (emptied first)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def __enter__(self):
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        # no Python call tracing: it slows the host many times over
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._window.__exit__(*exc)
        jax.profiler.stop_trace()
        return False
