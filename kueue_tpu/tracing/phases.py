"""Program phases on the profiler's clock.

A ``Phase`` names one region of the hot path (a registry name from
``tracing/names.py``, checked once where the phase is defined). Entering
``phase(p, sink)``:

- times the region with ``perf_counter`` and, when it exits without
  raising, adds the seconds (and the region's ``count``, under the
  phase's ``counter``) to ``sink.add_phase`` — a ``CycleTrace`` or the
  runtime's ``PhaseTotals``;
- wraps the region in ``jax.profiler.TraceAnnotation("kueue.<name>")``,
  so a profiler capture of the process places it on the same clock as
  the device's operations. With no profiler running the annotation
  costs well under a microsecond.

Phases are coarse by contract: one region per cycle phase or loop step,
never one per head or per workload.
"""

from __future__ import annotations

import functools
import threading
import time as _time
from typing import Dict

from kueue_tpu.tracing.names import PHASE_COUNTERS, PHASE_NAMES


@functools.cache
def _annotation():
    from kueue_tpu._jax import jax

    return jax.profiler.TraceAnnotation


class Phase:
    """One registered program region: its registry ``name``, the
    ``key`` it accumulates under in a ``CycleTrace`` (the name after
    its family prefix), its profiler ``label`` and the ``counter`` its
    region's count is added to ("" = none; else the phase's entry in
    ``PHASE_COUNTERS``)."""

    __slots__ = ("name", "key", "label", "counter")

    def __init__(self, name: str, counter: str = ""):
        if name not in PHASE_NAMES:
            raise ValueError(
                f"phase name {name!r} is not in the closed registry "
                "(kueue_tpu.tracing.names.PHASE_NAMES)"
            )
        if counter and PHASE_COUNTERS.get(name) != counter:
            raise ValueError(
                f"counter {counter!r} of phase {name!r} is not its "
                "registered one (kueue_tpu.tracing.names.PHASE_COUNTERS)"
            )
        self.name = name
        self.key = name.partition(".")[2]
        self.label = "kueue." + name
        self.counter = counter


class _Region:
    __slots__ = ("phase", "sink", "count", "seconds", "_t0", "_ann")

    def __init__(self, p: Phase, sink):
        self.phase = p
        self.sink = sink
        self.count = 0
        self.seconds = 0.0

    def __enter__(self) -> "_Region":
        self._ann = _annotation()(self.phase.label)
        self._ann.__enter__()
        self._t0 = _time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = _time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is None and self.sink is not None:
            self.sink.add_phase(self.phase, self.seconds, self.count)
        return False


def phase(p: Phase, sink=None) -> _Region:
    """Context manager over one region of phase ``p``. ``sink`` (any
    object with ``add_phase(phase, seconds, count)``, or None) receives
    the region's seconds; the region object also keeps them as
    ``.seconds`` after exit, and takes the count to report as
    ``.count`` while it runs."""
    return _Region(p, sink)


class PhaseTotals:
    """Cumulative seconds and counts of the runtime's phases (the loop
    around the cycles, the server's batch apply). Readers take
    snapshots and difference them, as they difference the CycleTrace
    ring. Request threads and the loop both add, hence the lock."""

    def __init__(self):
        self._lock = threading.Lock()
        self._seconds: Dict[str, float] = {}  # guarded by: _lock
        self._counts: Dict[str, int] = {}  # guarded by: _lock

    def add_phase(self, p: Phase, seconds: float, count: int = 0) -> None:
        with self._lock:
            self._seconds[p.name] = self._seconds.get(p.name, 0.0) + seconds
            if p.counter:
                self._counts[p.counter] = self._counts.get(p.counter, 0) + count

    def add_count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def snapshot(self) -> dict:
        """``{"spansMs": {name: ms}, "counts": {counter: n}}`` so far."""
        with self._lock:
            return {
                "spansMs": {
                    k: round(v * 1e3, 3) for k, v in self._seconds.items()
                },
                "counts": dict(self._counts),
            }
