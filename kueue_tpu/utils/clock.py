"""Injectable clock (k8s.io/utils/clock analog) for deterministic tests."""

from __future__ import annotations

import time


class Clock:
    # this clock runs with the wall: time passes during a blocking call
    # (the solver guard takes XLA compiles out of its deadlines) and
    # between two admissions (the preempt drain ranks its admissions as
    # the host cycle loop stamps them)
    tracks_wall = True

    def now(self) -> float:
        return time.time()


class FakeClock(Clock):
    tracks_wall = False

    def __init__(self, start: float = 0.0):
        self._now = start

    def now(self) -> float:
        return self._now

    def advance(self, seconds: float) -> None:
        self._now += seconds

    def set(self, t: float) -> None:
        self._now = t
