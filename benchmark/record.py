"""What a run recorded, as the per-layer metric readers see it.

``record["passes"]`` holds one entry per pass of the window: the
seconds it spent in the server (``served_s``), the admissions it made,
the benchmark's spans around its calls into the server (``ingest``,
``loop``, ``release``), their counts, and the program's ``CycleTrace``
dicts of every cycle it ran. ``record["trace"]``
is a ``benchmark.trace.Trace`` of the window in a traced run, else
``None``.
"""

from __future__ import annotations

from typing import Iterable, List, Optional


def cycles_of(p: dict, resolution: str) -> List[dict]:
    return [c for c in p["cycles"] if c["resolution"] == resolution]


def per_pass(record: dict, values: Iterable[float]) -> Optional[float]:
    """The mean over the window's passes, ``None`` without a pass."""
    vals = list(values)
    return sum(vals) / len(vals) if vals else None


def ms_per_1k(record: dict, span: str, count: str) -> Optional[float]:
    s = sum(p["spans"].get(span, 0.0) for p in record["passes"])
    n = sum(p["counts"].get(count, 0) for p in record["passes"])
    return s / n * 1e6 if n else None
