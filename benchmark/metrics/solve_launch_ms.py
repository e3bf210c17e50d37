"""Lowering and per-cycle solve: milliseconds per pass of lowering the
heads and launching the cycle solve (the ``encode`` and ``launch``
sub-phases: resident refresh, packing, transfers, the launch), in every
cycle that launched, whatever its resolution; nothing where no cycle
launched."""

from benchmark.record import per_pass

LAUNCH = ("encode", "launch")


def launched(c):
    return c.get("counts", {}).get("launches", 0) > 0


def read(record):
    if not any(launched(c) for p in record["passes"] for c in p["cycles"]):
        return None
    return per_pass(record, (
        sum(c["subMs"].get(k, 0.0)
            for c in p["cycles"] if launched(c) for k in LAUNCH)
        for p in record["passes"]
    ))
