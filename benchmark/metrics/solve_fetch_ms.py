"""Lowering and per-cycle solve: milliseconds per pass of the blocking
fetch of the cycle solve's packed result (the ``fetch`` sub-phase), in
every cycle that launched; nothing where no cycle launched."""

from benchmark.record import per_pass


def launched(c):
    return c.get("counts", {}).get("launches", 0) > 0


def read(record):
    if not any(launched(c) for p in record["passes"] for c in p["cycles"]):
        return None
    return per_pass(record, (
        sum(c["subMs"].get("fetch", 0.0) for c in p["cycles"] if launched(c))
        for p in record["passes"]
    ))
