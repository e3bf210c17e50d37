"""Scheduler cycle: milliseconds per pass of parking, the requeue of
every entry a cycle did not admit (the ``park`` sub-phase), in every
cycle; nothing where the program records no sub-phases."""

from benchmark.record import per_pass


def read(record):
    if not any("subMs" in c for p in record["passes"] for c in p["cycles"]):
        return None
    return per_pass(record, (
        sum(c["subMs"].get("park", 0.0) for c in p["cycles"])
        for p in record["passes"]
    ))
