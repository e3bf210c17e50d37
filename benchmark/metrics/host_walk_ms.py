"""Scheduler cycle: milliseconds per pass of the host's flavor walk and
victim search (the ``assign`` and ``victims`` sub-phases), in every
cycle, whatever decided it; nothing where the program records no
sub-phases."""

from benchmark.record import per_pass

WALK = ("assign", "victims")


def read(record):
    if not any("subMs" in c for p in record["passes"] for c in p["cycles"]):
        return None
    return per_pass(record, (
        sum(c["subMs"].get(k, 0.0) for c in p["cycles"] for k in WALK)
        for p in record["passes"]
    ))
