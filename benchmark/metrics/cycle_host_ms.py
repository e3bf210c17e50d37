"""Scheduler cycle: milliseconds per pass of every cycle's host phases
(heads, snapshot, admit); a device cycle's solve is in
``cycle_nominate_ms``."""

from benchmark.record import per_pass

HOST = ("heads", "snapshot", "admit")


def read(record):
    return per_pass(record, (
        sum(c["spansMs"].get(k, 0.0) for c in p["cycles"] for k in HOST)
        for p in record["passes"]
    ))
