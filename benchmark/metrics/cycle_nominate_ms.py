"""Lowering and per-cycle device solve: milliseconds per pass of the
device cycles' ``nominate`` spans (lowering the heads, the dispatch,
the fetch and the host's part of the flavor assignment); nothing where
no cycle was decided on the device."""

from benchmark.record import cycles_of, per_pass


def read(record):
    if not any(cycles_of(p, "device") for p in record["passes"]):
        return None
    return per_pass(record, (
        sum(c["spansMs"].get("nominate", 0.0) for c in cycles_of(p, "device"))
        for p in record["passes"]
    ))
