"""Runtime object store: milliseconds per 1,000 finished (or cancelled)
workloads deleted in-process, quota release and cohort requeue
included."""

from benchmark.record import ms_per_1k


def read(record):
    return ms_per_1k(record, "release", "released")
