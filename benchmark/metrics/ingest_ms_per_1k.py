"""Server batch apply: milliseconds per 1,000 workloads of the batch
POST, less the auto-reconcile (``run_until_idle``) run inside it."""

from benchmark.record import ms_per_1k


def read(record):
    return ms_per_1k(record, "ingest", "ingested")
