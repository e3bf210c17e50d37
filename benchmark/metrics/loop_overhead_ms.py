"""Runtime loop: milliseconds per pass spent in ``run_until_idle``
outside every cycle's own span (reconcile passes, state fingerprints)."""

from benchmark.record import per_pass


def read(record):
    return per_pass(record, (
        p["spans"].get("loop", 0.0) * 1e3 - sum(c["totalMs"] for c in p["cycles"])
        for p in record["passes"]
    ))
