"""Solver guard: the share of the window's admissions that the device
decided, in drain rounds or in per-cycle device solves (a guard
failover shows as a ``host-mirror`` cycle and counts against it)."""

DEVICE = ("drain", "device")


def read(record):
    cycles = [c for p in record["passes"] for c in p["cycles"]]
    total = sum(c["admitted"] for c in cycles)
    if not total:
        return None
    on_device = sum(c["admitted"] for c in cycles if c["resolution"] in DEVICE)
    return 100.0 * on_device / total
