"""Lowering and per-cycle solve: the share of the window's lowered
heads handed back to the host walk, either unrepresentable in the
kernel's batch or returned by the kernel non-Fit; nothing where no head
was lowered."""


def read(record):
    counts = [c.get("counts", {}) for p in record["passes"] for c in p["cycles"]]
    lowered = sum(k.get("lowered", 0) for k in counts)
    if not lowered:
        return None
    back = sum(k.get("unrepresentable", 0) + k.get("returned", 0) for k in counts)
    return 100.0 * back / lowered
