"""Bulk drain and per-cycle lowering: the share of the window's lowered
heads whose flavor walk resumed from a cursor (``walks_resumed``): the
heads a cycle or a drain round cut off mid-walk, lowered again past the
flavors they tried. Heads lowered are the per-cycle lowerings' heads
(``lowered``) and the drain rounds' heads. Nothing where no cycle
counted a resumed walk, or where the program does not count them."""


def read(record):
    cycles = [c for p in record["passes"] for c in p["cycles"]]
    if not any("walks_resumed" in c.get("counts", {}) for c in cycles):
        return None
    lowered = sum(
        c["heads"] if c["resolution"] == "drain" else c.get("counts", {}).get("lowered", 0)
        for c in cycles
    )
    if not lowered:
        return None
    resumed = sum(c.get("counts", {}).get("walks_resumed", 0) for c in cycles)
    return 100.0 * resumed / lowered
