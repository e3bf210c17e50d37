"""Lowering and per-cycle solve: the share of the window's heads of a
ClusterQueue with non-default flavor fungibility, offered to the
per-cycle lowering (``fungible_heads``), that it lowered onto the device
(``fungible_lowered``); nothing where no such head was offered, or where
the program does not count them."""


def read(record):
    counts = [c.get("counts", {}) for p in record["passes"] for c in p["cycles"]]
    offered = sum(k.get("fungible_heads", 0) for k in counts)
    if not offered:
        return None
    return 100.0 * sum(k.get("fungible_lowered", 0) for k in counts) / offered
