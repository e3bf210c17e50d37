"""Cycle kernels: the share of the window's admissions whose flavor, in
some resource group, lies past one where they fit only by borrowing
(``borrow_skipped``, written by the cycle kernel's decode and the host
flavor walk): whenCanBorrow TryNextFlavor at work. Nothing where no
cycle counted one, or where the program does not count them."""


def read(record):
    cycles = [c for p in record["passes"] for c in p["cycles"]]
    if not any("borrow_skipped" in c.get("counts", {}) for c in cycles):
        return None
    admitted = sum(c["admitted"] for c in cycles)
    if not admitted:
        return None
    skipped = sum(c.get("counts", {}).get("borrow_skipped", 0) for c in cycles)
    return 100.0 * skipped / admitted
