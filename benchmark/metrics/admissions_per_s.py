"""End to end: every admission of the window's whole passes over the
window's seconds, on the host clock."""


def read(record):
    admitted = sum(p["admitted"] for p in record["passes"])
    return admitted / record["window_s"] if record["window_s"] > 0 else None
