"""Device: the share of the traced window in which no operation ran."""


def read(record):
    trace = record.get("trace")
    if trace is None or not trace.devices:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
