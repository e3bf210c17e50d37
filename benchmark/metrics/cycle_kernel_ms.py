"""Per-cycle solver on the device: milliseconds per pass of the jitted
cycle programs (``*solve_cycle*``) in the profiler trace."""


def read(record):
    trace = record.get("trace")
    if trace is None or not record["passes"]:
        return None
    s = trace.module_s("solve_cycle")
    return s * 1e3 / len(record["passes"]) if s > 0 else None
