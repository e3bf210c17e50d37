"""End to end: seconds from the start of the process to the window."""


def read(record):
    return record["setup_s"]
