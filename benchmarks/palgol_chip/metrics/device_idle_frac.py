"""Share of the traced job's window in which no operation ran on the
device: 1 - busy / window. Nothing where the trace holds no device
operation (a CPU run)."""


def read(record):
    trace = record["trace"]
    if trace is None or trace["busy_s"] <= 0:
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]
