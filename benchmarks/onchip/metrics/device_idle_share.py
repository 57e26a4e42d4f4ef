"""Share of the traced window in which no operation ran on the device."""
from trace_reduce import busy_s, window_s


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - busy_s(run.trace) / window_s(run.trace))
