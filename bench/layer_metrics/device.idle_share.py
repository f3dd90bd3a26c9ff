"""Share of the traced window in which no operation ran on the device."""
from bench import trace_reduce


def read(r):
    if not r.window_s or not r.trace.devices:
        return None
    return 100.0 * (1.0 - trace_reduce.mean_busy_s(r.trace) / r.window_s)
