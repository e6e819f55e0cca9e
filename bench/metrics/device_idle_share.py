"""Share of the traced window with no op running on the device:
1 - (union of device op intervals) / window."""
from harness import trace


def read(run):
    b = trace.busy(run.trace)
    if b["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
