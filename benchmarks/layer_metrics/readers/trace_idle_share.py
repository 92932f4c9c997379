"""100 x (1 - busy / window) from the device trace."""

from benchmarks.harness import trace


def read(evidence):
    tr = evidence.get("trace")
    if not tr:
        return None
    busy_s, window_s = trace.busy(tr)
    return 100.0 * (1.0 - busy_s / window_s)
