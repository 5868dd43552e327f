"""Device: share of the traced window in which no operation ran,
averaged over the cell's chips."""
from bench.common import trace


def read(run):
    if not run.device_ops:
        return None
    lo, hi = run.trace_window
    planes = list(run.device_ops.values())[:run.cell.chips]
    busy = sum(trace.busy_ns(ops, lo, hi) for ops in planes) / len(planes)
    return 100.0 * (1.0 - busy / (hi - lo))
