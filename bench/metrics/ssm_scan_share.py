"""Model stage: the ``selective_scan`` kernel's device time as a share of
all device time inside the ``jit_jamba_score`` program over the traced
window, in percent: whether the Jamba expert's distinctive mechanism is a
large part of its time."""
from bench.metrics.ssm_scan_roofline import KERNEL_MODULE, kernel_seconds


def read(run):
    if not run.device_ops:
        return None
    lo, hi = run.trace_window
    total = sum(op.dur_ns * 1e-9
                for ops in list(run.device_ops.values())[:run.cell.chips]
                for op in ops
                if op.module == KERNEL_MODULE and lo <= op.start_ns <= hi)
    kernel = kernel_seconds(run)
    if kernel <= 0:
        return None
    return 100.0 * kernel / total
