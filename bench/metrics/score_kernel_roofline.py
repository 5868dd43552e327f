"""Transform stage: the banked T^C -> A -> T^Q kernel's share of its
roofline over the traced window.

The least time of one call is the larger of its FLOPs over the chip's
peak and its bytes over the HBM bandwidth, with the work counted so that
any implementation reads the same count (``harness.transform_flops`` and
``transform_bytes``: each window's rows and the distinct bank rows it
references, recorded around ``apply_transforms``).  The kernel's time is
the device time of its Mosaic call (``tpu_custom_call``) inside the
``jit_score_pipeline_banked`` program, in the trace."""
from bench.common import harness

KERNEL_MODULE = "jit_score_pipeline_banked"


def kernel_seconds(run) -> float:
    lo, hi = run.trace_window
    total = 0.0
    for ops in list(run.device_ops.values())[:run.cell.chips]:
        for op in ops:
            if op.kernel and op.module == KERNEL_MODULE \
                    and lo <= op.start_ns <= hi:
                total += op.dur_ns * 1e-9
    return total


def read(run):
    if not run.device_ops or not run.transform_calls:
        return None
    seconds = kernel_seconds(run)
    if seconds <= 0:
        return None
    n_q = run.dep.config["quantile_knots"]
    t_open, t_close = run.window
    least = 0.0
    for t, rows, distinct, k in run.transform_calls:
        if not t_open <= t <= t_close:
            continue
        flops = rows * harness.transform_flops(k, n_q)
        nbytes = harness.transform_bytes(rows, distinct, k, n_q)
        least += max(flops / run.peaks["flops_per_s"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
