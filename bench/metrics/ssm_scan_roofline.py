"""Model stage: the ``selective_scan`` kernel's share of its roofline over
the traced window.

The least time is the larger of the scan's FLOPs over the chip's peak and
its bytes over the HBM bandwidth (``scan_flops`` and ``scan_bytes`` of
``bench/experts/jamba.py``: dt, x, B and C read and y written once per
token and Mamba layer), summed over the events of the windows whose
experts include a Jamba expert (``window_log`` ``size``: padding rows are
not counted, so padding reads as lost roofline).  The kernel's time is the
device time of the Mosaic calls (``tpu_custom_call``) inside the
``jit_jamba_score`` program, in the trace."""
from bench.common import registry

KERNEL_MODULE = "jit_jamba_score"


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
    if not run.device_ops or not run.windows:
        return None
    seconds = kernel_seconds(run)
    if seconds <= 0:
        return None
    kind = registry.expert_kind("jamba")
    least = 0.0
    for name, spec in run.dep.experts.items():
        if spec["kind"] != "jamba":
            continue
        events = sum(w["size"] for w in run.windows
                     if name in w["key"].split("+"))
        tokens = events * spec["tokens_per_event"]
        least += max(kind.scan_flops(spec, tokens) / run.peaks["flops_per_s"],
                     kind.scan_bytes(spec, tokens)
                     / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds if least > 0 else None
