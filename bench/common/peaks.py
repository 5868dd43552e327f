"""Peak rates of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A chip that is not here is an error: a share
of a peak is never computed against a guessed one."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip,
    # 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(f"no peaks for device kind {device_kind!r}; "
                          f"known: {sorted(PEAKS)}") from None
