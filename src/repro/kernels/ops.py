"""Public entry points for the Pallas kernels.

Each kernel compiles to Mosaic on a TPU and runs in the Pallas interpreter
on any other backend (see :func:`repro.kernels.resolve_interpret`); pass
``interpret=`` to pin one or the other.
"""
from __future__ import annotations

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quantile_map import quantile_map
from repro.kernels.score_pipeline import (
    banked_skip_stats,
    score_pipeline,
    score_pipeline_banked,
)

__all__ = ["banked_skip_stats", "decode_attention", "flash_attention",
           "quantile_map", "score_pipeline", "score_pipeline_banked"]
