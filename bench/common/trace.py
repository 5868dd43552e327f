"""Reduce a JAX profiler trace to what the per-layer readers need.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX.  On a TPU the
device planes are named ``/device:TPU:<n>``.  Their ``XLA Modules`` line
holds one event per program run (``jit_score_pipeline_banked(<hash>)``),
and their ``XLA Ops`` line one event per operation, named by its HLO text
(``%score_pipeline_banked.1 = f32[128]{0} custom-call(...),
custom_call_target="tpu_custom_call"``); an operation belongs to the
module whose event contains it.  Host threads are the lines of
``/host:CPU``; the benchmark's own spans (``jax.profiler.TraceAnnotation``)
appear there under their names.  Both are on one clock, in nanoseconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass(frozen=True)
class DeviceOp:
    name: str           # the HLO instruction, e.g. score_pipeline_banked.1
    module: str         # the program, e.g. jit_score_pipeline_banked
    start_ns: float
    dur_ns: float
    kernel: bool = False   # a Pallas (Mosaic) kernel call


def _module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def _op_name(hlo_text: str) -> str:
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def _device_ops(plane) -> list[DeviceOp]:
    lines = {line.name: line for line in plane.lines}
    modules = sorted((float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                      _module_name(ev.name))
                     for ev in (lines[MODULES_LINE].events
                                if MODULES_LINE in lines else ()))
    starts = [m[0] for m in modules]
    ops = []
    for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
        start = float(ev.start_ns)
        i = bisect.bisect_right(starts, start) - 1
        module = modules[i][2] if i >= 0 and start <= modules[i][1] else ""
        ops.append(DeviceOp(_op_name(ev.name), module, start,
                            float(ev.duration_ns), KERNEL_CALL in ev.name))
    return sorted(ops, key=lambda op: op.start_ns)


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[DeviceOp]]          # plane name -> ops
    spans: dict[str, list[tuple[float, float]]]  # span name -> (start, dur)


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory},"
                                f" found {len(paths)}")
    return paths[0]


def load(path: str, span_prefix: str = "bench.") -> Trace:
    """Device operations of every device plane, and the host spans whose
    names start with ``span_prefix``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list[DeviceOp]] = {}
    spans: dict[str, list[tuple[float, float]]] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = _device_ops(plane)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        spans.setdefault(ev.name, []).append(
                            (float(ev.start_ns), float(ev.duration_ns)))
    return Trace(devices, spans)


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """(start, end) pairs cut to [lo, hi]; empty ones dropped."""
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) pairs into disjoint sorted intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(ops, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which at least one operation ran."""
    iv = union(clip(((op.start_ns, op.start_ns + op.dur_ns) for op in ops),
                    lo, hi))
    return sum(e - s for s, e in iv)


def gaps(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi], longest first."""
    iv = union(clip(((op.start_ns, op.start_ns + op.dur_ns) for op in ops),
                    lo, hi))
    out, t = [], lo
    for s, e in iv:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def op_seconds(ops, lo: float, hi: float) -> dict[str, float]:
    """Device seconds per operation name, inside [lo, hi]."""
    out: dict[str, float] = {}
    for op in ops:
        s, e = max(op.start_ns, lo), min(op.start_ns + op.dur_ns, hi)
        if e > s:
            key = f"{op.module}/{op.name}" if op.module else op.name
            out[key] = out.get(key, 0.0) + (e - s) * 1e-9
    return out


def host_activity(spans: dict, start: float, end: float) -> str:
    """Name the host span that covers most of [start, end], or ``host``."""
    best, cover = "host", 0.0
    for name, items in spans.items():
        c = sum(max(0.0, min(s + d, end) - max(s, start)) for s, d in items)
        if c > cover:
            best, cover = name, c
    return best
