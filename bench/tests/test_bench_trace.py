"""The reduction from a profiler trace to busy time, idle gaps and kernel
time: on hand-made intervals, and on a small trace recorded on a TPU v5e
(a 0.3 s window of ``tabular512.backlog``)."""
import pathlib

import pytest

from bench.common import trace

RECORDED = pathlib.Path(__file__).parent / "data" / "tabular512.xplane.pb"


def op(start, dur, name="fusion", module="jit_f"):
    return trace.DeviceOp(name, module, float(start), float(dur))


def test_busy_is_the_union_of_overlapping_operations():
    ops = [op(0, 10), op(5, 10), op(30, 5), op(90, 20)]
    # [0, 15) and [30, 35) and [90, 100) inside [0, 100)
    assert trace.busy_ns(ops, 0, 100) == 30
    assert trace.gaps(ops, 0, 100) == [(35, 90), (15, 30)]
    assert trace.busy_ns(ops, 0, 100) + sum(
        e - s for s, e in trace.gaps(ops, 0, 100)) == 100


def test_operation_seconds_by_module_and_name():
    ops = [op(0, 1e9, "custom-call", "jit_k"), op(2e9, 5e8, "custom-call",
                                                   "jit_k"),
           op(3e9, 1e9, "fusion.1", "jit_g")]
    got = trace.op_seconds(ops, 0, 3.5e9)
    assert got == {"jit_k/custom-call": 1.5, "jit_g/fusion.1": 0.5}


def test_host_activity_names_the_covering_span():
    spans = {"bench.track": [(0.0, 50.0)], "bench.run_models": [(40.0, 100.0)]}
    assert trace.host_activity(spans, 45, 120) == "bench.run_models"
    assert trace.host_activity(spans, 200, 300) == "host"


def test_recorded_trace():
    tr = trace.load(str(RECORDED))
    assert list(tr.devices) == ["/device:TPU:0"]
    (lo, dur), = tr.spans["bench.window"]
    ops = tr.devices["/device:TPU:0"]
    busy = trace.busy_ns(ops, lo, lo + dur)
    idle = sum(e - s for s, e in trace.gaps(ops, lo, lo + dur))
    assert 0 < busy < dur
    assert busy + idle == pytest.approx(dur)
    kernel = sum(s for name, s in trace.op_seconds(ops, lo, lo + dur).items()
                 if "score_pipeline_banked" in name)
    assert 0 < kernel < busy * 1e-9
    assert tr.spans["bench.apply_transforms"]
