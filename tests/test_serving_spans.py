"""Spans and per-window stamps inside the serving path.

The engine stamps each window's ``window_log`` record (batcher wait, lane
wait, the model and kernel fetches, delivery) and each response with its
window; the stages, the refresh pass and the publish open ``muse.*`` host
spans on the profiler's clock.  The benchmark's readers of those stamps
(``bench/metrics/``) are checked here on hand-made records.
"""
import pathlib
import sys
import tempfile
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predictor import PredictorSpec
from repro.core.quantiles import StreamingQuantileEstimator
from repro.core.routing import Condition, Intent, RoutingTable, ScoringRule
from repro.core.transforms import QuantileMap
from repro.serving import (
    AsyncDispatchEngine,
    CalibrationController,
    FleetCalibrationController,
    MuseServer,
    RefreshPolicy,
    ServerConfig,
)
from repro.serving import spans
from repro.serving.tiering import TieringConfig
from repro.serving.types import ScoringRequest
from repro.serving.warmup import count_compiles

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

DIM = 8
STAMPS = ("arrival_wait_ms", "lane_wait_ms", "model_fetch_ms",
          "kernel_wait_ms", "respond_ms")


def _model(seed: int):
    w = np.random.default_rng(seed).normal(0, 1, DIM).astype(np.float32)
    return lambda x: jnp.asarray(
        1.0 / (1.0 + np.exp(-(np.asarray(x, np.float32) @ w))))


FACTORIES = {"m1": lambda: _model(1), "m2": lambda: _model(2)}


def _server(n_tenants: int = 2, n_levels: int = 64, **config) -> MuseServer:
    rules = tuple(ScoringRule(Condition(tenants=(f"t{i}",)), f"p{i}")
                  for i in range(n_tenants)) + \
        (ScoringRule(Condition(), "p0"),)
    server = MuseServer(RoutingTable(rules, (), version="v1"),
                        ServerConfig(refresh_alert_rate=0.05,
                                     refresh_rel_error=0.5, **config))
    for i in range(n_tenants):
        server.deploy(PredictorSpec(f"p{i}", ("m1", "m2"), (0.2, 0.4),
                                    (1.0, 1.0),
                                    QuantileMap.identity(n_levels)),
                      FACTORIES)
    return server


def _req(tenant: str, seed: int) -> ScoringRequest:
    rng = np.random.default_rng(seed)
    return ScoringRequest(intent=Intent(tenant=tenant),
                          features=rng.normal(0, 1, DIM).astype(np.float32))


def _ready_stream(server, tenant: str, pred: str, seed: int = 0) -> None:
    """A stream past the Eq. 5 gate whose refit validates."""
    est = StreamingQuantileEstimator(capacity=131072, seed=seed)
    est.update(np.random.default_rng(seed).uniform(0, 1, 5000))
    server._estimators[(tenant, pred)] = est


def _policy(n_levels: int = 64) -> RefreshPolicy:
    return RefreshPolicy(alert_rate=0.05, rel_error=0.5, n_levels=n_levels)


REF = np.linspace(0.0, 1.0, 64) ** 2


def _serve(engine, n: int, gap_s: float = 0.0):
    """Submit ``n`` requests; returns (futures, submit times, done times)."""
    futs, sent, done = [], [], {}
    for i in range(n):
        sent.append(time.perf_counter())
        fut = engine.submit(_req(f"t{i % 2}", i))
        fut.add_done_callback(
            lambda _, i=i: done.__setitem__(i, time.perf_counter()))
        futs.append(fut)
        if gap_s:
            time.sleep(gap_s)
    return futs, sent, done


class TestWindowStamps:
    def test_stamps_are_ordered_and_non_negative(self):
        engine = AsyncDispatchEngine(_server(), max_batch=4,
                                     max_wait_ms=1e9)
        engine.score_batch([_req("t0", 100 + i) for i in range(4)])  # warm
        engine.drain()
        engine.window_log.clear()
        futs, sent, done = _serve(engine, 24, gap_s=0.001)
        responses = [f.result(timeout=60) for f in futs]
        engine.close()
        assert len(engine.window_log) == 6
        window_of = {}
        for i, resp in enumerate(responses):
            window_of.setdefault(resp.window, []).append(i)
        for w in engine.window_log:
            assert all(w[k] >= 0.0 for k in STAMPS), w
            # the two fetches lie inside the model-start-to-result span
            assert w["model_fetch_ms"] + w["kernel_wait_ms"] \
                <= w["latency_ms"]
            members = window_of[w["seq"]]
            # arrival <= leaving the batcher <= model start <= result <=
            # the answer: each request's own submit-to-answer time holds
            # its share of the window's waits
            lived_ms = sum(done[i] - sent[i] for i in members) * 1e3
            assert lived_ms >= w["arrival_wait_ms"] + len(members) * (
                w["lane_wait_ms"] + w["latency_ms"])
            for i in members:
                assert (done[i] - sent[i]) * 1e3 >= \
                    w["lane_wait_ms"] + w["latency_ms"]

    def test_response_window_matches_its_record(self):
        engine = AsyncDispatchEngine(_server(), max_batch=4,
                                     max_wait_ms=1e9)
        futs, _, _ = _serve(engine, 18)
        engine.flush()
        responses = [f.result(timeout=60) for f in futs]
        engine.close()
        records = {w["seq"]: w for w in engine.window_log}
        assert len(records) == len(engine.window_log) == 5
        sizes: dict[int, int] = {}
        for resp in responses:
            assert resp.window in records
            assert resp.latency_ms == records[resp.window]["latency_ms"]
            sizes[resp.window] = sizes.get(resp.window, 0) + 1
        assert sizes == {s: w["size"] for s, w in records.items()}

    def test_outside_the_engine_a_response_has_no_window(self):
        resp = _server().score_batch([_req("t0", 1)])[0]
        assert resp.window == -1


@pytest.mark.parametrize("config", [
    {}, {"tenant_shards": 1},
    {"tiering": TieringConfig(hot_capacity=1, victim_capacity=2)}],
    ids=["dense", "sharded", "tiered"])
def test_apply_transforms_calls_back_once_its_kernel_is_queued(config):
    """On every topology the bound ``on_dispatch`` runs once per binding,
    after the kernel call and before the result's fetch is stamped; the
    shadow dispatches that follow in the same stage, and calls outside any
    binding, make none."""
    server = _server(2, **config)
    reqs = [_req(f"t{i % 2}", i) for i in range(6)]
    names = [f"p{i % 2}" for i in range(6)]
    raws = server.run_models(reqs, list(range(6)), names)
    record, calls = {}, []
    with spans.bind(record,
                    on_dispatch=lambda: calls.append(dict(record))):
        scores, _, _ = server.apply_transforms(raws, names)
        server.apply_transforms(raws, names)
    server.apply_transforms(raws, names)
    assert len(calls) == 1
    assert "kernel_wait_ms" not in calls[0]     # not fetched yet
    assert len(scores) == 6


def _run(windows):
    return types.SimpleNamespace(windows=windows)


WINDOWS = [
    {"seq": 0, "size": 2, "arrival_wait_ms": 6.0, "lane_wait_ms": 1.0,
     "respond_ms": 0.5, "model_fetch_ms": 4.0, "kernel_wait_ms": 2.0},
    {"seq": 1, "size": 6, "arrival_wait_ms": 30.0, "lane_wait_ms": 3.0,
     "respond_ms": 0.1, "model_fetch_ms": 8.0, "kernel_wait_ms": 1.0},
]


@pytest.mark.parametrize("metric, want", [
    ("batch_wait_ms", 36.0 / 8),                  # sum of waits / events
    ("lane_wait_ms", (2 * 1.0 + 6 * 3.0) / 8),    # weighted by window size
    ("respond_ms", (2 * 0.5 + 6 * 0.1) / 8),
    ("model_fetch_ms", (4.0 + 8.0) / 2),          # mean over windows
    ("kernel_wait_ms", (2.0 + 1.0) / 2),
])
def test_window_readers(metric, want):
    from bench.common import registry

    read = registry.metric_reader(metric)
    assert read(_run(WINDOWS)) == pytest.approx(want)
    # a record without the stamp (a program that lacks it) reads nothing
    unstamped = {"key": "k", "size": 4, "latency_ms": 9.0,
                 "bank_generation": 0}
    assert read(_run(WINDOWS + [unstamped])) is None
    assert read(_run([])) is None


PARENTS = {
    "muse.models": ("muse.models.features", "muse.models.forward",
                    "muse.models.fetch"),
    "muse.transforms": ("muse.transforms.bank", "muse.transforms.kernel",
                        "muse.transforms.fetch"),
    "muse.refresh": ("muse.refresh.scan", "muse.refresh.refit",
                     "muse.refresh.validate", "muse.refresh.publish"),
    "muse.refresh.publish": ("muse.publish",),
    "muse.publish": ("muse.publish.bank",),
}


def _inside(child, parents) -> bool:
    s, d = child
    return any(ps <= s and s + d <= ps + pd for ps, pd in parents)


def test_a_profiler_trace_holds_the_spans_under_bare_names():
    from bench.common import trace

    server = _server()
    _ready_stream(server, "t0", "p0")
    controller = CalibrationController(server, REF, _policy())
    engine = AsyncDispatchEngine(server, max_batch=64,
                                 max_wait_ms=5.0).start()
    engine.score_batch([_req("t0", 100 + i) for i in range(4)])  # warm
    engine.drain()
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            futs, _, _ = _serve(engine, 6)       # aged out by the poll tick
            for f in futs:
                f.result(timeout=60)
            result = engine.schedule_refresh(controller).result(timeout=60)
            engine.drain()
        finally:
            jax.profiler.stop_trace()
            engine.close()
        tr = trace.load(trace.find_xplane(tmp), span_prefix="muse.")
    assert result.refreshed
    names = set(PARENTS) | {c for cs in PARENTS.values() for c in cs} | {
        "muse.flush", "muse.respond", "muse.track"}
    # bare names: exactly the spans of the serving path, no arguments
    assert set(tr.spans) == names
    for parent, children in PARENTS.items():
        for child in children:
            for event in tr.spans[child]:
                assert _inside(event, tr.spans[parent]), (child, parent)


@pytest.mark.parametrize("fleet", [False, True])
def test_refresh_seconds_sum_to_no_more_than_the_pass(fleet):
    server = _server()
    _ready_stream(server, "t0", "p0")
    controller = FleetCalibrationController(
        [types.SimpleNamespace(replica_id="r0", server=server)], REF,
        _policy()) if fleet else CalibrationController(server, REF, _policy())
    t0 = time.perf_counter()
    result = controller.refresh_fleet()
    elapsed = time.perf_counter() - t0
    assert result.refreshed
    steps = (result.scan_seconds, result.refit_seconds,
             result.validate_seconds, result.publish_seconds)
    assert all(s > 0.0 for s in steps)
    assert sum(steps) <= elapsed


def test_planning_a_first_map_compiles_nothing():
    # a level count no other test uses, so no earlier compile hides one
    server = _server(n_levels=57)
    _ready_stream(server, "t0", "p0")
    controller = CalibrationController(server, REF, _policy(n_levels=57))
    snaps, _ = controller._snapshot(controller.scan())
    with count_compiles() as compiles:
        updates, _, _, _ = controller._plan(snaps)
    assert "p0" in updates
    assert compiles == []
