"""Async banked dispatch engine: the concurrency test campaign.

Proves the ROADMAP's "Async banked dispatch" + "Refresh under live
concurrency" items: the stage-pipelined engine preserves the synchronous
path's semantics (parity, 1:1 request/response mapping, per-key ordering,
per-dispatch latency), and the PR-2 atomic ``TransformBank`` swap survives
genuinely overlapping dispatches — a ``refresh_fleet`` publish landing
mid-stream never produces a torn read, and the bank generations any one
stream observes are monotone.

Threaded tests are marked ``concurrency`` (isolated from the fast ``-x``
pass via ``./test.sh --concurrency``); the end-to-end soak is additionally
``slow``.
"""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.predictor import PredictorSpec
from repro.core.quantiles import StreamingQuantileEstimator, required_sample_size
from repro.core.routing import Condition, Intent, RoutingTable, ScoringRule, ShadowRule
from repro.core.transforms import QuantileMap, score_pipeline
from repro.serving import (
    AsyncDispatchEngine,
    CalibrationController,
    MicroBatcher,
    MuseServer,
    RefreshPolicy,
    Replica,
    ReplicaSet,
    RollingUpdate,
    ServerBatcher,
    ServerConfig,
)
from repro.serving.types import ScoringRequest

DIM = 8
TOL = 1e-5
REF = np.linspace(0.0, 1.0, 64) ** 2  # smooth, front-loaded reference


def _linear_model(seed: int, dim: int = DIM):
    rng = np.random.default_rng(seed)
    w = rng.normal(0, 1, dim).astype(np.float32)

    def score(x):
        x = np.asarray(x, np.float32)
        return jnp.asarray(1.0 / (1.0 + np.exp(-(x @ w))))

    return score


FACTORIES = {f"m{i}": (lambda i=i: _linear_model(i)) for i in (1, 2, 3)}


def _req(tenant, seed):
    rng = np.random.default_rng(seed)
    return ScoringRequest(intent=Intent(tenant=tenant),
                          features=rng.normal(0, 1, DIM).astype(np.float32))


def _fleet(n_tenants=4, *, shadow=False, n_groups=1) -> MuseServer:
    """One predictor per tenant; predictors alternate between ``n_groups``
    model groups ({m1,m2} vs {m1,m2,m3}) so multi-key batching is real."""
    rules = tuple(ScoringRule(Condition(tenants=(f"t{i}",)), f"p{i}")
                  for i in range(n_tenants)) + \
        (ScoringRule(Condition(), "p0"),)
    shadows = (ShadowRule(Condition(tenants=("t0",)), ("p-sh",)),) \
        if shadow else ()
    server = MuseServer(
        RoutingTable(rules, shadows, version="v1"),
        ServerConfig(refresh_alert_rate=0.05, refresh_rel_error=0.5))
    for i in range(n_tenants):
        group = ("m1", "m2") if n_groups == 1 or i % 2 == 0 \
            else ("m1", "m2", "m3")
        betas = (0.2, 0.4) if len(group) == 2 else (0.2, 0.4, 0.1)
        server.deploy(PredictorSpec(f"p{i}", group, betas,
                                    (1.0,) * len(group),
                                    QuantileMap.identity(64)), FACTORIES)
    if shadow:
        server.deploy(PredictorSpec("p-sh", ("m1", "m2"), (0.5, 0.9),
                                    (2.0, 1.0), QuantileMap.identity(64)),
                      FACTORIES)
    return server


def _policy(**kw) -> RefreshPolicy:
    base = dict(alert_rate=0.05, rel_error=0.5, n_levels=64)
    base.update(kw)
    return RefreshPolicy(**base)


def _inject(server, tenant, pred, n=5000, seed=0):
    """A gate-passing estimator stream big enough that concurrent live
    tracking cannot move its distribution (refresh validation stays green)."""
    rng = np.random.default_rng(seed)
    est = StreamingQuantileEstimator(capacity=131072, seed=seed)
    est.update(rng.uniform(0, 1, n))
    server._estimators[(tenant, pred)] = est
    return est


def _pipeline_registry(server):
    return {n: p.pipeline for n, p in server.predictors.items()}


def _assert_consistent(responses, registry):
    """Every response's score must reproduce from the pipelines of the ONE
    generation it is stamped with — any torn read diverges."""
    for resp in responses:
        pipe = registry[resp.bank_generation][resp.predictor]
        want = float(score_pipeline(
            jnp.asarray(resp.raw_scores, jnp.float32), pipe.betas,
            pipe.weights, pipe.src_quantiles, pipe.ref_quantiles))
        assert resp.score == pytest.approx(want, abs=TOL), \
            (resp.request_id, resp.predictor, resp.bank_generation)


def _assert_monotone_generations(responses):
    """Per stream (tenant), in completion order, generations never step back."""
    seen: dict[str, int] = {}
    for resp in responses:
        tenant = resp.predictor  # one predictor per tenant in _fleet
        last = seen.get(tenant, -1)
        assert resp.bank_generation >= last, \
            (tenant, last, resp.bank_generation)
        seen[tenant] = resp.bank_generation


class TestEngineParity:
    def test_pipelined_scores_match_sync_path(self):
        sync, pipe = _fleet(4), _fleet(4)
        reqs = [_req(f"t{i % 4}", i) for i in range(40)]
        want = {r.request_id: r.score for r in sync.score_batch(reqs)}
        engine = AsyncDispatchEngine(pipe, max_batch=8, max_wait_ms=1e9)
        futs = [engine.submit(r) for r in reqs]
        out = engine.drain()
        engine.close()
        assert sorted(r.request_id for r in out) == \
            sorted(r.request_id for r in reqs)
        for resp in out:
            assert resp.score == pytest.approx(want[resp.request_id], abs=TOL)
            assert resp.bank_generation == 0
        assert all(f.done() for f in futs)
        # exactly one model-group call + one kernel dispatch per window —
        # the pipelining adds no extra executions
        assert pipe.metrics["model_group_calls"] == len(engine.window_log)
        assert pipe.metrics["kernel_dispatches"] == len(engine.window_log)
        assert pipe.metrics["requests"] == len(reqs)

    def test_score_batch_facade_preserves_request_order(self):
        sync, pipe = _fleet(3), _fleet(3)
        engine = AsyncDispatchEngine(pipe, max_batch=8, max_wait_ms=1e9)
        reqs = [_req(f"t{i % 3}", 50 + i) for i in range(20)]
        want = sync.score_batch(reqs)
        got = engine.score_batch(reqs)
        engine.close()
        assert [r.request_id for r in got] == [r.request_id for r in reqs]
        np.testing.assert_allclose([r.score for r in got],
                                   [r.score for r in want], atol=TOL)

    def test_self_scheduling_poll_flushes_aged_windows(self):
        server = _fleet(2)
        engine = AsyncDispatchEngine(server, max_batch=100,
                                     max_wait_ms=10.0).start()
        try:
            futs = [engine.submit(_req("t0", i)) for i in range(3)]
            # no manual poll()/flush()/drain(): the armed timer must flush
            # the aged-out window and resolve the futures on its own.
            # Generous bound: the 8-device lanes pay first-trace costs here
            resps = [f.result(timeout=60.0) for f in futs]
            assert [r.request_id for r in resps] == \
                [f.result().request_id for f in futs]
        finally:
            engine.close()

    def test_shadow_dedup_through_engine(self):
        server = _fleet(2, shadow=True)
        engine = AsyncDispatchEngine(server, max_batch=4, max_wait_ms=1e9)
        reqs = [_req("t0", 70 + i) for i in range(4)]
        out = engine.score_batch(reqs)
        engine.close()
        # live + shadow share {m1,m2}: ONE model-group call, TWO kernel
        # dispatches, raw scores reused by the shadow rows
        assert server.metrics["model_group_calls"] == 1
        assert server.metrics["kernel_dispatches"] == 2
        recs = server.sink.records("p-sh")
        assert len(recs) == 4
        by_id = {r.request_id: r for r in out}
        for rec in recs:
            assert rec.raw_scores == by_id[rec.request_id].raw_scores

    def test_latency_is_per_dispatch_not_cumulative(self):
        server = _fleet(2)
        engine = AsyncDispatchEngine(server, max_batch=16, max_wait_ms=1e9)
        engine.score_batch([_req("t0", i) for i in range(16)])  # warm/compile
        engine.take_completed()
        engine.window_log.clear()
        futs = [engine.submit(_req(f"t{i % 2}", 100 + i)) for i in range(48)]
        out = engine.drain()
        engine.close()
        assert len(out) == len(futs) == 48
        lats = [w["latency_ms"] for w in engine.window_log]
        assert len(lats) == 3 and all(l > 0 for l in lats)
        # a cumulative (stale-t0) latency would make the last window carry
        # roughly the sum of all three dispatch times
        assert max(lats) < 0.8 * sum(lats)
        # each response reports ITS window's dispatch latency
        per_window = {round(w["latency_ms"], 9): w["size"]
                      for w in engine.window_log}
        for resp in out:
            assert round(resp.latency_ms, 9) in per_window

    def test_submit_after_close_raises(self):
        engine = AsyncDispatchEngine(_fleet(1), max_batch=4, max_wait_ms=1e9)
        engine.close()
        with pytest.raises(RuntimeError):
            engine.submit(_req("t0", 0))


class TestOrderingProperties:
    """Property-style ordering invariants (hypothesis)."""

    @settings(max_examples=10, deadline=None)
    @given(st.integers(2, 6), st.integers(5, 40), st.integers(1, 4))
    def test_microbatcher_flushes_map_one_to_one_per_key(
            self, max_batch, n, n_keys):
        mb = MicroBatcher(max_batch=max_batch, max_wait_ms=1e9)
        submitted: dict[str, list[int]] = {}
        flushed: dict[str, list[int]] = {}
        key_of: dict[int, str] = {}
        for i in range(n):
            key = f"k{i % n_keys}"
            r = _req(key, i)
            key_of[r.request_id] = key
            submitted.setdefault(key, []).append(r.request_id)
            out = mb.add(key, r)
            if out is not None:
                assert len(out) == max_batch  # size trigger is exact
                for rr in out:
                    assert key_of[rr.request_id] == key
                flushed.setdefault(key, []).extend(
                    rr.request_id for rr in out)
        for key, batch in mb.flush_all():
            flushed.setdefault(key, []).extend(r.request_id for r in batch)
        # 1:1 per key AND submission order preserved within each key
        assert flushed == submitted
        assert mb.pending_count == 0

    @settings(max_examples=12, deadline=None)
    @given(st.floats(0.5, 50.0), st.floats(0.0, 100.0))
    def test_age_flush_fires_deterministically(self, wait_ms, advance_ms):
        if abs(advance_ms - wait_ms) < 1e-6:
            return  # exact-boundary draws are fp-ambiguous by construction
        t = [0.0]
        mb = MicroBatcher(max_batch=100, max_wait_ms=wait_ms,
                          clock=lambda: t[0])
        mb.add("a", _req("a", 0))
        t[0] = advance_ms / 1000.0
        expired = mb.expired()
        if advance_ms > wait_ms:
            assert len(expired) == 1 and len(expired[0][1]) == 1
            assert mb.pending_count == 0
        else:
            assert expired == [] and mb.pending_count == 1

    @settings(max_examples=5, deadline=None)
    @given(st.integers(1, 4), st.integers(6, 20))
    def test_server_batcher_responses_map_one_to_one(self, max_batch, n):
        server = _fleet(3)
        sb = ServerBatcher(server, MicroBatcher(max_batch=max_batch,
                                                max_wait_ms=1e9))
        reqs = [_req(f"t{i % 3}", i) for i in range(n)]
        got: dict[int, str] = {}

        def record(resps):
            for r in resps:
                assert r.request_id not in got  # no duplicates
                got[r.request_id] = r.predictor

        for r in reqs:
            out = sb.submit(r)
            if out is not None:
                record(out)
        record(sb.drain())
        assert sorted(got) == sorted(r.request_id for r in reqs)  # no drops
        for r in reqs:
            assert got[r.request_id] == f"p{int(r.intent.tenant[1:]) % 3}"

    def test_engine_preserves_per_key_submission_order(self):
        server = _fleet(6, n_groups=2)  # p0/2/4 on {m1,m2}; p1/3/5 on 3-group
        engine = AsyncDispatchEngine(server, max_batch=4, max_wait_ms=1e9)
        reqs = [_req(f"t{i % 6}", 200 + i) for i in range(48)]
        futs = [engine.submit(r) for r in reqs]
        out = engine.drain()
        engine.close()
        assert sorted(r.request_id for r in out) == \
            sorted(r.request_id for r in reqs)
        assert all(f.done() for f in futs)
        assert len(engine.window_log) == 48 // 4
        # within each model-group key, completion order == submission order
        group_of = {f"t{i}": ("even" if i % 2 == 0 else "odd")
                    for i in range(6)}
        submitted = {"even": [], "odd": []}
        for r in reqs:
            submitted[group_of[r.intent.tenant]].append(r.request_id)
        completed = {"even": [], "odd": []}
        for r in out:
            completed[group_of[f"t{r.predictor[1:]}"]].append(r.request_id)
        assert completed == submitted


@pytest.mark.concurrency
class TestReaderWriterEpochSafety:
    """The PR-2 atomic swap under REAL overlap: a traffic thread streams
    windows through the pipelined engine while a writer thread repeatedly
    publishes ``refresh_fleet`` generations."""

    def test_no_torn_reads_and_monotone_generations(self):
        n_t = 8
        server = _fleet(n_t)
        server.score_batch([_req(f"t{i % n_t}", 10_000 + i)
                            for i in range(16)])  # compile before the clock
        for i in range(n_t):
            _inject(server, f"t{i}", f"p{i}", seed=i)
        ctrl = CalibrationController(server, REF, _policy())
        registry = {server.bank_generation: _pipeline_registry(server)}
        # warm the refresh path before the clock starts: the FIRST pass pays
        # one-time trace/compile costs that would otherwise push every
        # in-loop publish past the traffic window
        res0 = ctrl.refresh_fleet()
        assert res0.generation == 1
        registry[1] = _pipeline_registry(server)
        engine = AsyncDispatchEngine(server, max_batch=16, max_wait_ms=1e9)
        reqs = [_req(f"t{i % n_t}", i) for i in range(1280)]

        stop = threading.Event()
        published: list[int] = []

        def writer():
            # repeated atomic publishes while windows are in flight; the
            # registry snapshot is safe: this thread is the only publisher
            while not stop.is_set() and len(published) < 60:
                res = ctrl.refresh_fleet()
                registry[res.generation] = _pipeline_registry(server)
                published.append(res.generation)
                time.sleep(0.002)

        def traffic():
            for r in reqs:
                engine.submit(r)

        wt = threading.Thread(target=writer)
        tt = threading.Thread(target=traffic)
        wt.start()
        tt.start()
        # bounded joins: a wedged thread must FAIL the test, not hang the
        # whole CI lane (the drain is already timeout-bounded)
        tt.join(timeout=300.0)
        assert not tt.is_alive(), "traffic thread wedged"
        responses = engine.drain(timeout=300.0)
        stop.set()
        wt.join(timeout=300.0)
        assert not wt.is_alive(), "refresh writer wedged"
        engine.close()

        # 1:1 delivery despite the concurrent publishes
        assert sorted(r.request_id for r in responses) == \
            sorted(r.request_id for r in reqs)
        # a real publish landed mid-stream...
        assert max(published) >= 3
        assert len({r.bank_generation for r in responses}) >= 2
        # ...yet every response is internally consistent with exactly ONE
        # generation (no torn reads), and per-stream generations are monotone
        _assert_consistent(responses, registry)
        _assert_monotone_generations(responses)

    def test_refresh_scheduled_from_engine_between_stage_boundaries(self):
        n_t = 4
        server = _fleet(n_t)
        server.score_batch([_req(f"t{i % n_t}", 20_000 + i)
                            for i in range(8)])  # compile before the clock
        for i in range(n_t):
            _inject(server, f"t{i}", f"p{i}", seed=10 + i)
        ctrl = CalibrationController(server, REF, _policy())
        registry = {server.bank_generation: _pipeline_registry(server)}
        engine = AsyncDispatchEngine(server, max_batch=8, max_wait_ms=1e9)

        futs, results = [], []
        for k in range(4):
            futs += [engine.submit(_req(f"t{i % n_t}", 500 * k + i))
                     for i in range(16)]
            res = engine.schedule_refresh(ctrl).result(timeout=120.0)
            results.append(res)
            registry[res.generation] = _pipeline_registry(server)
        responses = engine.drain(timeout=120.0)
        engine.close()

        # each scheduled pass ran at its own stage boundary: epochs are
        # strictly increasing and stamped into the results
        assert [r.epoch for r in results] == [1, 2, 3, 4]
        assert engine.epoch == 4
        assert [r.generation for r in results] == [1, 2, 3, 4]
        assert server.bank_generation == 4
        for res in results:
            assert len(res.refreshed) == n_t
        assert sorted(r.request_id for r in responses) == \
            sorted(f.result().request_id for f in futs)
        _assert_consistent(responses, registry)
        _assert_monotone_generations(responses)


# ---------------------------------------------------------------------------
# End-to-end soak: FraudWorld traffic through the engine across a rolling
# model promotion with auto-calibration (paper Sec. 3.1/3.2 + Fig. 5)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.concurrency
class TestEngineSoakScenario:
    """The sync-path PR-2 invariant, now through the pipelined engine AND a
    ``RollingUpdate`` promotion: three tenants serve continuously while the
    ensemble is extended ({m1,m2} -> {m1,m2,m3}) on a surged replica whose
    calibration refresh is scheduled at an engine stage boundary.  Zero
    request ids may be dropped or duplicated, and per-tenant alert rates at
    the fixed client threshold must hold the PR-2 bounds (±1.2pp of target,
    ≤2pp pre-vs-post drift)."""

    def test_soak_across_rolling_promotion_with_auto_calibration(self):
        from repro.experiments.fraud_world import DIM as FDIM
        from repro.experiments.fraud_world import FraudWorld, train_expert
        from repro.serving.drift import realized_alert_rate
        from repro.training.data import FraudEventStream, TenantProfile

        a = 0.02
        B = 320                    # window size == dispatch chunk (one shape)
        per_phase = 3200           # events per tenant per phase (> Eq.-5 gate)
        world = FraudWorld.build(n_experts=2, betas=(0.18, 0.18), seed=17,
                                 client_shift=0.3)
        recent = FraudEventStream(TenantProfile(
            "train-pool", fraud_rate=0.01, feature_shift=0.3, seed=303))
        world.experts["m3"] = train_expert(recent, "m3", 0.02, mask_seed=33)
        old_ens, new_ens = ("m1", "m2"), ("m1", "m2", "m3")

        tenants = [f"bank{i}" for i in range(3)]
        streams = {
            t: FraudEventStream(TenantProfile(
                t, fraud_rate=0.006 + 0.003 * i,
                feature_shift=0.25 + 0.06 * i, seed=500 + i))
            for i, t in enumerate(tenants)
        }
        policy = RefreshPolicy(alert_rate=a, rel_error=0.3)
        qm0 = world.coldstart_quantile_map(old_ens, n_trials=1)

        def build_server(version, ensemble, qms):
            rules = tuple(ScoringRule(Condition(tenants=(t,)), f"p-{t}")
                          for t in tenants)
            server = MuseServer(
                RoutingTable(rules, version=version),
                ServerConfig(refresh_alert_rate=a, refresh_rel_error=0.3))
            for t in tenants:
                server.deploy(
                    world.predictor_spec(f"p-{t}", ensemble, qms[t]),
                    world.model_factories())
            return server

        def make_engine(server):
            # wide facade timeout: the soak's first windows after a replica
            # surge pay fresh XLA traces, slower still on the 8-device lane
            return AsyncDispatchEngine(server, max_batch=B, max_wait_ms=50.0,
                                       facade_timeout_s=300.0).start()

        server_v1 = build_server("v1", old_ens, {t: qm0 for t in tenants})
        replica = Replica(0, server_v1, "v1", ready=True,
                          engine=make_engine(server_v1))
        rs = ReplicaSet([replica])

        submitted: list[int] = []
        collected: list = []

        def serve_phase(n_per_tenant):
            xs = {t: streams[t].sample(n_per_tenant)[0] for t in tenants}
            reqs = [
                ScoringRequest(intent=Intent(tenant=t), features=xs[t][i])
                for i in range(n_per_tenant) for t in tenants
            ]
            submitted.extend(r.request_id for r in reqs)
            phase: list = []
            for i in range(0, len(reqs), B):
                phase.extend(rs.dispatch(reqs[i:i + B]))
            collected.extend(phase)
            return phase

        def rates(resps):
            by_tenant: dict[str, list[float]] = {t: [] for t in tenants}
            for r in resps:
                by_tenant[r.predictor[2:]].append(r.score)
            return {t: realized_alert_rate(np.asarray(s),
                                           world.ref_quantiles, a)
                    for t, s in by_tenant.items()}

        # Phase A: cold-start maps serve through the engine while the live
        # streams fill past the Eq.-5 gate; refresh at a stage boundary.
        serve_phase(per_phase)
        ctrl_v1 = CalibrationController(server_v1, world.ref_quantiles,
                                        policy)
        res1 = replica.engine.schedule_refresh(ctrl_v1).result(timeout=300.0)
        assert len(res1.refreshed) == 3, [r.reasons for r in res1.reports]
        assert res1.epoch == 1
        assert server_v1.bank_generation == 1

        # Phase B: refreshed v1 fleet — the pre-update baseline.
        pre = rates(serve_phase(per_phase))
        for t in tenants:
            assert pre[t] == pytest.approx(a, abs=0.012), (t, pre)

        # Model promotion via rolling update: the surged replica ships the
        # new ensemble with the STALE tenant maps, fills its own streams,
        # and auto-refreshes at an engine stage boundary before the old
        # replica drains.
        def make_server_v2():
            stale = {t: server_v1.predictors[f"p-{t}"].pipeline
                     for t in tenants}
            qms = {t: QuantileMap(stale[t].src_quantiles,
                                  stale[t].ref_quantiles) for t in tenants}
            server = build_server("v2", new_ens, qms)
            # "streams fill" step of the lifecycle: the promoted replica
            # accumulates live-distribution samples past the Eq.-5 gate
            # before its calibrate step (same traffic mix, sync path)
            xs = {t: streams[t].sample(per_phase)[0] for t in tenants}
            fill = [
                ScoringRequest(intent=Intent(tenant=t), features=xs[t][i])
                for i in range(per_phase) for t in tenants
            ]
            for i in range(0, len(fill), B):
                server.score_batch(fill[i:i + B])
            return server

        update = RollingUpdate(
            rs, make_server_v2, "v2", schema_dim=FDIM,
            warmup_batch_sizes=(1, B),
            calibration_factory=lambda srv: CalibrationController(
                srv, world.ref_quantiles, policy),
            engine_factory=make_engine)
        for _ in update.steps():
            serve_phase(B // len(tenants))   # live traffic at every transition
        assert len(update.refreshes) == 1
        res2 = update.refreshes[0]
        assert len(res2.refreshed) == 3, [r.reasons for r in res2.reports]
        assert res2.epoch >= 1              # scheduled via the v2 engine
        assert [r.version for r in rs.replicas] == ["v2"]
        assert rs.replicas[0].server.bank_generation >= 1

        # Phase D: the invariant — post-update alert rates back on target
        # and stable vs the pre-update baseline, served by the refreshed v2
        # engine end to end.
        post_resps = serve_phase(per_phase)
        assert {r.routing_version for r in post_resps} == {"v2"}
        assert all(r.bank_generation >= 1 for r in post_resps)
        post = rates(post_resps)
        for t in tenants:
            assert post[t] == pytest.approx(a, abs=0.012), (t, post)
            assert abs(post[t] - pre[t]) <= 0.02, (t, pre, post)

        # zero dropped / duplicated request ids across the whole campaign
        got = sorted(r.request_id for r in collected)
        assert got == sorted(submitted)
        assert len(set(got)) == len(got)


@pytest.mark.concurrency
class TestPollTimerShutdown:
    """Regression home for the poll-timer shutdown race: ``_arm_poll`` used
    to check ``_running``/``_closed`` OUTSIDE the lock, so ``close()`` could
    cancel the already-fired timer and then lose to the re-arm — a live
    timer polling into shut-down executors.  An exception escaping
    ``poll()`` also silently killed the re-arm chain."""

    def test_close_vs_tick_stress(self):
        """Hammer start -> submit -> close with a sub-millisecond poll
        interval: after close() returns, the tick chain must be provably
        dead (no late re-arm) and no tick may ever have polled into the
        shut-down executors (that surfaces as a tick error)."""
        server = _fleet(2)
        for i in range(25):
            eng = AsyncDispatchEngine(server, max_batch=8, max_wait_ms=0.01,
                                      poll_interval_ms=0.05)
            ticks = []
            orig_poll = eng.poll
            eng.poll = lambda op=orig_poll, t=ticks: (t.append(1), op())[1]
            eng.start()
            # age-out windows so ticks genuinely launch into the executors
            for j in range(4):
                eng.submit(_req(f"t{j % 2}", 1000 * i + j))
            time.sleep(0.0002 * (i % 7))     # vary the close/tick phase
            eng.close()
            assert eng.tick_errors == 0, eng.errors
            assert eng.errors == []
            # the chain must be dead: tick count stabilizes after close
            time.sleep(0.002)
            n1 = len(ticks)
            time.sleep(0.01)                 # ~200 intervals of grace
            assert len(ticks) == n1

    def test_tick_failure_surfaces_in_metric_and_chain_survives(self):
        """An exception escaping poll() is counted (tick_errors + errors),
        and the timer chain keeps re-arming through failures."""
        server = _fleet(1)
        eng = AsyncDispatchEngine(server, poll_interval_ms=1.0)
        boom = RuntimeError("boom")
        calls = []

        def bad_expired():
            calls.append(1)
            raise boom

        eng.batcher.expired = bad_expired
        eng.start()
        deadline = time.monotonic() + 5.0
        while len(calls) < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(calls) >= 3               # chain survived the failures
        assert eng.tick_errors >= 3
        assert any(e is boom for _, e in eng.errors)
        del eng.batcher.expired              # restore for a clean close
        eng.close()
        assert eng.tick_errors >= 3
