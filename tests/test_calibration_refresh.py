"""Fleet-wide atomic calibration refresh: the full update-lifecycle campaign.

Covers the paper's headline invariant end-to-end (a model update never
shifts a tenant's alert rate once T^Q is refreshed), the control-plane
mechanics (Eq.-5 gating, candidate validation, atomic versioned publish),
property-style invariants of refreshed QuantileMaps, bank-cache staleness,
and the rollout promotion trigger.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.predictor import PredictorSpec
from repro.core.quantiles import (
    StreamingQuantileEstimator,
    batch_sample_quantiles,
    required_sample_size,
)
from repro.core.routing import (
    Condition,
    Intent,
    RoutingTable,
    ScoringRule,
    ShadowRule,
)
from repro.core.transforms import QuantileMap, score_pipeline
from repro.serving import (
    CalibrationController,
    MuseServer,
    RefreshPolicy,
    Replica,
    ReplicaSet,
    RollingUpdate,
    ServerConfig,
)
from repro.serving.drift import realized_alert_rate
from repro.serving.types import ScoringRequest

DIM = 8
TOL = 1e-5


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


def _fleet(n_tenants=3, *, shadow=False, fused=True) -> MuseServer:
    """One predictor per tenant over a shared {m1,m2} model group."""
    rules = tuple(ScoringRule(Condition(tenants=(f"t{i}",)), f"p{i}")
                  for i in range(n_tenants)) + \
        (ScoringRule(Condition(), "p0"),)
    shadows = (ShadowRule(Condition(tenants=("t0",)), ("p-sh",)),) \
        if shadow else ()
    server = MuseServer(
        RoutingTable(rules, shadows, version="v1"),
        ServerConfig(refresh_alert_rate=0.05, refresh_rel_error=0.5,
                     fused_kernel=fused))
    for i in range(n_tenants):
        server.deploy(PredictorSpec(f"p{i}", ("m1", "m2"), (0.2, 0.4),
                                    (1.0, 1.0), QuantileMap.identity(64)),
                      FACTORIES)
    if shadow:
        server.deploy(PredictorSpec("p-sh", ("m1", "m2"), (0.5, 0.9),
                                    (2.0, 1.0), QuantileMap.identity(64)),
                      FACTORIES)
    return server


def _policy(**kw) -> RefreshPolicy:
    base = dict(alert_rate=0.05, rel_error=0.5, n_levels=64)
    base.update(kw)
    return RefreshPolicy(**base)


def _inject(server, tenant, pred, samples, seed=0):
    est = StreamingQuantileEstimator(capacity=65536, seed=seed)
    est.update(samples)
    server._estimators[(tenant, pred)] = est
    return est


REF = np.linspace(0.0, 1.0, 64) ** 2  # smooth, front-loaded reference


class TestRefreshFleetControlPlane:
    def test_eq5_gate_blocks_thin_streams(self):
        server = _fleet(2)
        rng = np.random.default_rng(0)
        gate = required_sample_size(0.05, 0.5)
        _inject(server, "t0", "p0", rng.uniform(0, 1, gate + 10))
        _inject(server, "t1", "p1", rng.uniform(0, 1, gate // 4))
        ctrl = CalibrationController(server, REF, _policy())
        res = ctrl.refresh_fleet()
        assert [(r.tenant, r.predictor) for r in res.refreshed] == [("t0", "p0")]
        assert [(r.tenant, r.predictor) for r in res.not_ready] == [("t1", "p1")]
        assert res.not_ready[0].reasons == ("eq5_gate",)
        assert server.bank_generation == res.generation == 1

    def test_no_ready_streams_is_a_noop_publish(self):
        server = _fleet(1)
        ctrl = CalibrationController(server, REF, _policy())
        res = ctrl.refresh_fleet()
        assert res.reports == ()
        assert res.generation == server.bank_generation == 0

    def test_degenerate_stream_rejected_others_ship(self):
        server = _fleet(2)
        rng = np.random.default_rng(1)
        gate = required_sample_size(0.05, 0.5)
        _inject(server, "t0", "p0", rng.uniform(0, 1, gate + 50))
        _inject(server, "t1", "p1", np.full(gate + 50, 0.37))  # poisoned
        old_qm_p1 = server.predictors["p1"].pipeline.src_quantiles
        ctrl = CalibrationController(server, REF, _policy())
        res = ctrl.refresh_fleet()
        assert [(r.tenant, r.predictor) for r in res.refreshed] == [("t0", "p0")]
        (rej,) = res.rejected
        assert (rej.tenant, rej.predictor) == ("t1", "p1")
        assert "degenerate_support" in rej.reasons
        # the rejected predictor keeps serving its OLD map
        assert server.predictors["p1"].pipeline.src_quantiles is old_qm_p1
        assert server.bank_generation == 1  # healthy stream still published

    def test_poisoned_tenant_vetoes_shared_predictor(self):
        """Two tenants share one predictor; the pooled candidate must
        validate against EVERY tenant stream before it ships."""
        server = _fleet(1)  # p0 serves t0 and (catch-all) t9
        rng = np.random.default_rng(2)
        gate = required_sample_size(0.05, 0.5)
        _inject(server, "t0", "p0", rng.uniform(0, 1, gate + 50))
        _inject(server, "t9", "p0", np.full(gate + 50, 0.99), seed=1)
        old = server.predictors["p0"].pipeline.src_quantiles
        res = CalibrationController(server, REF, _policy()).refresh_fleet()
        assert res.refreshed == []
        assert {r.status for r in res.reports} == {"rejected"}
        assert server.predictors["p0"].pipeline.src_quantiles is old
        assert server.bank_generation == 0  # nothing published

    def test_healthy_tenant_reported_as_peer_vetoed(self):
        """When the shared predictor is withheld because ONE tenant stream
        fails, streams that passed individually are reported as
        'vetoed_by_peer' — not as their own validation failure."""
        server = _fleet(1)
        rng = np.random.default_rng(12)
        gate = required_sample_size(0.05, 0.5)
        _inject(server, "t0", "p0", rng.uniform(0, 0.5, gate + 50))
        # t9 matches t0's history, but its RECENT traffic shifted outside
        # the pooled support — only t9's own recency check fails
        est = StreamingQuantileEstimator(capacity=256, seed=4,
                                         recent_capacity=2048)
        est.update(rng.uniform(0.0, 0.5, 500_000))
        est.update(rng.uniform(0.8, 0.95, 2048))
        server._estimators[("t9", "p0")] = est
        res = CalibrationController(server, REF, _policy()).refresh_fleet()
        assert res.refreshed == []
        by_tenant = {r.tenant: r for r in res.reports}
        assert by_tenant["t0"].reasons == ("vetoed_by_peer",)
        assert "support_coverage_recent" in by_tenant["t9"].reasons
        assert server.bank_generation == 0

    def test_recent_shift_fails_support_coverage(self):
        """A shift that happens AFTER the reservoir filled is nearly
        invisible to the uniform reservoir but dominates the recent window:
        the candidate must be rejected, not published."""
        server = _fleet(2)
        rng = np.random.default_rng(8)
        gate = required_sample_size(0.05, 0.5)
        _inject(server, "t0", "p0", rng.uniform(0, 1, gate + 50))
        # t1: long history on [0, 0.4], then a hard shift to [0.7, 0.9]
        est = StreamingQuantileEstimator(capacity=256, seed=3,
                                         recent_capacity=2048)
        est.update(rng.uniform(0.0, 0.4, 500_000))
        est.update(rng.uniform(0.7, 0.9, 2048))
        server._estimators[("t1", "p1")] = est
        old = server.predictors["p1"].pipeline.src_quantiles
        res = CalibrationController(server, REF, _policy()).refresh_fleet()
        (rej,) = [r for r in res.rejected if r.tenant == "t1"]
        assert "support_coverage_recent" in rej.reasons
        assert server.predictors["p1"].pipeline.src_quantiles is old
        assert [(r.tenant, r.predictor) for r in res.refreshed] == [("t0", "p0")]

    def test_refresh_only_filter_limits_the_pass(self):
        server = _fleet(2)
        rng = np.random.default_rng(9)
        gate = required_sample_size(0.05, 0.5)
        for i in range(2):
            _inject(server, f"t{i}", f"p{i}",
                    rng.uniform(0, 1, gate + 50), seed=i)
        ctrl = CalibrationController(server, REF, _policy())
        res = ctrl.refresh_fleet(only={("t1", "p1")})
        assert [(r.tenant, r.predictor) for r in res.refreshed] == [("t1", "p1")]
        assert len(res.reports) == 1  # t0 untouched, not even reported

    def test_only_filter_still_validates_predictor_peers(self):
        """refresh_fleet(only={alarmed tenant}) must not bypass the peer
        veto: every live stream of the touched predictor joins the pooled
        refit and validation."""
        server = _fleet(1)  # p0 serves t0 and catch-all t9
        rng = np.random.default_rng(11)
        gate = required_sample_size(0.05, 0.5)
        _inject(server, "t0", "p0", rng.uniform(0, 1, gate + 50))
        _inject(server, "t9", "p0", np.full(gate + 50, 0.42), seed=1)
        res = CalibrationController(server, REF, _policy()).refresh_fleet(
            only={("t0", "p0")})
        assert res.refreshed == []          # poisoned peer vetoed the publish
        assert {r.tenant for r in res.reports} == {"t0", "t9"}
        assert server.bank_generation == 0

    def test_decommission_purges_estimator_streams(self):
        """A predictor redeployed under a decommissioned name must NOT be
        refit from the dead model's score stream."""
        server = _fleet(2)
        rng = np.random.default_rng(10)
        gate = required_sample_size(0.05, 0.5)
        _inject(server, "t0", "p0", rng.uniform(0, 1, gate + 50))
        server.decommission("p0")
        assert ("t0", "p0") not in server.estimator_streams()
        server.deploy(PredictorSpec("p0", ("m1", "m2"), (0.2, 0.4),
                                    (1.0, 1.0), QuantileMap.identity(64)),
                      FACTORIES)
        assert server.estimator_streams() == {}
        res = CalibrationController(server, REF, _policy()).refresh_fleet()
        assert res.reports == ()  # no stale stream resurfaced

    def test_vectorized_refit_matches_per_stream_quantiles(self):
        rng = np.random.default_rng(3)
        streams = [rng.beta(0.5 + i, 6.0, 500 + 100 * i) for i in range(7)]
        levels = np.linspace(0, 1, 33)
        got = batch_sample_quantiles(streams, levels)
        want = np.stack([np.maximum.accumulate(np.quantile(s, levels))
                         for s in streams])
        np.testing.assert_allclose(got, want, atol=1e-12)

    def test_refresh_aligns_live_streams_to_reference(self):
        """Post-refresh, each tenant's served distribution matches R: the
        realized alert rate at the client threshold hits the target."""
        server = _fleet(3)
        rng = np.random.default_rng(4)
        reqs = [_req(f"t{i % 3}", 1000 + i) for i in range(512)]
        for i in range(0, 512, 128):
            server.score_batch(reqs[i:i + 128])
        # streams were fed by real traffic; force the gate open by topping
        # them up from the same live distribution (the estimators hold the
        # T^Q INPUT aggregate, reproduced here through the bank oracle)
        for (t, p), est in list(server.estimator_streams().items()):
            vals = est.values()
            est.update(rng.choice(vals, 2000))
        ctrl = CalibrationController(server, REF, _policy(alert_rate=0.05))
        res = ctrl.refresh_fleet()
        assert len(res.refreshed) == 3
        scores = [r.score for r in server.score_batch(reqs)]
        rate = realized_alert_rate(np.asarray(scores), REF, 0.05)
        assert rate == pytest.approx(0.05, abs=0.02)


class TestAtomicPublish:
    def test_generation_bumps_and_banks_are_immutable(self):
        server = _fleet(3)
        server.score_batch([_req(f"t{i}", i) for i in range(3)])  # warm bank
        (key,) = server._banks
        old_entry = server._banks[key]
        old_src = np.asarray(old_entry.bank.src_quantiles).copy()
        gate = required_sample_size(0.05, 0.5)
        rng = np.random.default_rng(5)
        for i in range(3):
            _inject(server, f"t{i}", f"p{i}",
                    rng.uniform(0, 1, gate + 50), seed=i)
        res = CalibrationController(server, REF, _policy()).refresh_fleet()
        new_entry = server._banks[key]
        assert new_entry is not old_entry
        assert new_entry.bank.generation == res.generation == 1
        assert old_entry.bank.generation == 0
        # the old bank object an in-flight dispatch may hold is untouched
        np.testing.assert_array_equal(
            np.asarray(old_entry.bank.src_quantiles), old_src)
        assert not np.allclose(np.asarray(new_entry.bank.src_quantiles),
                               old_src)

    def test_in_flight_dispatch_scores_on_old_generation(self):
        """A dispatch that snapshotted the old bank keeps its parameters even
        after a publish lands — scoring through the captured bank must
        reproduce pre-publish scores exactly."""
        server = _fleet(2)
        reqs = [_req("t0", 11), _req("t1", 12)]
        pre = [r.score for r in server.score_batch(reqs)]
        (key,) = server._banks
        captured = server._banks[key].bank  # what an in-flight window holds
        gate = required_sample_size(0.05, 0.5)
        rng = np.random.default_rng(6)
        for i in range(2):
            _inject(server, f"t{i}", f"p{i}",
                    rng.uniform(0, 1, gate + 50), seed=i)
        CalibrationController(server, REF, _policy()).refresh_fleet()
        post = [r.score for r in server.score_batch(reqs)]
        assert pre != pytest.approx(post, abs=1e-9)  # publish changed serving
        # replay the in-flight window through the captured old bank
        raws = np.asarray([reqs[0].features, reqs[1].features], np.float32)
        pred0 = server.predictors["p0"]
        raw_scores = np.stack(
            [np.asarray(h.score_fn(raws)) for h in pred0._handles], axis=-1)
        replay = np.asarray(captured(jnp.asarray(raw_scores, jnp.float32),
                                     jnp.asarray([0, 1], jnp.int32)))
        np.testing.assert_allclose(replay, pre, atol=TOL)

    def test_publish_after_in_place_redeploy_rebuilds_bank(self):
        """A predictor redeployed under an existing name leaves a stale
        cached bank; a later publish touching a bank-mate must fully rebuild
        that bank from the CURRENT pipelines, not patch-and-repin the stale
        rows (which would serve the dead pipeline's T^C/A forever)."""
        server = _fleet(2)
        reqs = [_req("t0", 41), _req("t1", 42)]
        server.score_batch(reqs)          # warm the shared (p0,p1) bank
        server.deploy(PredictorSpec("p1", ("m1", "m2"), (0.9, 0.7),
                                    (2.0, 1.0), QuantileMap.identity(64)),
                      FACTORIES)          # in-place redeploy, new T^C/A
        qs = jnp.linspace(0, 1, 64)
        server.publish_quantile_maps({"p0": QuantileMap(qs, qs ** 2)})
        resps = server.score_batch(reqs)
        for resp, name in zip(resps, ["p0", "p1"]):
            pipe = server.predictors[name].pipeline
            want = float(score_pipeline(
                jnp.asarray(resp.raw_scores, jnp.float32), pipe.betas,
                pipe.weights, pipe.src_quantiles, pipe.ref_quantiles))
            assert resp.score == pytest.approx(want, abs=TOL), name

    def test_recent_ring_keeps_newest_after_bulk_write(self):
        """The recency window must hold the newest samples even when a bulk
        update repositioned the ring (regression: pointer misalignment kept
        old samples and evicted newer ones)."""
        est = StreamingQuantileEstimator(capacity=64, seed=0,
                                         recent_capacity=8)
        est.update(np.arange(20.0))
        est.update(np.array([100.0, 101.0]))
        assert set(est.recent()) == {14.0, 15.0, 16.0, 17.0, 18.0, 19.0,
                                     100.0, 101.0}
        est.update(np.array([200.0]))
        assert 200.0 in est.recent() and 14.0 not in est.recent()

    def test_publish_many_predictors_is_one_generation(self):
        server = _fleet(3)
        qs = jnp.linspace(0, 1, 64)
        updates = {f"p{i}": QuantileMap(qs, qs ** (i + 2)) for i in range(3)}
        gen = server.publish_quantile_maps(updates)
        assert gen == server.bank_generation == 1
        assert server.publish_quantile_maps({}) == 1  # empty = no bump
        with pytest.raises(KeyError):
            server.publish_quantile_maps({"ghost": QuantileMap(qs, qs)})


class TestBankCacheStaleness:
    def test_swap_then_score_never_serves_old_params(self):
        server = _fleet(2, shadow=True)
        reqs = [_req("t0", 21), _req("t1", 22)]
        server.score_batch(reqs)          # warm live + shadow banks
        qs = jnp.linspace(0, 1, 64)
        server.swap_transformation("p0", QuantileMap(qs, qs ** 4))
        server.swap_transformation("p-sh", QuantileMap(qs, jnp.sqrt(qs)))
        resps = server.score_batch(reqs)
        # oracle from the CURRENT pipelines: any staleness diverges
        for resp, (name, row) in zip(resps, [("p0", 0), ("p1", 1)]):
            pipe = server.predictors[name].pipeline
            want = float(score_pipeline(
                jnp.asarray(resp.raw_scores, jnp.float32), pipe.betas,
                pipe.weights, pipe.src_quantiles, pipe.ref_quantiles))
            assert resp.score == pytest.approx(want, abs=TOL)
        # interleaved shadow dispatch also sees the swapped shadow T^Q
        rec = server.sink.records("p-sh")[-1]
        pipe = server.predictors["p-sh"].pipeline
        want = float(score_pipeline(
            jnp.asarray(rec.raw_scores, jnp.float32), pipe.betas,
            pipe.weights, pipe.src_quantiles, pipe.ref_quantiles))
        assert rec.score == pytest.approx(want, abs=TOL)

    def test_fleet_publish_then_score_serves_new_params(self):
        server = _fleet(2)
        server.score_batch([_req("t0", 31), _req("t1", 32)])
        gate = required_sample_size(0.05, 0.5)
        rng = np.random.default_rng(7)
        for i in range(2):
            _inject(server, f"t{i}", f"p{i}",
                    rng.uniform(0, 1, gate + 50), seed=i)
        CalibrationController(server, REF, _policy()).refresh_fleet()
        resps = server.score_batch([_req("t0", 31), _req("t1", 32)])
        for resp, name in zip(resps, ["p0", "p1"]):
            pipe = server.predictors[name].pipeline
            want = float(score_pipeline(
                jnp.asarray(resp.raw_scores, jnp.float32), pipe.betas,
                pipe.weights, pipe.src_quantiles, pipe.ref_quantiles))
            assert resp.score == pytest.approx(want, abs=TOL)


class TestRefreshedMapProperties:
    """Property-style invariants of refitted maps (hypothesis)."""

    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 10_000), st.floats(0.4, 3.0), st.floats(2.0, 9.0))
    def test_refit_is_monotone_non_decreasing(self, seed, a, b):
        rng = np.random.default_rng(seed)
        src = batch_sample_quantiles(
            [rng.beta(a, b, 2000)], np.linspace(0, 1, len(REF)))[0]
        assert (np.diff(src) >= -1e-12).all()
        qm = QuantileMap(jnp.asarray(src, jnp.float32),
                         jnp.asarray(REF, jnp.float32))
        x = jnp.linspace(0, 1, 257)
        y = np.asarray(qm(x))
        assert (np.diff(y) >= -1e-6).all()   # rank preservation (ROC claim)

    @settings(max_examples=6, deadline=None)
    @given(st.integers(0, 10_000), st.sampled_from([1.0, 2.0]))
    def test_refit_on_reference_traffic_is_identity(self, seed, gamma):
        """T^Q fitted on a stream ALREADY distributed as R must be ~id."""
        rng = np.random.default_rng(seed)
        levels = np.linspace(0, 1, 129)
        ref = levels ** gamma
        samples = np.interp(rng.uniform(0, 1, 6000), levels, ref)
        src = batch_sample_quantiles([samples], levels)[0]
        qm = QuantileMap(jnp.asarray(src, jnp.float32),
                         jnp.asarray(ref, jnp.float32))
        x = np.interp(np.linspace(0.05, 0.95, 61), levels, ref)  # interior
        y = np.asarray(qm(jnp.asarray(x, jnp.float32)))
        np.testing.assert_allclose(y, x, atol=0.06)

    @settings(max_examples=4, deadline=None)
    @given(st.integers(0, 10_000))
    def test_banked_kernel_oracle_parity_after_mid_stream_swap(self, seed):
        """Fused kernel == pure-jnp banked oracle across an atomic swap."""
        fused = _fleet(3, fused=True)
        plain = _fleet(3, fused=False)
        rng = np.random.default_rng(seed)
        reqs = [_req(f"t{i % 3}", int(rng.integers(1 << 30))) for i in range(12)]
        np.testing.assert_allclose(
            [r.score for r in fused.score_batch(reqs)],
            [r.score for r in plain.score_batch(reqs)], atol=TOL)
        qs = jnp.linspace(0, 1, 64)
        updates = {"p0": QuantileMap(qs, qs ** 3),
                   "p2": QuantileMap(qs, jnp.sqrt(qs))}
        assert fused.publish_quantile_maps(updates) == 1
        assert plain.publish_quantile_maps(updates) == 1
        np.testing.assert_allclose(
            [r.score for r in fused.score_batch(reqs)],
            [r.score for r in plain.score_batch(reqs)], atol=TOL)


class TestDriftTickThroughController:
    def test_tick_refreshes_unalarmed_peer_without_crashing(self):
        """One alarmed tenant on a shared predictor widens to its peer: the
        tick must publish once, reset BOTH monitors, and report the peer
        with its own (sub-alarm) PSI — regression for a KeyError on peers
        absent from the alarmed set."""
        from repro.serving.drift import CalibrationRefreshController

        server = _fleet(1)  # p0 serves t0 and catch-all t9
        rng = np.random.default_rng(13)
        gate = required_sample_size(0.05, 0.5)
        _inject(server, "t0", "p0", rng.uniform(0, 1, gate + 50))
        _inject(server, "t9", "p0", rng.uniform(0, 1, gate + 50), seed=1)
        ctl = CalibrationRefreshController(server, REF, window=2000)
        # t0's served distribution drifted hard; t9's matches R
        ctl.observe("t0", "p0", np.full(2000, 0.97))
        levels = np.linspace(0, 1, len(REF))
        ctl.observe("t9", "p0", np.interp(rng.uniform(0, 1, 2000),
                                          levels, REF))
        assert ctl._monitors[("t0", "p0")].drifted()
        assert not ctl._monitors[("t9", "p0")].drifted()
        done = ctl.tick()
        keys = {(t, p) for t, p, _ in done}
        assert keys == {("t0", "p0"), ("t9", "p0")}
        assert server.bank_generation == 1  # one atomic publish for both
        psis = {(t, p): v for t, p, v in done}
        assert psis[("t0", "p0")] > 0.25      # the alarm
        assert psis[("t9", "p0")] < 0.25      # peer reported sub-alarm
        for key in keys:                      # both windows judged fresh
            assert ctl._monitors[key].count == 0

    def test_rejected_alarm_is_recorded_and_backs_off(self):
        """A poisoned stream that trips the alarm but fails validation must
        be visible in `rejections` and must NOT re-run the refit on every
        subsequent tick (cooldown)."""
        from repro.serving.drift import CalibrationRefreshController

        server = _fleet(1)
        gate = required_sample_size(0.05, 0.5)
        _inject(server, "t0", "p0", np.full(gate + 50, 0.5))  # degenerate
        ctl = CalibrationRefreshController(server, REF, window=2000,
                                           reject_cooldown=3)
        ctl.observe("t0", "p0", np.full(2000, 0.97))  # drifted hard
        assert ctl.tick() == []
        assert server.bank_generation == 0
        assert len(ctl.rejections) == 1
        tenant, pred, reasons = ctl.rejections[0]
        assert (tenant, pred) == ("t0", "p0")
        assert "degenerate_support" in reasons
        # cooldown: the next ticks skip the stream entirely
        for _ in range(2):
            assert ctl.tick() == []
        assert len(ctl.rejections) == 1  # no repeated refit/rejection

    def test_not_ready_peer_outside_support_vetoes_publish(self):
        """A below-gate peer stream is still recalibrated by a publish; if
        its traffic falls outside the candidate's support, the predictor
        must be withheld (support-coverage vote for not-ready peers)."""
        server = _fleet(1)  # p0 serves t0 and catch-all t9
        rng = np.random.default_rng(14)
        gate = required_sample_size(0.05, 0.5)
        _inject(server, "t0", "p0", rng.uniform(0, 0.5, gate + 50))
        _inject(server, "t9", "p0", rng.uniform(0.8, 1.0, gate // 4), seed=1)
        res = CalibrationController(server, REF, _policy()).refresh_fleet()
        assert res.refreshed == []
        assert server.bank_generation == 0
        by_tenant = {r.tenant: r for r in res.reports}
        assert by_tenant["t9"].status == "not_ready"
        assert "support_coverage" in by_tenant["t9"].reasons
        assert by_tenant["t0"].reasons == ("vetoed_by_peer",)


class TestRolloutPromotionTrigger:
    def test_promotion_triggers_fleet_refresh(self):
        gate = required_sample_size(0.05, 0.5)

        def make_server(version="v2"):
            s = _fleet(2)
            s.routing = RoutingTable(s.routing.scoring_rules,
                                     s.routing.shadow_rules, version=version)
            rng = np.random.default_rng(42)
            for i in range(2):
                _inject(s, f"t{i}", f"p{i}",
                        rng.uniform(0, 1, gate + 50), seed=i)
            return s

        replicas = [Replica(i, make_server("v1"), "v1", ready=True)
                    for i in range(2)]
        rs = ReplicaSet(replicas)
        update = RollingUpdate(
            rs, make_server, "v2", schema_dim=DIM,
            warmup_batch_sizes=(1, 2),
            calibration_factory=lambda srv: CalibrationController(
                srv, REF, _policy()))

        def traffic():
            i = 0
            while True:
                yield [_req("t0", i), _req("t1", i + 1)]
                i += 2

        update.run_with_traffic(traffic(), batches_per_transition=1)
        # every promoted replica ran a fleet refresh and published atomically
        assert len(update.refreshes) == 2
        for res in update.refreshes:
            assert len(res.refreshed) == 2
            assert res.generation >= 1
        for r in rs.replicas:
            assert r.server.bank_generation >= 1
        assert sum(e.kind == "calibrate" for e in update.events) == 2


# ---------------------------------------------------------------------------
# End-to-end scenario: live fleet through a model update (paper Sec. 3.1/3.2)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestModelUpdateScenario:
    """The headline invariant, end to end: three tenants with distinct
    distributions serve live traffic, the ensemble is retrained/extended
    ({m1,m2} -> {m1,m2,m3}) and promoted with its STALE T^Q, then one
    ``refresh_fleet()`` pass refits every tenant from the live stream and
    publishes atomically — per-tenant alert rates at the fixed client
    threshold must match the target before AND after the model update."""

    def test_alert_rates_stable_across_model_update(self):
        from repro.experiments.fraud_world import FraudWorld, train_expert
        from repro.training.data import FraudEventStream, TenantProfile

        # Target alert rate a=2% with Eq.-5 delta=0.3: the gate needs ~2.1k
        # samples and guarantees the realized rate within ±30% relative
        # (95% conf.).  4k samples/phase keeps both the fit and the
        # measurement inside an abs tolerance of 1.2pp with margin.
        a = 0.02
        batch, per_phase = 500, 4000
        world = FraudWorld.build(n_experts=2, betas=(0.18, 0.18), seed=17,
                                 client_shift=0.3)
        # the model update: a third expert trained on recent shifted traffic
        recent = FraudEventStream(TenantProfile(
            "train-pool", fraud_rate=0.01, feature_shift=0.3, seed=303))
        world.experts["m3"] = train_expert(recent, "m3", 0.02, mask_seed=33)
        old, new = ("m1", "m2"), ("m1", "m2", "m3")

        tenants = [f"bank{i}" for i in range(3)]
        streams = {
            t: FraudEventStream(TenantProfile(
                t, fraud_rate=0.006 + 0.003 * i,
                feature_shift=0.25 + 0.06 * i, seed=500 + i))
            for i, t in enumerate(tenants)
        }
        qm0 = world.coldstart_quantile_map(old, n_trials=1)
        rules = tuple(ScoringRule(Condition(tenants=(t,)), f"p-old-{t}")
                      for t in tenants)
        server = MuseServer(RoutingTable(rules, version="v1"),
                            ServerConfig(refresh_alert_rate=a,
                                         refresh_rel_error=0.3))
        for t in tenants:
            server.deploy(world.predictor_spec(f"p-old-{t}", old, qm0),
                          world.model_factories())
        ctrl = CalibrationController(
            server, world.ref_quantiles,
            RefreshPolicy(alert_rate=a, rel_error=0.3))

        def serve_phase(n_per_tenant) -> dict[str, np.ndarray]:
            scores: dict[str, list[float]] = {t: [] for t in tenants}
            for t in tenants:
                x, _ = streams[t].sample(n_per_tenant)
                for i in range(0, n_per_tenant, batch):
                    resps = server.score_batch([
                        ScoringRequest(intent=Intent(tenant=t), features=f)
                        for f in x[i:i + batch]
                    ])
                    scores[t].extend(r.score for r in resps)
            return {t: np.asarray(s) for t, s in scores.items()}

        def rates(scores: dict[str, np.ndarray]) -> dict[str, float]:
            return {t: realized_alert_rate(s, world.ref_quantiles, a)
                    for t, s in scores.items()}

        # Phase A: cold-start maps serve while live streams accumulate past
        # the Eq.-5 gate; then the first fleet refresh customizes every T^Q.
        serve_phase(per_phase)
        res1 = ctrl.refresh_fleet()
        assert len(res1.refreshed) == 3, [r.reasons for r in res1.reports]
        assert server.bank_generation == 1

        # Phase B: refreshed fleet — the pre-update baseline alert rates.
        pre = rates(serve_phase(per_phase))
        for t in tenants:
            assert pre[t] == pytest.approx(a, abs=0.012), (t, pre)

        # Model promotion: new ensemble ships with the OLD tenant maps (the
        # paper's p1.5 stale state) — transparent routing swap, zero model
        # re-provisioning for m1/m2.
        prov_before = server.pool.provision_events
        for t in tenants:
            stale = server.predictors[f"p-old-{t}"].pipeline
            server.deploy(world.predictor_spec(
                f"p-new-{t}", new,
                QuantileMap(stale.src_quantiles, stale.ref_quantiles)),
                world.model_factories())
        assert server.pool.provision_events == prov_before + 1  # only m3
        server.publish_routing(RoutingTable(
            tuple(ScoringRule(Condition(tenants=(t,)), f"p-new-{t}")
                  for t in tenants), version="v2"))

        # Phase C: stale maps serve the new ensemble while the new
        # (tenant, p-new) streams fill; then ONE fleet refresh pass.
        stale_rates = rates(serve_phase(per_phase))
        res2 = ctrl.refresh_fleet()
        refreshed = {(r.tenant, r.predictor) for r in res2.refreshed}
        assert {(t, f"p-new-{t}") for t in tenants} <= refreshed, \
            [r.reasons for r in res2.reports]
        assert server.bank_generation == 2

        # Phase D: the invariant — post-update alert rates back on target,
        # and stable relative to the pre-update baseline.
        post = rates(serve_phase(per_phase))
        for t in tenants:
            assert post[t] == pytest.approx(a, abs=0.012), (t, post)
            assert abs(post[t] - pre[t]) <= 0.02, (t, pre, post, stale_rates)
        # and the post-refresh distributions sit inside the drift bound
        from repro.serving.drift import transformed_stream_psi
        for t, s in serve_phase(per_phase).items():
            assert transformed_stream_psi(s, world.ref_quantiles) < 0.25
