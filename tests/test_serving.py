"""Serving-runtime tests: server data plane, shadows, batching, rollout."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predictor import PredictorSpec
from repro.core.routing import Condition, Intent, RoutingTable, ScoringRule, ShadowRule
from repro.core.transforms import QuantileMap
from repro.serving.batching import MicroBatcher
from repro.serving.rollout import Replica, ReplicaSet, RollingUpdate
from repro.serving.server import MuseServer, ServerConfig
from repro.serving.types import ScoringRequest
from repro.serving.warmup import count_compiles, warm_up

DIM = 8


def _linear_model(seed: int, dim: int = DIM):
    rng = np.random.default_rng(seed)
    w = rng.normal(0, 1, dim).astype(np.float32)

    def score(x):
        x = np.asarray(x, np.float32)
        return jnp.asarray(1.0 / (1.0 + np.exp(-(x @ w))))

    return score


def _qm(n=32):
    return QuantileMap.identity(n)


def _basic_server(extra_shadow: bool = False,
                  config: ServerConfig | None = None) -> MuseServer:
    rules = [ScoringRule(Condition(tenants=("bank1",)), "p-bank1"),
             ScoringRule(Condition(), "p-global")]
    shadows = [ShadowRule(Condition(tenants=("bank1",)), ("p-shadow",))] if extra_shadow else []
    server = MuseServer(RoutingTable(tuple(rules), tuple(shadows), version="v1"),
                        config)
    factories = {
        "m1": lambda: _linear_model(1),
        "m2": lambda: _linear_model(2),
        "m3": lambda: _linear_model(3),
    }
    server.deploy(PredictorSpec("p-bank1", ("m1", "m2"), (0.2, 0.2),
                                (1.0, 1.0), _qm()), factories)
    server.deploy(PredictorSpec.single("p-global", "m1", _qm()), factories)
    if extra_shadow:
        server.deploy(PredictorSpec("p-shadow", ("m1", "m2", "m3"),
                                    (0.2, 0.2, 0.05), (1.0, 1.0, 1.0), _qm()),
                      factories)
    return server


def _req(tenant="bank1", seed=0):
    rng = np.random.default_rng(seed)
    return ScoringRequest(intent=Intent(tenant=tenant),
                          features=rng.normal(0, 1, DIM).astype(np.float32))


class TestServerDataPlane:
    def test_routing_to_tenant_predictor(self):
        server = _basic_server()
        resp = server.score(_req("bank1"))
        assert resp.predictor == "p-bank1"
        assert 0.0 <= resp.score <= 1.0
        assert len(resp.raw_scores) == 2
        resp2 = server.score(_req("other"))
        assert resp2.predictor == "p-global"

    def test_model_dedup_across_predictors(self):
        server = _basic_server(extra_shadow=True)
        # m1, m2, m3 deployed once each despite three predictors sharing them
        assert server.pool.provision_events == 3
        assert set(server.pool.names()) == {"m1", "m2", "m3"}

    def test_shadow_scoring_does_not_affect_response(self):
        s_with = _basic_server(extra_shadow=True)
        s_without = _basic_server(extra_shadow=False)
        req = _req("bank1", seed=7)
        r1 = s_with.score(req)
        r2 = s_without.score(req)
        assert r1.score == pytest.approx(r2.score, abs=1e-7)
        assert len(s_with.sink) == 1
        assert len(s_without.sink) == 0
        rec = s_with.sink.records("p-shadow")[0]
        assert rec.tenant == "bank1"
        assert len(rec.raw_scores) == 3

    def test_batch_grouping_multi_tenant(self):
        server = _basic_server()
        reqs = [_req("bank1", i) for i in range(3)] + [_req("t2", i) for i in range(2)]
        resps = server.score_batch(reqs)
        assert [r.predictor for r in resps] == ["p-bank1"] * 3 + ["p-global"] * 2
        assert [r.request_id for r in resps] == [q.request_id for q in reqs]

    def test_transformation_swap_without_model_touch(self):
        server = _basic_server()
        prov_before = server.pool.provision_events
        qs = jnp.linspace(0, 1, 32)
        server.swap_transformation("p-bank1", QuantileMap(qs, qs**2))
        assert server.pool.provision_events == prov_before  # zero models touched
        resp = server.score(_req("bank1"))
        assert 0.0 <= resp.score <= 1.0

    def test_banked_skip_stats_as_serving_metric(self):
        """skip_blocks_* metrics through the REAL dispatcher path: a window
        sorted by tenant is all uniform blocks (scalar-prefetch fast path),
        an interleaved window defeats it entirely."""
        rules = [ScoringRule(Condition(tenants=("bank1",)), "p-bank1"),
                 ScoringRule(Condition(), "p-bank2")]
        factories = {"m1": lambda: _linear_model(1),
                     "m2": lambda: _linear_model(2)}

        def mk():
            server = MuseServer(RoutingTable(tuple(rules), version="v1"))
            # two predictors sharing one model group -> one banked window
            server.deploy(PredictorSpec("p-bank1", ("m1", "m2"), (0.2, 0.2),
                                        (1.0, 1.0), _qm()), factories)
            server.deploy(PredictorSpec("p-bank2", ("m1", "m2"), (0.2, 0.2),
                                        (1.0, 1.0), _qm()), factories)
            return server

        n = 2048  # two kernel blocks of 1024
        sorted_reqs = [_req("bank1", i) for i in range(n // 2)] + \
            [_req("bank2", i) for i in range(n // 2)]
        server = mk()
        server.score_batch(sorted_reqs)
        assert server.metrics["skip_blocks_total"] == 2
        assert server.metrics["skip_blocks_uniform"] == 2  # skip rate 1.0

        interleaved = [r for pair in zip(sorted_reqs[: n // 2],
                                         sorted_reqs[n // 2:]) for r in pair]
        server = mk()
        server.score_batch(interleaved)
        assert server.metrics["skip_blocks_total"] == 2
        assert server.metrics["skip_blocks_uniform"] == 0  # skip rate 0.0

    def test_publish_routing_validates_targets(self):
        server = _basic_server()
        bad = RoutingTable((ScoringRule(Condition(), "ghost"),), version="v2")
        with pytest.raises(KeyError):
            server.publish_routing(bad)

    def test_feature_enrichment_for_wider_models(self):
        """Easy Feature Evolution: a model with a wider feature set gets its
        derived features from the store; clients keep sending DIM features."""
        server = _basic_server()
        wide_dim = DIM + 4
        server.deploy(
            PredictorSpec.single("p-wide", "m-wide", _qm()),
            {"m-wide": lambda: _linear_model(9, wide_dim)},
        )
        server.predictors["p-wide"]._handles[0].metadata["feature_dim"] = wide_dim
        server.features.put("bank1", np.full(4, 0.5))
        server.publish_routing(RoutingTable(
            (ScoringRule(Condition(tenants=("bank1",)), "p-wide"),
             ScoringRule(Condition(), "p-global")), version="v3"))
        resp = server.score(_req("bank1"))
        assert resp.predictor == "p-wide"
        assert 0.0 <= resp.score <= 1.0

    def test_shadow_dedup_reuses_raw_scores_within_model_group(self):
        """A shadow predictor sharing its request's live model group must NOT
        re-run the expert models: raw scores are cached per (group, request)
        inside score_batch, so the shadow costs one extra banked kernel
        dispatch but zero extra model executions."""
        rules = [ScoringRule(Condition(tenants=("bank1",)), "p-bank1"),
                 ScoringRule(Condition(), "p-global")]
        shadows = [ShadowRule(Condition(tenants=("bank1",)), ("p-shadow-same",))]
        server = MuseServer(RoutingTable(tuple(rules), tuple(shadows),
                                         version="v1"))
        factories = {"m1": lambda: _linear_model(1),
                     "m2": lambda: _linear_model(2)}
        server.deploy(PredictorSpec("p-bank1", ("m1", "m2"), (0.2, 0.2),
                                    (1.0, 1.0), _qm()), factories)
        server.deploy(PredictorSpec("p-shadow-same", ("m1", "m2"), (0.5, 0.8),
                                    (2.0, 1.0), _qm()), factories)
        server.deploy(PredictorSpec.single("p-global", "m1", _qm()), factories)
        reqs = [_req("bank1", seed=i) for i in range(4)]
        before = dict(server.metrics)
        resps = server.score_batch(reqs)
        # live + shadow each take a banked kernel dispatch...
        assert server.metrics["kernel_dispatches"] - before["kernel_dispatches"] == 2
        # ...but the {m1,m2} group executed exactly ONCE (2 model forwards)
        assert server.metrics["model_group_calls"] - before["model_group_calls"] == 1
        assert server.metrics["model_calls"] - before["model_calls"] == 2
        # shadow records reused the live dispatch's raw expert scores
        recs = server.sink.records("p-shadow-same")
        assert len(recs) == 4
        for resp, rec in zip(resps, recs):
            assert rec.raw_scores == resp.raw_scores
            assert rec.score != pytest.approx(resp.score, abs=1e-9)

    def test_shadow_distinct_model_group_still_runs_models(self):
        """Control case: a shadow on a DIFFERENT model group cannot reuse
        raw scores — it pays its own model execution."""
        server = _basic_server(extra_shadow=True)  # shadow adds m3
        before = dict(server.metrics)
        server.score_batch([_req("bank1", seed=3)])
        assert server.metrics["kernel_dispatches"] - before["kernel_dispatches"] == 2
        assert server.metrics["model_group_calls"] - before["model_group_calls"] == 2
        # live {m1,m2} = 2 forwards + shadow {m1,m2,m3} = 3 forwards
        assert server.metrics["model_calls"] - before["model_calls"] == 5

    def test_calibration_refresh_gate_and_fit(self):
        cfgd = ServerConfig(refresh_alert_rate=0.05, refresh_rel_error=0.5)
        server = _basic_server()
        server.config = cfgd
        assert not server.calibration_ready("bank1", "p-bank1")
        n_needed = 1 + int(1.96**2 * 0.95 / (0.25 * 0.05))
        for i in range(0, n_needed, 64):
            server.score_batch([_req("bank1", seed=i + j) for j in range(64)])
        assert server.calibration_ready("bank1", "p-bank1")
        qm = server.fit_custom_quantile_map("bank1", "p-bank1",
                                            np.linspace(0, 1, 64))
        assert (np.diff(np.asarray(qm.src_quantiles)) >= -1e-7).all()


class TestMicroBatcher:
    def test_size_trigger(self):
        mb = MicroBatcher(max_batch=3, max_wait_ms=1e9)
        assert mb.add("p", _req()) is None
        assert mb.add("p", _req()) is None
        batch = mb.add("p", _req())
        assert batch is not None and len(batch) == 3
        assert mb.pending_count == 0

    def test_age_trigger_with_fake_clock(self):
        t = [0.0]
        mb = MicroBatcher(max_batch=100, max_wait_ms=5.0, clock=lambda: t[0])
        mb.add("p", _req())
        assert mb.expired() == []
        t[0] = 0.006
        expired = mb.expired()
        assert len(expired) == 1 and len(expired[0][1]) == 1

    def test_keys_are_independent(self):
        mb = MicroBatcher(max_batch=2, max_wait_ms=1e9)
        mb.add("a", _req())
        assert mb.add("b", _req()) is None
        assert mb.add("a", _req()) is not None


class TestRollout:
    def test_rolling_update_availability_and_version_shift(self):
        def make_server(version="v1"):
            s = _basic_server()
            s.routing = RoutingTable(s.routing.scoring_rules,
                                     s.routing.shadow_rules, version=version)
            return s

        replicas = [Replica(i, make_server(), "v1", ready=True) for i in range(3)]
        rs = ReplicaSet(replicas)
        update = RollingUpdate(rs, lambda: make_server("v2"), "v2",
                               schema_dim=DIM, warmup_batch_sizes=(1, 4))

        def traffic():
            i = 0
            while True:
                yield [_req("bank1", seed=i), _req("t2", seed=i + 1)]
                i += 2

        timeline = update.run_with_traffic(traffic(), batches_per_transition=2)
        # availability: every sample had >= 3 ready replicas (maxUnavailable=0)
        assert min(t["ready_count"] for t in timeline) >= 3
        # surge: pod count peaked above baseline
        assert max(t["pod_count"] for t in timeline) == 4
        # traffic fully shifted to v2 by the end
        assert timeline[-1]["version"] == "v2"
        versions = {t["version"] for t in timeline}
        assert versions == {"v1", "v2"}
        # every replica was warmed before serving
        assert all(r.warmup_seconds > 0 for r in rs.replicas)

    def test_warmup_compiles_all_predictors(self):
        server = _basic_server(extra_shadow=True)
        timings = warm_up(server, DIM, batch_sizes=(1, 2))
        assert set(timings) == {"p-bank1", "p-global", "p-shadow"}
        # warmed path: subsequent call is fast and doesn't recompile
        import time
        t0 = time.perf_counter()
        server.score_batch([_req("bank1", seed=1)])
        assert time.perf_counter() - t0 < 0.5

    @pytest.mark.parametrize("track_device", [False, True])
    def test_warmed_server_compiles_nothing_on_first_window(self,
                                                            track_device):
        # warm-up reaches the models, the banked kernel and (with device
        # tracking) the fused tracker at every window bucket
        server = _basic_server(extra_shadow=True, config=ServerConfig(
            track_quantiles=track_device, track_device=track_device))
        warm_up(server, DIM, batch_sizes=(2, 3))
        # model-group windows of 3 (p-bank1, and its shadow) and 2
        # (p-global): the buckets of the warmed sizes
        window = [_req(t, seed=i) for i, t in
                  enumerate(["bank1", "bank2", "bank1", "x", "bank1"])]
        with count_compiles() as compiles:
            responses = server.score_batch(window)
        assert compiles == []
        assert len(responses) == len(window)
        assert server.metrics["kernel_dispatches"] == 3  # 2 live + shadow
