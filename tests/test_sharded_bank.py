"""Multi-device parity campaign: tenant-sharded transform banks.

Proves the ROADMAP's "Sharded transform banks" item: row-partitioning the
``TransformBank`` over a mesh "tenants" axis (each replica shard holds only
its tenant rows) changes WHERE the parameters live but not a single bit of
WHAT gets served.  The campaign asserts, on 1/2/4/8 host devices:

  * sharded-vs-dense score parity is EXACT (bitwise on f32) — the per-shard
    banked kernel runs the identical per-row fp op sequence as the dense
    dispatch, whatever the assignment;
  * the partition machinery is lossless under arbitrary tenant->shard
    permutations, uneven occupancy, empty shards, and tenants absent from a
    batch (hypothesis property sweep);
  * ``refresh_fleet`` publishes land atomically ACROSS shards: a traffic
    thread never observes a torn per-shard mix and the fleet generation
    stays monotone (concurrency case).

The estimator-persistence tests ride along unmarked (no devices needed):
a surged replica restores its (tenant, predictor) reservoirs and starts
past the Eq.-5 gate instead of cold.

Marked ``sharded`` -> ``./test.sh --sharded`` (which exports
``XLA_FLAGS=--xla_force_host_platform_device_count=8``); shard counts
beyond the available device count skip at runtime so a plain single-device
pytest pass stays green.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.predictor import PredictorSpec
from repro.core.quantiles import StreamingQuantileEstimator, required_sample_size
from repro.core.routing import Condition, Intent, RoutingTable, ScoringRule
from repro.core.transforms import (
    QuantileMap,
    ShardedTransformBank,
    TransformBank,
    banked_score_pipeline,
    score_pipeline,
)
from repro.kernels import ops
from repro.launch.mesh import make_tenant_mesh
from repro.serving import (
    AsyncDispatchEngine,
    CalibrationController,
    MuseServer,
    RefreshPolicy,
    ServerConfig,
    ShardedBankDispatcher,
)
from repro.serving.types import ScoringRequest

NDEV = jax.device_count()
TOL = 1e-5
DIM = 8
SHARD_COUNTS = (1, 2, 4, 8)


def _needs_devices(n: int) -> None:
    if NDEV < n:
        pytest.skip(f"needs {n} devices, have {NDEV} "
                    "(run via ./test.sh --sharded)")


def _bits(x) -> np.ndarray:
    return np.asarray(x, np.float32).view(np.uint32)


def _random_bank(rng, t, k, n, generation=0) -> TransformBank:
    betas = rng.uniform(0.05, 1.0, (t, k)).astype(np.float32)
    weights = rng.uniform(0.1, 2.0, (t, k)).astype(np.float32)
    src = np.sort(rng.uniform(0.0, 1.0, (t, n)), axis=-1).astype(np.float32)
    ref = np.sort(rng.uniform(0.0, 1.0, (t, n)), axis=-1).astype(np.float32)
    return TransformBank(
        betas=jnp.asarray(betas), weights=jnp.asarray(weights),
        src_quantiles=jnp.asarray(src), ref_quantiles=jnp.asarray(ref),
        generation=generation)


def _dense_scores(bank, scores, tid) -> np.ndarray:
    return np.asarray(ops.score_pipeline_banked(
        jnp.asarray(scores), jnp.asarray(tid), bank.betas, bank.weights,
        bank.src_quantiles, bank.ref_quantiles))


# ---------------------------------------------------------------------------
# Partition machinery (pure array plumbing — no mesh required)
# ---------------------------------------------------------------------------

@pytest.mark.sharded
class TestShardedBankStructure:
    def test_round_trip_is_lossless(self):
        rng = np.random.default_rng(0)
        bank = _random_bank(rng, 13, 3, 32, generation=7)
        sbank = ShardedTransformBank.from_dense(bank, 4)
        assert sbank.num_shards == 4
        assert sbank.num_rows == 13
        assert sbank.generation == 7
        assert int(sbank.row_counts.sum()) == 13
        back = sbank.to_dense()
        for field in ("betas", "weights", "src_quantiles", "ref_quantiles"):
            np.testing.assert_array_equal(
                np.asarray(getattr(back, field)),
                np.asarray(getattr(bank, field)))
        assert back.generation == 7
        # the remap is a bijection rows -> (shard, local slot)
        pairs = set(zip(sbank.shard_of.tolist(), sbank.local_of.tolist()))
        assert len(pairs) == 13
        assert all(0 <= l < sbank.rows_per_shard for _, l in pairs)

    def test_uneven_occupancy_and_empty_shards(self):
        rng = np.random.default_rng(1)
        bank = _random_bank(rng, 6, 2, 16)
        # everything piles onto shard 2 of 4: shards 0/1/3 are EMPTY
        assign = np.full(6, 2)
        sbank = ShardedTransformBank.from_dense(bank, 4, shard_of=assign)
        np.testing.assert_array_equal(sbank.row_counts, [0, 0, 6, 0])
        assert sbank.rows_per_shard == 6
        back = sbank.to_dense()
        np.testing.assert_array_equal(np.asarray(back.betas),
                                      np.asarray(bank.betas))
        # an empty shard still exposes a well-formed (inert) sub-bank
        assert sbank.shard_bank(0).num_rows == 1
        assert sbank.shard_bank(2).num_rows == 6

    def test_per_shard_bytes_shrink_with_shard_count(self):
        rng = np.random.default_rng(2)
        bank = _random_bank(rng, 64, 4, 256)
        dense_bytes = 64 * (2 * 4 + 2 * 256) * 4
        for s in (1, 2, 4, 8):
            sbank = ShardedTransformBank.from_dense(bank, s)
            assert sbank.per_shard_bytes * s == pytest.approx(
                dense_bytes, rel=0.05)

    def test_with_rows_scatters_only_into_owning_shard(self):
        rng = np.random.default_rng(3)
        bank = _random_bank(rng, 8, 2, 16)
        sbank = ShardedTransformBank.from_dense(bank, 4)  # round-robin t % 4
        qm = QuantileMap(jnp.linspace(0, 1, 16), jnp.linspace(0, 1, 16) ** 2)
        out = sbank.with_rows({5: qm})                    # owner: shard 1
        owner = int(sbank.shard_of[5])
        assert owner == 1
        for s in range(4):
            same_src = np.array_equal(_bits(out.src_quantiles[s]),
                                      _bits(sbank.src_quantiles[s]))
            assert same_src == (s != owner)
        # the receiver is untouched; the update landed at (owner, local)
        local = int(sbank.local_of[5])
        np.testing.assert_array_equal(
            np.asarray(out.src_quantiles[owner, local]),
            np.asarray(qm.src_quantiles))
        assert out.generation == sbank.generation + 1
        # narrow tables edge-pad, wide tables are a shape error (dense parity)
        narrow = QuantileMap(jnp.linspace(0, 1, 8), jnp.linspace(0, 1, 8))
        padded = sbank.with_rows({0: narrow})
        assert padded.num_quantiles == 16
        wide = QuantileMap(jnp.linspace(0, 1, 64), jnp.linspace(0, 1, 64))
        with pytest.raises(ValueError):
            sbank.with_rows({0: wide})

    def test_with_rows_matches_dense_with_rows(self):
        """Sharded and dense functional updates stay interchangeable."""
        rng = np.random.default_rng(4)
        bank = _random_bank(rng, 10, 3, 32)
        sbank = ShardedTransformBank.from_dense(bank, 4)
        updates = {2: QuantileMap(jnp.linspace(0, 1, 32),
                                  jnp.linspace(0, 1, 32) ** 3),
                   7: QuantileMap(jnp.linspace(0, 1, 32),
                                  jnp.sqrt(jnp.linspace(0, 1, 32)))}
        dense_new = bank.with_rows(updates, generation=5)
        sharded_new = sbank.with_rows(updates, generation=5).to_dense()
        for field in ("betas", "weights", "src_quantiles", "ref_quantiles"):
            np.testing.assert_array_equal(
                np.asarray(getattr(sharded_new, field)),
                np.asarray(getattr(dense_new, field)))
        assert sharded_new.generation == dense_new.generation == 5

    def test_bad_assignment_raises(self):
        bank = _random_bank(np.random.default_rng(5), 4, 2, 8)
        with pytest.raises(ValueError):
            ShardedTransformBank.from_dense(bank, 0)
        with pytest.raises(ValueError):
            ShardedTransformBank.from_dense(bank, 2, shard_of=np.array([0, 1]))
        with pytest.raises(ValueError):
            ShardedTransformBank.from_dense(
                bank, 2, shard_of=np.array([0, 1, 2, 0]))
        with pytest.raises(IndexError):
            ShardedTransformBank.from_dense(bank, 2).with_rows(
                {9: QuantileMap.identity(8)})


# ---------------------------------------------------------------------------
# Sharded-vs-dense parity on real host devices
# ---------------------------------------------------------------------------

@pytest.mark.sharded
class TestShardedDispatchParity:
    @pytest.mark.parametrize("shards", SHARD_COUNTS)
    def test_bitwise_parity_vs_dense_kernel(self, shards):
        _needs_devices(shards)
        rng = np.random.default_rng(100 + shards)
        t, k, n, b = 23, 3, 64, 517
        bank = _random_bank(rng, t, k, n)
        scores = rng.uniform(0, 1, (b, k)).astype(np.float32)
        tid = rng.integers(0, t, b)
        dense = _dense_scores(bank, scores, tid.astype(np.int32))
        sbank = ShardedTransformBank.from_dense(bank, shards)
        disp = ShardedBankDispatcher(make_tenant_mesh(shards))
        got = disp(scores, tid, sbank)
        assert np.array_equal(_bits(got), _bits(dense))

    @pytest.mark.parametrize("shards", SHARD_COUNTS)
    def test_unfused_fallback_parity(self, shards):
        _needs_devices(shards)
        rng = np.random.default_rng(200 + shards)
        t, k, n, b = 11, 2, 32, 260
        bank = _random_bank(rng, t, k, n)
        scores = rng.uniform(0, 1, (b, k)).astype(np.float32)
        tid = rng.integers(0, t, b)
        dense = np.asarray(banked_score_pipeline(
            jnp.asarray(scores), jnp.asarray(tid.astype(np.int32)),
            bank.betas, bank.weights, bank.src_quantiles,
            bank.ref_quantiles))
        sbank = ShardedTransformBank.from_dense(bank, shards)
        disp = ShardedBankDispatcher(make_tenant_mesh(shards), fused=False)
        got = disp(scores, tid, sbank)
        np.testing.assert_allclose(got, dense, atol=TOL, rtol=TOL)


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


def _fleet(n_tenants=6, *, shards=1) -> MuseServer:
    """One predictor per tenant, all sharing one model group, so a mixed
    batch is ONE multi-tenant banked window."""
    rules = tuple(ScoringRule(Condition(tenants=(f"t{i}",)), f"p{i}")
                  for i in range(n_tenants)) + \
        (ScoringRule(Condition(), "p0"),)
    server = MuseServer(
        RoutingTable(rules, version="v1"),
        ServerConfig(refresh_alert_rate=0.05, refresh_rel_error=0.5,
                     tenant_shards=shards))
    rng = np.random.default_rng(42)
    for i in range(n_tenants):
        n = 32
        qm = QuantileMap(
            src_quantiles=jnp.asarray(np.sort(rng.uniform(0, 1, n)),
                                      jnp.float32),
            ref_quantiles=jnp.asarray(np.sort(rng.uniform(0, 1, n)),
                                      jnp.float32))
        server.deploy(PredictorSpec(f"p{i}", ("m1", "m2"),
                                    (0.2 + 0.1 * (i % 3), 0.4),
                                    (1.0, 1.0 + i % 2), qm), FACTORIES)
    return server


@pytest.mark.sharded
class TestShardedServerParity:
    @pytest.mark.parametrize("shards", SHARD_COUNTS)
    def test_score_batch_bitwise_vs_dense_server(self, shards):
        _needs_devices(shards)
        dense, sharded = _fleet(6), _fleet(6, shards=shards)
        reqs = [_req(f"t{i % 6}", 1000 + i) for i in range(37)]
        want = dense.score_batch(reqs)
        got = sharded.score_batch(reqs)
        assert [r.request_id for r in got] == [r.request_id for r in want]
        for a, b in zip(got, want):
            assert a.score == b.score, (a.predictor, a.score, b.score)
            assert a.bank_generation == b.bank_generation
        # the whole mixed window went through the sharded dispatch path
        # (tenant_shards=1 IS the dense path by design — no mesh to split)
        if shards > 1:
            assert sharded.metrics["shard_dispatches"] == \
                sharded.metrics["kernel_dispatches"] == 1
        else:
            assert sharded.metrics["shard_dispatches"] == 0
        assert dense.metrics["shard_dispatches"] == 0

    def test_engine_serves_through_sharded_path(self):
        _needs_devices(4)
        dense, sharded = _fleet(4), _fleet(4, shards=4)
        reqs = [_req(f"t{i % 4}", 2000 + i) for i in range(32)]
        want = {r.request_id: r.score for r in dense.score_batch(reqs)}
        engine = AsyncDispatchEngine(sharded, max_batch=8, max_wait_ms=1e9)
        out = engine.score_batch(reqs)
        engine.close()
        assert sharded.metrics["shard_dispatches"] >= 1
        for r in out:
            assert r.score == want[r.request_id]


# ---------------------------------------------------------------------------
# Property sweep: permutations, uneven occupancy, absent tenants
# ---------------------------------------------------------------------------

@pytest.mark.sharded
class TestShardedProperties:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10_000), st.integers(1, 8), st.integers(1, 31))
    def test_arbitrary_assignment_preserves_scores_bitwise(
            self, seed, shards, t):
        """Any tenant->shard permutation — uneven, with empty shards, with
        tenants absent from the batch — serves bitwise-identical scores."""
        if NDEV < shards:
            return  # drawn shard count beyond this host's devices
        rng = np.random.default_rng(seed)
        k, n, b = 2, 16, 97
        bank = _random_bank(rng, t, k, n)
        # arbitrary assignment: uneven occupancy, shards may be empty
        assign = rng.integers(0, shards, t)
        sbank = ShardedTransformBank.from_dense(bank, shards, shard_of=assign)
        # batch over a SUBSET of tenants (some tenants absent entirely)
        present = rng.choice(t, size=max(1, t // 2), replace=False)
        tid = rng.choice(present, size=b)
        scores = rng.uniform(0, 1, (b, k)).astype(np.float32)
        dense = _dense_scores(bank, scores, tid.astype(np.int32))
        disp = ShardedBankDispatcher(make_tenant_mesh(shards))
        got = disp(scores, tid, sbank)
        assert np.array_equal(_bits(got), _bits(dense))
        # and the partition itself is lossless
        np.testing.assert_array_equal(
            np.asarray(sbank.to_dense().src_quantiles),
            np.asarray(bank.src_quantiles))

    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 10_000), st.integers(2, 8))
    def test_permuted_assignment_equals_default(self, seed, shards):
        """The assignment is representation only: two different layouts of
        the same bank score every request identically (bitwise)."""
        if NDEV < shards:
            return
        rng = np.random.default_rng(seed)
        t, k, n, b = 12, 3, 32, 130
        bank = _random_bank(rng, t, k, n)
        scores = rng.uniform(0, 1, (b, k)).astype(np.float32)
        tid = rng.integers(0, t, b)
        disp = ShardedBankDispatcher(make_tenant_mesh(shards))
        default = disp(scores, tid,
                       ShardedTransformBank.from_dense(bank, shards))
        permuted = disp(scores, tid, ShardedTransformBank.from_dense(
            bank, shards, shard_of=rng.permutation(t) % shards))
        assert np.array_equal(_bits(default), _bits(permuted))


# ---------------------------------------------------------------------------
# Atomic cross-shard calibration publish under live concurrency
# ---------------------------------------------------------------------------

def _inject(server, tenant, pred, n=5000, seed=0):
    rng = np.random.default_rng(seed)
    est = StreamingQuantileEstimator(capacity=131072, seed=seed)
    est.update(rng.uniform(0, 1, n))
    server._estimators[(tenant, pred)] = est
    return est


def _pipeline_registry(server):
    return {n: p.pipeline for n, p in server.predictors.items()}


@pytest.mark.sharded
@pytest.mark.concurrency
class TestShardedRefreshAtomicity:
    """``refresh_fleet`` publishes must land atomically ACROSS shards: the
    dense bank and every per-shard sub-bank swap in one control-plane
    assignment, so a traffic thread can never see shard A at generation g
    and shard B at g+1, and the fleet generation is monotone."""

    def test_publishes_are_atomic_across_shards(self):
        _needs_devices(4)
        n_t = 8
        server = _fleet(n_t, shards=4)
        server.score_batch([_req(f"t{i % n_t}", 30_000 + i)
                            for i in range(16)])  # compile before the clock
        for i in range(n_t):
            _inject(server, f"t{i}", f"p{i}", seed=i)
        ref = np.linspace(0.0, 1.0, 64) ** 2
        ctrl = CalibrationController(
            server, ref,
            RefreshPolicy(alert_rate=0.05, rel_error=0.5, n_levels=64))
        registry = {server.bank_generation: _pipeline_registry(server)}
        res0 = ctrl.refresh_fleet()     # warm the refresh path pre-clock
        assert res0.generation == 1
        registry[1] = _pipeline_registry(server)

        engine = AsyncDispatchEngine(server, max_batch=16, max_wait_ms=1e9,
                                     facade_timeout_s=300.0)
        reqs = [_req(f"t{i % n_t}", i) for i in range(960)]
        stop = threading.Event()
        published: list[int] = []

        def writer():
            while not stop.is_set() and len(published) < 40:
                res = ctrl.refresh_fleet()
                registry[res.generation] = _pipeline_registry(server)
                published.append(res.generation)

        wt = threading.Thread(target=writer)
        tt = threading.Thread(target=lambda: [engine.submit(r) for r in reqs])
        wt.start()
        tt.start()
        tt.join(timeout=300.0)
        assert not tt.is_alive(), "traffic thread wedged"
        responses = engine.drain(timeout=300.0)
        stop.set()
        wt.join(timeout=300.0)
        assert not wt.is_alive(), "refresh writer wedged"
        engine.close()

        # 1:1 delivery, and publishes really overlapped the traffic
        assert sorted(r.request_id for r in responses) == \
            sorted(r.request_id for r in reqs)
        assert len(published) >= 2
        # ONE fleet generation per publish: strictly consecutive, no skips
        # (a torn per-shard publish would surface as a duplicated or
        # out-of-order generation)
        assert published == list(range(2, 2 + len(published)))
        # every response reproduces from the pipelines of the ONE generation
        # it is stamped with — any cross-shard tear diverges
        for resp in responses:
            pipe = registry[resp.bank_generation][resp.predictor]
            want = float(score_pipeline(
                jnp.asarray(resp.raw_scores, jnp.float32), pipe.betas,
                pipe.weights, pipe.src_quantiles, pipe.ref_quantiles))
            assert resp.score == pytest.approx(want, abs=TOL), \
                (resp.request_id, resp.predictor, resp.bank_generation)
        # per-stream generations never step back
        seen: dict[str, int] = {}
        for resp in responses:
            last = seen.get(resp.predictor, -1)
            assert resp.bank_generation >= last
            seen[resp.predictor] = resp.bank_generation


# ---------------------------------------------------------------------------
# Estimator persistence (warm surge) — runs in the fast lane, no devices
# ---------------------------------------------------------------------------

class TestEstimatorPersistence:
    def test_estimator_round_trip_is_exact(self, tmp_path):
        rng = np.random.default_rng(0)
        est = StreamingQuantileEstimator(capacity=256, seed=3,
                                         recent_capacity=32)
        est.update(rng.uniform(0, 1, 700))   # past capacity: reservoir live
        restored = StreamingQuantileEstimator.from_checkpoint(
            est.checkpoint_arrays(), est.checkpoint_meta())
        assert restored.count == est.count
        np.testing.assert_array_equal(restored.values(), est.values())
        np.testing.assert_array_equal(restored.recent(), est.recent())
        levels = np.linspace(0, 1, 33)
        np.testing.assert_array_equal(restored.quantiles(levels),
                                      est.quantiles(levels))
        # the RNG state round-trips too: both continue the SAME
        # reservoir-acceptance sequence
        more = rng.uniform(0, 1, 500)
        est.update(more)
        restored.update(more)
        np.testing.assert_array_equal(restored.values(), est.values())

    def test_surged_replica_restores_past_eq5_gate(self, tmp_path):
        """save -> restore -> the Eq.-5 gate still passes and a refresh
        ships — the warm-surge lifecycle."""
        alert_rate, rel_error = 0.05, 0.5
        need = required_sample_size(alert_rate, rel_error)
        server = _fleet(3)
        rng = np.random.default_rng(9)
        for i in range(3):
            est = StreamingQuantileEstimator(capacity=8192, seed=i)
            est.update(rng.uniform(0, 1, need + 50))
            server._estimators[(f"t{i}", f"p{i}")] = est
        assert server.calibration_ready("t0", "p0")
        path = server.save_estimators(str(tmp_path / "est"), step=4)
        assert path.endswith("4")

        surged = _fleet(3)                 # fresh replica: cold streams
        assert not surged.calibration_ready("t0", "p0")
        n = surged.restore_estimators(str(tmp_path / "est"))  # latest step
        assert n == 3
        for i in range(3):
            assert surged.calibration_ready(f"t{i}", f"p{i}")
            np.testing.assert_array_equal(
                surged._estimators[(f"t{i}", f"p{i}")].values(),
                server._estimators[(f"t{i}", f"p{i}")].values())
        # the restored streams refit + validate + publish like live ones
        ref = np.linspace(0.0, 1.0, 64) ** 2
        ctrl = CalibrationController(
            surged, ref,
            RefreshPolicy(alert_rate=alert_rate, rel_error=rel_error,
                          n_levels=64))
        res = ctrl.refresh_fleet()
        assert len(res.refreshed) == 3, [r.reasons for r in res.reports]
        assert surged.bank_generation == 1

    def test_restore_missing_checkpoint_raises(self, tmp_path):
        server = _fleet(2)
        with pytest.raises(FileNotFoundError):
            server.restore_estimators(str(tmp_path / "nope"))
