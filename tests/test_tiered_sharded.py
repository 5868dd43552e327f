"""Tiered-over-sharded composition: per-shard hot tiers on the tenant mesh.

PR 9 lifts the tiering/`tenant_shards` mutual exclusion: every shard of
the tenant mesh owns a bounded hot tier + victim cache + pinned prior row
over its slice of the host store (``ShardedTieredBankStore``), and one
``shard_map`` launch per pass scores all shards' slot-remapped buckets
through the same fused banked kernel.  The campaign asserts, on 1/2/4/8
host devices:

  * composed scores match the dense bank AND the pure-sharded dispatcher
    BITWISE on f32 — cold path, warm path, multi-pass victim overflow,
    after prefetch and after rebalance;
  * device residency is ``(hot+victims+1)·(2K+2N)·4`` bytes PER SHARD,
    constant across tenant counts (host bytes grow; device bytes do not);
  * the fenced-publish contract survives composition: one
    ``apply_updates`` lands in every shard's host rows and device view
    under ONE generation, per-shard generations advance in lockstep,
    stale stamps are rejected, and a bad update touches no shard
    (property-tested over random dispatch/promote/publish/mark-cold
    schedules);
  * the serving layer composes end to end: ``ServerConfig(tenant_shards,
    tiering)`` server parity, engine-pipelined parity, and cross-topology
    ``warm_tiers_from`` (single-tier victim -> composed surge and back).

S=1 cases run on a plain single-device pytest pass; S>1 cases skip
unless the device count allows (``./test.sh --tiering`` exports the
8-virtual-device XLA_FLAGS).  Campaign classes are marked ``tiering``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.predictor import PredictorSpec
from repro.core.routing import Condition, RoutingTable, ScoringRule
from repro.core.transforms import QuantileMap, ShardedTransformBank, shard_rows
from repro.kernels import ops
from repro.launch.mesh import make_tenant_mesh
from repro.serving import (
    AsyncDispatchEngine,
    MuseServer,
    ServerConfig,
    ShardedBankDispatcher,
    StaleGenerationError,
)
from repro.serving.tiering import (
    HostBankStore,
    ShardedTieredBankStore,
    TieringConfig,
)
from test_tiering import (
    _TIER_CFG,
    _req,
    _tenant_server,
    EASY_GATE,
    FACTORIES,
)

NDEV = jax.device_count()
SHARD_COUNTS = (1, 2, 4, 8)


def _needs_devices(n: int) -> None:
    if NDEV < n:
        pytest.skip(f"needs {n} devices, have {NDEV} "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _bitwise(a: np.ndarray, b: np.ndarray) -> bool:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return bool(np.array_equal(a.view(np.uint32), b.view(np.uint32)))


def _mono(rng, t, n) -> np.ndarray:
    q = np.cumsum(rng.uniform(1e-3, 1.0, (t, n)).astype(np.float32),
                  axis=1, dtype=np.float32)
    return q / q[:, -1:]


def _host(rng, t, k=4, n=32) -> HostBankStore:
    return HostBankStore(
        rng.uniform(0.05, 1.0, (t, k)).astype(np.float32),
        rng.uniform(0.1, 2.0, (t, k)).astype(np.float32),
        _mono(rng, t, n), _mono(rng, t, n))


def _cfg(hot=4, victims=2, **kw) -> TieringConfig:
    return TieringConfig(hot_capacity=hot, victim_capacity=victims,
                         **{**EASY_GATE, **kw})


def _dense_scores(host: HostBankStore, raws, tid) -> np.ndarray:
    bank = host.dense_bank(0)
    return np.asarray(ops.score_pipeline_banked(
        jnp.asarray(raws), jnp.asarray(tid, jnp.int32), bank.betas,
        bank.weights, bank.src_quantiles, bank.ref_quantiles))


# --------------------------------------------------------------------------
# store-level bitwise parity (dense + pure-sharded oracles)
# --------------------------------------------------------------------------

class TestComposedParity:
    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_bitwise_parity_vs_dense_and_pure_sharded(self, s):
        _needs_devices(s)
        rng = np.random.default_rng(100 + s)
        t, k = 37, 4
        host = _host(rng, t, k=k)
        mesh = make_tenant_mesh(s)
        dispatcher = ShardedBankDispatcher(mesh)
        store = ShardedTieredBankStore(host, s, _cfg(),
                                       dispatcher=dispatcher)
        sharded = ShardedTransformBank.from_dense(host.dense_bank(0), s)
        raws = rng.uniform(0, 1, (48, k)).astype(np.float32)
        tid = rng.integers(0, t, 48)
        want = _dense_scores(host, raws, tid)
        # pure-sharded oracle through the SAME dispatcher
        pure = dispatcher(raws, np.asarray(tid, np.int32), sharded)
        assert _bitwise(pure, want)
        # cold path: every row pages through victim caches (multi-pass —
        # 37 tenants over at most 6 resident slots per shard)
        got, gen = store.dispatch(raws, tid)
        assert _bitwise(got, want)
        assert gen == 0
        assert store.metrics["cold_miss_stalls"] > 0
        # warm path: residents serve straight from the device views
        got2, _ = store.dispatch(raws, tid)
        assert _bitwise(got2, want)
        # prefetch + rebalance do not perturb served values
        store.prefetch(tid)
        store.rebalance()
        got3, _ = store.dispatch(raws, tid)
        assert _bitwise(got3, want)

    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_multipass_overflow_parity(self, s):
        _needs_devices(s)
        rng = np.random.default_rng(200 + s)
        t, k = 64, 4
        host = _host(rng, t, k=k)
        # victim_capacity=1: every shard pages one row per pass, so a
        # window spanning all tenants forces many joint passes
        store = ShardedTieredBankStore(host, s, _cfg(hot=2, victims=1))
        tid = np.arange(t)
        raws = rng.uniform(0, 1, (t, k)).astype(np.float32)
        got, _ = store.dispatch(raws, tid)
        assert _bitwise(got, _dense_scores(host, raws, tid))
        assert store.metrics["extra_passes"] > 0

    def test_row_partition_matches_sharded_bank_rule(self):
        # the composed store and ShardedTransformBank must bucket a tenant
        # to the SAME shard, or engine prefetch and rebalance would warm
        # the wrong shard's tier
        assign, local, counts = shard_rows(11, 4)
        assert np.array_equal(assign, np.arange(11) % 4)
        host = _host(np.random.default_rng(3), 11)
        store = ShardedTieredBankStore(host, 4, _cfg(),
                                       dispatcher=object())
        assert np.array_equal(store.shard_of, assign)
        assert np.array_equal(store.local_of, local)
        assert np.array_equal(store.row_counts, counts)


# --------------------------------------------------------------------------
# per-shard residency bound
# --------------------------------------------------------------------------

class TestComposedResidency:
    def test_per_shard_device_bytes_independent_of_tenants(self):
        rng = np.random.default_rng(7)
        k, n, hot, victims = 4, 32, 4, 2
        per_shard = []
        for t in (16, 64, 256):
            store = ShardedTieredBankStore(
                _host(rng, t, k=k, n=n), 1, _cfg(hot=hot, victims=victims))
            per_shard.append(store.per_shard_device_bytes)
        assert len(set(per_shard)) == 1
        assert per_shard[0] == (hot + victims + 1) * (2 * k + 2 * n) * 4

    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_device_bytes_scale_with_shards_not_tenants(self, s):
        _needs_devices(s)
        rng = np.random.default_rng(8)
        store = ShardedTieredBankStore(_host(rng, 61), s, _cfg())
        assert store.device_bytes == s * store.per_shard_device_bytes
        assert store.host_bytes == _host(rng, 61).nbytes

    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_each_shard_lives_on_its_own_device(self, s):
        # shard s's tier view (also after cold rows staged into it) and
        # shard s's rows of a sharded bank sit on the mesh's device s
        _needs_devices(s)
        rng = np.random.default_rng(10)
        host = _host(rng, 29)
        store = ShardedTieredBankStore(host, s, _cfg())
        raws = rng.uniform(0.05, 0.95, (40, 4)).astype(np.float32)
        store.dispatch(raws, rng.integers(0, 29, 40).astype(np.int32))
        devices = store.dispatcher.devices
        assert [st.devices for st in store.shards] == [{d} for d in devices]
        sbank = ShardedTransformBank.from_dense(
            host.dense_bank(), s, sharding=store.dispatcher.sharding)
        for shard in sbank.betas.addressable_shards:
            assert shard.device == devices[shard.index[0].start or 0]

    def test_uneven_shards_share_hot_slot_count(self):
        # 5 rows over 4 shards: shard 0 owns 2 rows, shard 3 owns 1 —
        # every shard still gets the same hot-slot count so the per-shard
        # views stack into one (S, R, ·) shard_map operand
        store = ShardedTieredBankStore(
            _host(np.random.default_rng(9), 5), 4, _cfg(hot=8),
            dispatcher=object())
        assert len({st.hot_capacity for st in store.shards}) == 1
        assert len({st.device_bytes for st in store.shards}) == 1


# --------------------------------------------------------------------------
# fenced publish across shards
# --------------------------------------------------------------------------

class TestComposedPublish:
    def _store(self, s=2, t=13, seed=11):
        rng = np.random.default_rng(seed)
        host = _host(rng, t)
        return ShardedTieredBankStore(host, s, _cfg()), host, rng

    @pytest.mark.parametrize("s", SHARD_COUNTS)
    def test_publish_lands_on_every_shard_under_one_generation(self, s):
        _needs_devices(s)
        store, host, rng = self._store(s=s)
        t = host.num_rows
        raws = rng.uniform(0, 1, (t, 4)).astype(np.float32)
        tid = np.arange(t)
        store.dispatch(raws, tid)              # make rows device-resident
        n = host.num_quantiles
        upd = {row: QuantileMap(np.sort(rng.uniform(0, 1, n)),
                                np.linspace(0, 1, n) ** 2)
               for row in range(0, t, 3)}      # rows spanning every shard
        gen = store.apply_updates(upd)
        assert gen == 1
        assert all(st.generation == 1 for st in store.shards)
        # the oracle host store takes the same updates -> bitwise parity
        # proves hot AND victim device copies were rescattered everywhere
        host.write_rows(upd)
        got, got_gen = store.dispatch(raws, tid)
        assert got_gen == 1
        assert _bitwise(got, _dense_scores(host, raws, tid))

    def test_fenced_fast_forward_and_stale_rejection(self):
        store, _, _ = self._store(s=1)
        assert store.apply_updates({}, generation=5) == 5
        assert store.generation == 5
        assert all(st.generation == 5 for st in store.shards)
        with pytest.raises(StaleGenerationError):
            store.apply_updates({}, generation=5)
        with pytest.raises(StaleGenerationError):
            store.rebalance(generation=4)      # rebalance fenced the other way
        assert store.rebalance(generation=5)["generation"] == 5
        assert store.generation == 5           # rebalance never bumps

    def test_bad_update_touches_no_shard(self):
        store, host, rng = self._store(s=1, t=6)
        n = host.num_quantiles
        good = QuantileMap(np.sort(rng.uniform(0, 1, n)),
                           np.linspace(0, 1, n) ** 2)
        wide = QuantileMap(np.sort(rng.uniform(0, 1, 2 * n)),
                           np.linspace(0, 1, 2 * n))
        before = [st.host.src_quantiles.copy() for st in store.shards]
        with pytest.raises(ValueError):
            store.apply_updates({0: good, 5: wide})
        assert store.generation == 0
        for st, b in zip(store.shards, before):
            assert np.array_equal(st.host.src_quantiles, b)
        with pytest.raises(IndexError):
            store.apply_updates({99: good})


# --------------------------------------------------------------------------
# property sweep: random op schedules keep shards lockstep + bitwise
# --------------------------------------------------------------------------

@pytest.mark.tiering
class TestComposedScheduleProperty:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2 ** 32 - 1))
    def test_random_schedule_lockstep_generations_and_parity(self, seed):
        if NDEV < 2:
            pytest.skip("needs 2 devices")
        rng = np.random.default_rng(seed)
        t = int(rng.integers(5, 24))
        host = _host(rng, t)
        oracle = HostBankStore(host.betas, host.weights,
                               host.src_quantiles, host.ref_quantiles)
        store = ShardedTieredBankStore(host, 2, _cfg(hot=3, victims=2))
        n = host.num_quantiles
        for _ in range(12):
            op = rng.choice(["dispatch", "prefetch", "rebalance",
                             "publish", "fenced", "mark_cold"])
            if op == "dispatch":
                b = int(rng.integers(1, 17))
                tid = rng.integers(0, t, b)
                raws = rng.uniform(0, 1, (b, 4)).astype(np.float32)
                got, gen = store.dispatch(raws, tid)
                # score only admitted rows against the oracle (cold-marked
                # rows serve the prior; their parity is pinned elsewhere)
                adm = np.zeros(t, bool)
                for s, sub in enumerate(store.shards):
                    adm[store.global_of[s]] = sub.host.admitted
                mask = adm[tid]
                want = _dense_scores(oracle, raws, tid)
                assert _bitwise(got[mask], want[mask])
                assert gen == store.generation
            elif op == "prefetch":
                store.prefetch(rng.integers(0, t, 8))
            elif op == "rebalance":
                store.rebalance()
            elif op == "publish":
                rows = rng.choice(t, rng.integers(1, 4), replace=False)
                upd = {int(r): QuantileMap(np.sort(rng.uniform(0, 1, n)),
                                           np.linspace(0, 1, n) ** 2)
                       for r in rows}
                store.apply_updates(upd)
                oracle.write_rows(upd)
            elif op == "fenced":
                store.apply_updates({}, generation=store.generation + 3)
            elif op == "mark_cold":
                row = int(rng.integers(0, t))
                store.mark_cold([row])
            gens = {st.generation for st in store.shards}
            assert gens == {store.generation}, "shard generations diverged"


# --------------------------------------------------------------------------
# serving layer: server + engine + rollout warm start
# --------------------------------------------------------------------------

def _composed_server(n_tenants=4, shards=2,
                     tiering=_TIER_CFG) -> MuseServer:
    rules = tuple(ScoringRule(Condition(tenants=(f"t{i}",)), f"p{i}")
                  for i in range(n_tenants)) + \
        (ScoringRule(Condition(), "p0"),)
    server = MuseServer(
        RoutingTable(rules, version="v1"),
        ServerConfig(refresh_alert_rate=0.05, refresh_rel_error=0.5,
                     tenant_shards=shards, tiering=tiering))
    for i in range(n_tenants):
        server.deploy(PredictorSpec(f"p{i}", ("m1", "m2"), (0.2, 0.4),
                                    (1.0, 1.0), QuantileMap.identity(64)),
                      FACTORIES)
    return server


@pytest.mark.tiering
class TestComposedServing:
    def test_server_parity_and_store_type(self):
        _needs_devices(2)
        comp = _composed_server()
        dense = _tenant_server(4)
        reqs = [_req(f"t{i % 4}", seed=i) for i in range(16)]
        rd = dense.score_batch(list(reqs))
        rc = comp.score_batch(list(reqs))
        for a, b in zip(rd, rc):
            assert a.score == b.score
            assert a.bank_generation == b.bank_generation == 0
        (store,) = comp.tiered_stores().values()
        assert isinstance(store, ShardedTieredBankStore)
        assert store.num_shards == 2
        assert comp.metrics["tier_dispatches"] >= 1
        assert comp.metrics["shard_dispatches"] >= 1

    def test_server_publish_parity_and_stamp(self):
        _needs_devices(2)
        rng = np.random.default_rng(21)
        comp = _composed_server()
        dense = _tenant_server(4)
        reqs = [_req(f"t{i % 4}", seed=i) for i in range(8)]
        comp.score_batch(list(reqs))
        dense.score_batch(list(reqs))
        qm = QuantileMap(np.sort(rng.uniform(0, 1, 64)),
                         np.linspace(0.0, 1.0, 64) ** 2)
        assert dense.publish_quantile_maps({"p1": qm, "p2": qm}) == 1
        assert comp.publish_quantile_maps({"p1": qm, "p2": qm}) == 1
        rd = dense.score_batch(list(reqs))
        rc = comp.score_batch(list(reqs))
        for a, b in zip(rd, rc):
            assert a.score == b.score
            assert b.bank_generation == 1

    def test_engine_pipeline_parity(self):
        _needs_devices(2)
        comp = _composed_server()
        dense = _tenant_server(4)
        engine = AsyncDispatchEngine(comp, max_batch=6, max_wait_ms=1e9)
        try:
            futs = [engine.submit(_req(f"t{i % 4}", seed=i))
                    for i in range(24)]
            engine.flush()
            scores = [f.result(timeout=60).score for f in futs]
            assert not engine.errors
        finally:
            engine.close()
        want = [r.score for r in dense.score_batch(
            [_req(f"t{i % 4}", seed=i) for i in range(24)])]
        assert scores == want
        assert comp.metrics["shard_dispatches"] >= 1

    def test_engine_prefetch_routes_to_composed_store(self):
        _needs_devices(2)
        # 8 predictors over 2 shards: 4 rows per shard, hot=3 + victims=2
        # slots — a full window leaves cold rows for prefetch to stage
        comp = _composed_server(n_tenants=8)
        comp.score_batch([_req(f"t{i}", i) for i in range(8)])
        assert comp.prefetch_enabled
        names = [f"p{i}" for i in range(8)]
        staged = comp.prefetch_transforms(names, create=False)
        assert staged >= 1
        (store,) = comp.tiered_stores().values()
        assert store.metrics["prefetched_rows"] >= staged

    def test_warm_tiers_across_topologies(self):
        _needs_devices(2)
        single = _tenant_server(4, tiering=_TIER_CFG)
        # one window over all four predictors keys the ("p0".."p3") store,
        # with traffic concentrated on rows 1 and 2 (store rows are
        # group-local: row i serves predictor p<i>)
        reqs = [_req("t1", seed=i) for i in range(3)] + \
            [_req("t2", seed=i + 100) for i in range(3)] + \
            [_req("t0", seed=200), _req("t3", seed=201)]
        single.score_batch(list(reqs))
        single.rebalance_tiers()
        (old_store,) = single.tiered_stores().values()
        assert {1, 2} <= set(old_store.hot_rows().tolist())
        # surge a composed replica from the single-tier victim: the
        # global-indexed snapshot scatters hotness onto the owning shards
        comp = _composed_server()
        assert comp.warm_tiers_from(single) == 1
        (store,) = comp.tiered_stores().values()
        assert isinstance(store, ShardedTieredBankStore)
        assert {1, 2} <= set(store.hot_rows().tolist())
        # ... and back: a single-tier replica warms from the composed one
        single2 = _tenant_server(4, tiering=_TIER_CFG)
        assert single2.warm_tiers_from(comp) == 1
        (s2,) = single2.tiered_stores().values()
        assert {1, 2} <= set(s2.hot_rows().tolist())
