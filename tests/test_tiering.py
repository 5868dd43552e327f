"""Tiered tenant-bank store: hot device rows, host-paged cold rows, priors.

Covers the three-tier serving contract end to end:

  * **parity** — a tiered dispatch (slot-remapped rows through the same
    fused banked kernel) matches a dense ``TransformBank`` built from the
    same rows BITWISE on f32, in the hot-path steady state, across cold
    misses, multi-pass windows, and promotions;
  * **cold start** — a tenant with no history scores through the fitted
    Beta-mixture default quantiles (Eqs. 6–8) until its stream passes the
    Eq.-5 sample-size gate, then is admitted and (once hot) promoted;
  * **atomic publish** — ``apply_updates`` lands refreshed maps in host
    rows AND every device-resident copy under ONE generation; a
    post-publish read of any tenant — hot, cold, or freshly promoted —
    serves the new generation's parameters (property-tested over random
    promote/demote/publish/mark-cold schedules under the ``tiering``
    marker);
  * **integration** — the single-server and fleet calibration refresh
    paths, the async engine's anti-stall prefetch, and rollout warm-up
    (a surged replica adopting its victim's hot set).

The fast unit subset rides the default (tier-1) lane unmarked; the
campaign classes are ``-m tiering`` (``./test.sh --tiering``).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.hotness import HotnessTracker
from repro.core.predictor import PredictorSpec
from repro.core.quantiles import StreamingQuantileEstimator, required_sample_size
from repro.core.routing import Condition, Intent, RoutingTable, ScoringRule
from repro.core.transforms import QuantileMap, TransformBank, banked_score_pipeline
from repro.kernels import ops
from repro.serving import (
    CalibrationController,
    FleetCalibrationController,
    MuseServer,
    RefreshPolicy,
    Replica,
    ReplicaSet,
    RollingUpdate,
    ServerConfig,
    StaleGenerationError,
)
from repro.serving.engine import AsyncDispatchEngine
from repro.serving.tiering import (
    HostBankStore,
    TieredBankStore,
    TieringConfig,
    prior_bank_row,
)
from repro.serving.types import ScoringRequest

DIM = 8


# --------------------------------------------------------------------------
# shared builders
# --------------------------------------------------------------------------

def _random_bank(rng, t, k=4, n=32) -> TransformBank:
    return TransformBank(
        betas=jnp.asarray(rng.uniform(0.05, 1.0, (t, k)), jnp.float32),
        weights=jnp.asarray(rng.uniform(0.1, 2.0, (t, k)), jnp.float32),
        src_quantiles=jnp.asarray(
            np.sort(rng.uniform(0, 1, (t, n)), -1), jnp.float32),
        ref_quantiles=jnp.asarray(
            np.sort(rng.uniform(0, 1, (t, n)), -1), jnp.float32))


def _dense_scores(bank: TransformBank, raws, tid, fused=True) -> np.ndarray:
    impl = ops.score_pipeline_banked if fused else banked_score_pipeline
    return np.asarray(impl(
        jnp.asarray(raws, jnp.float32), jnp.asarray(tid, jnp.int32),
        bank.betas, bank.weights, bank.src_quantiles, bank.ref_quantiles))


def _bitwise(a: np.ndarray, b: np.ndarray) -> bool:
    return np.array_equal(np.asarray(a, np.float32).view(np.uint32),
                          np.asarray(b, np.float32).view(np.uint32))


# an "easy" Eq.-5 gate: required_sample_size(0.5, 1.0) == 4 events
EASY_GATE = dict(gate_alert_rate=0.5, gate_rel_error=1.0)


def _store(rng, t=32, hot=8, victims=4, **kw) -> tuple[TieredBankStore,
                                                       TransformBank]:
    bank = _random_bank(rng, t)
    cfg = TieringConfig(hot_capacity=hot, victim_capacity=victims,
                        **{**EASY_GATE, **kw})
    return TieredBankStore(HostBankStore.from_bank(bank), cfg), bank


def _linear_model(seed: int, dim: int = DIM):
    rng = np.random.default_rng(seed)
    w = rng.normal(0, 1, dim).astype(np.float32)

    def score(x):
        x = np.asarray(x, np.float32)
        return jnp.asarray(1.0 / (1.0 + np.exp(-(x @ w))))

    return score


FACTORIES = {f"m{i}": (lambda i=i: _linear_model(i)) for i in (1, 2)}
REF64 = np.linspace(0.0, 1.0, 64) ** 2


def _tenant_server(n_tenants=4, tiering: TieringConfig | None = None,
                   version="v1") -> MuseServer:
    """One predictor per tenant over a shared {m1, m2} model group."""
    rules = tuple(ScoringRule(Condition(tenants=(f"t{i}",)), f"p{i}")
                  for i in range(n_tenants)) + \
        (ScoringRule(Condition(), "p0"),)
    server = MuseServer(
        RoutingTable(rules, version=version),
        ServerConfig(refresh_alert_rate=0.05, refresh_rel_error=0.5,
                     tiering=tiering))
    for i in range(n_tenants):
        server.deploy(PredictorSpec(f"p{i}", ("m1", "m2"), (0.2, 0.4),
                                    (1.0, 1.0), QuantileMap.identity(64)),
                      FACTORIES)
    return server


def _req(tenant, seed):
    rng = np.random.default_rng(seed)
    return ScoringRequest(intent=Intent(tenant=tenant),
                          features=rng.normal(0, 1, DIM).astype(np.float32))


def _inject(server, tenant, pred, samples, seed=0):
    est = StreamingQuantileEstimator(capacity=65536, seed=seed)
    est.update(samples)
    server._estimators[(tenant, pred)] = est
    return est


def _policy(**kw) -> RefreshPolicy:
    base = dict(alert_rate=0.05, rel_error=0.5, n_levels=64)
    base.update(kw)
    return RefreshPolicy(**base)


_TIER_CFG = TieringConfig(hot_capacity=3, victim_capacity=2, **EASY_GATE)


# --------------------------------------------------------------------------
# hotness tracker (core/hotness.py)
# --------------------------------------------------------------------------

class TestHotnessTracker:
    def test_decay_orders_recent_over_stale(self):
        tr = HotnessTracker(4, decay=0.5)
        tr.record(np.array([0, 0, 0, 0]))     # old burst on key 0
        tr.tick(3)                            # three quiet windows
        tr.record(np.array([1, 1]))           # fresh traffic on key 1
        assert tr.score(1) > tr.score(0)
        assert list(tr.top(2)) == [1, 0]

    def test_lazy_decay_matches_closed_form(self):
        tr = HotnessTracker(2, decay=0.9)
        expect = 0.0
        for w in range(50):
            tr.record(np.array([0]))
            expect = expect * 0.9 + 0.0  # decay applies on tick below
            tr.tick()
        # score = sum_{w=0..49} 0.9^(50-w) applied per-tick after each record
        want = sum(0.9 ** (50 - w) for w in range(50))
        assert tr.score(0) == pytest.approx(want, rel=1e-12)
        assert tr.windows == 50

    def test_rescale_keeps_scores_exact(self):
        tr = HotnessTracker(2, decay=0.5)
        tr.record(np.array([0]))
        tr.tick(400)                          # 0.5^400 << rescale floor
        tr.record(np.array([1]))
        assert tr.score(1) == pytest.approx(1.0, rel=1e-9)
        assert tr.score(0) == pytest.approx(0.0, abs=1e-100)

    def test_top_respects_mask_and_zero_scores(self):
        tr = HotnessTracker(4, decay=1.0)
        tr.record(np.array([0, 0, 1, 2]))
        mask = np.array([False, True, True, True])
        assert list(tr.top(3, mask=mask)) == [1, 2]   # 0 masked, 3 never seen
        assert list(tr.top(0)) == []

    def test_snapshot_adopt_roundtrip_and_resize(self):
        tr = HotnessTracker(3, decay=0.9)
        tr.record(np.array([0, 1, 1]))
        tr.tick()
        snap = tr.snapshot()
        other = HotnessTracker(5, decay=0.9)
        other.adopt(snap)
        assert other.score(1) == pytest.approx(tr.score(1))
        smaller = HotnessTracker(2, decay=0.9)
        smaller.adopt(snap)                    # common prefix only
        assert smaller.score(0) == pytest.approx(tr.score(0))

    def test_invalid_decay_rejected(self):
        with pytest.raises(ValueError):
            HotnessTracker(2, decay=0.0)
        with pytest.raises(ValueError):
            HotnessTracker(2, decay=1.5)


# --------------------------------------------------------------------------
# host store (authoritative numpy rows)
# --------------------------------------------------------------------------

class TestHostBankStore:
    def test_from_rows_matches_dense_bank_padding(self):
        rng = np.random.default_rng(0)
        params = [
            (rng.uniform(0.1, 1, 2), rng.uniform(0.5, 2, 2),
             np.sort(rng.uniform(0, 1, 16)), np.sort(rng.uniform(0, 1, 16))),
            (rng.uniform(0.1, 1, 3), rng.uniform(0.5, 2, 3),
             np.sort(rng.uniform(0, 1, 8)), np.sort(rng.uniform(0, 1, 8))),
        ]
        host = HostBankStore.from_rows(params)
        bank = TransformBank.from_params(params)
        assert _bitwise(host.betas, np.asarray(bank.betas))
        assert _bitwise(host.src_quantiles, np.asarray(bank.src_quantiles))
        assert host.num_rows == 2 and host.num_experts == 3
        assert host.nbytes == host.betas.nbytes * 2 + \
            host.src_quantiles.nbytes * 2

    def test_write_rows_pads_like_with_rows(self):
        rng = np.random.default_rng(1)
        bank = _random_bank(rng, 4, n=32)
        host = HostBankStore.from_bank(bank)
        qm = QuantileMap(np.sort(rng.uniform(0, 1, 16)),
                         np.sort(rng.uniform(0, 1, 16)))
        host.write_rows({2: qm})
        updated = bank.with_rows({2: qm})
        assert _bitwise(host.src_quantiles, np.asarray(updated.src_quantiles))
        assert _bitwise(host.ref_quantiles, np.asarray(updated.ref_quantiles))

    def test_write_rows_rejects_bad_rows_and_wide_tables(self):
        rng = np.random.default_rng(2)
        host = HostBankStore.from_bank(_random_bank(rng, 4, n=16))
        with pytest.raises(IndexError):
            host.write_rows({9: QuantileMap.identity(16)})
        with pytest.raises(ValueError):
            host.write_rows({0: QuantileMap.identity(64)})

    def test_mismatched_row_counts_rejected(self):
        with pytest.raises(ValueError):
            HostBankStore(np.ones((3, 2)), np.ones((2, 2)),
                          np.ones((3, 8)), np.ones((3, 8)))


# --------------------------------------------------------------------------
# tiered store: parity + staging
# --------------------------------------------------------------------------

class TestTieredDispatchParity:
    def test_bitwise_parity_cold_and_warm(self):
        rng = np.random.default_rng(3)
        store, bank = _store(rng, t=32, hot=8, victims=4)
        raws = rng.uniform(0, 1, (64, 4)).astype(np.float32)
        tid = rng.integers(0, 32, 64)
        want = _dense_scores(bank, raws, tid)
        got, gen = store.dispatch(raws, tid)          # all-miss first window
        assert _bitwise(got, want)
        assert gen == 0
        store.rebalance()                             # promote the hot set
        got2, _ = store.dispatch(raws, tid)           # warm path
        assert _bitwise(got2, want)
        assert store.metrics["hot_hits"] > 0

    def test_oracle_kernel_parity(self):
        rng = np.random.default_rng(4)
        store, bank = _store(rng, t=16, hot=4, victims=2, fused_kernel=False)
        raws = rng.uniform(0, 1, (16, 4)).astype(np.float32)
        tid = rng.integers(0, 16, 16)
        got, _ = store.dispatch(raws, tid)
        want = np.asarray(banked_score_pipeline(
            jnp.asarray(raws), jnp.asarray(tid, jnp.int32), bank.betas,
            bank.weights, bank.src_quantiles, bank.ref_quantiles))
        assert _bitwise(got, want)

    def test_device_bytes_bounded_by_config_not_tenants(self):
        rng = np.random.default_rng(5)
        small, _ = _store(rng, t=32, hot=8, victims=4)
        large, _ = _store(rng, t=2048, hot=8, victims=4)
        assert small.device_bytes == large.device_bytes
        assert large.host_bytes > 16 * small.device_bytes
        # exact bound: (hot + victim + prior row) * (2K+2N) * 4
        assert large.device_bytes == (8 + 4 + 1) * (2 * 4 + 2 * 32) * 4

    def test_window_wider_than_victim_cache_multi_pass(self):
        rng = np.random.default_rng(6)
        store, bank = _store(rng, t=32, hot=2, victims=2)
        raws = rng.uniform(0, 1, (24, 4)).astype(np.float32)
        tid = np.arange(24) % 12                      # 12 distinct cold rows
        want = _dense_scores(bank, raws, tid)
        got, _ = store.dispatch(raws, tid)
        assert _bitwise(got, want)
        assert store.metrics["extra_passes"] > 0      # capacity < working set

    def test_prefetch_removes_cold_miss_stalls(self):
        rng = np.random.default_rng(7)
        store, bank = _store(rng, t=32, hot=8, victims=4)
        tid = np.array([3, 9, 3, 17])
        staged = store.prefetch(tid)
        assert staged == 3                            # distinct cold rows
        raws = rng.uniform(0, 1, (4, 4)).astype(np.float32)
        got, _ = store.dispatch(raws, tid)
        assert _bitwise(got, _dense_scores(bank, raws, tid))
        assert store.metrics["cold_miss_stalls"] == 0
        assert store.metrics["victim_hits"] == 4
        assert store.prefetch(tid) == 0               # already resident

    def test_promotion_moves_hot_tenants_to_hot_slots(self):
        rng = np.random.default_rng(8)
        store, bank = _store(rng, t=32, hot=4, victims=2)
        hot_traffic = np.repeat(np.array([5, 6, 7, 8]), 8)
        raws = rng.uniform(0, 1, (len(hot_traffic), 4)).astype(np.float32)
        store.dispatch(raws, hot_traffic)
        res = store.rebalance()
        assert res["promoted"] == 4
        assert set(store.hot_rows()) == {5, 6, 7, 8}
        store.dispatch(raws, hot_traffic)
        assert store.metrics["hot_hits"] >= len(hot_traffic)
        # shifted traffic demotes the stale hot set after enough windows
        new_traffic = np.repeat(np.array([1, 2, 3, 4]), 8)
        for _ in range(40):
            store.dispatch(raws, new_traffic)
            store.rebalance()
        assert set(store.hot_rows()) == {1, 2, 3, 4}
        assert store.metrics["demotions"] >= 4

    def test_empty_window_is_noop(self):
        rng = np.random.default_rng(9)
        store, _ = _store(rng, t=8)
        out, gen = store.dispatch(np.empty((0, 4), np.float32),
                                  np.empty(0, np.int64))
        assert out.shape == (0,) and gen == 0
        assert store.metrics["dispatches"] == 0

    def test_dispatch_hot_path_has_no_tenant_linear_alloc(self):
        """PR 9 regression: per-window seen counting used
        ``self._seen += np.bincount(tid, minlength=T)`` — an O(T) int64
        temp (8 MB at 10^6 tenants) per window, under the dispatch lock.
        The ``np.add.at`` scatter is O(window); pin the hot-path peak
        well below one O(T) temp."""
        import tracemalloc
        t, k, n = 1_000_000, 1, 2
        host = HostBankStore(
            np.ones((t, k), np.float32), np.ones((t, k), np.float32),
            np.broadcast_to(np.array([0.0, 1.0], np.float32), (t, n)).copy(),
            np.broadcast_to(np.array([0.0, 1.0], np.float32), (t, n)).copy())
        store = TieredBankStore(
            host, TieringConfig(hot_capacity=4, victim_capacity=2,
                                **EASY_GATE))
        rng = np.random.default_rng(10)
        tid = rng.integers(0, 2, 64)
        raws = rng.uniform(0, 1, (64, k)).astype(np.float32)
        store.dispatch(raws, tid)          # warm: stage rows + compile
        tracemalloc.start()
        store.dispatch(raws, tid)          # pure device-hit window
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert store.metrics["cold_miss_stalls"] == 2  # warm-up only
        # the old bincount temp alone was t * 8 = 8_000_000 bytes
        assert peak < 4_000_000, f"O(T) allocation on the hot path: {peak}"

    def test_multipass_pad_slot_eviction_parity(self):
        """_score_slots edge-pads a bucketed slot vector with the LAST
        event's slot, which may be a live victim slot.  Pad references
        must not protect that slot from eviction in later passes of the
        SAME window (protection is rebuilt per pass from the unpadded
        event slots) — force exactly that eviction and require bitwise
        parity."""
        rng = np.random.default_rng(11)
        store, bank = _store(rng, t=8, hot=1, victims=2)
        seen_calls: list[tuple[np.ndarray, np.ndarray]] = []
        orig = store._score_slots

        def spy(raws, slots, view):
            seen_calls.append((np.asarray(slots).copy(),
                               store._owner.copy()))
            return orig(raws, slots, view)

        store._score_slots = spy
        # pass 1 stages rows {0, 1} and scores events [0, 0, 1] — length
        # 3, padded to 4 with row 1's victim slot; pass 2 must evict BOTH
        # victim slots (rows 2, 3 stage over them), re-owning the slot
        # pass 1's pad referenced
        tid = np.array([0, 0, 1, 2, 3])
        raws = rng.uniform(0, 1, (5, 4)).astype(np.float32)
        got, _ = store.dispatch(raws, tid)
        assert _bitwise(got, _dense_scores(bank, raws, tid))
        assert store.metrics["extra_passes"] >= 1
        assert len(seen_calls) >= 2
        slots0, _ = seen_calls[0]
        assert len(slots0) == 3                 # padded call (bucket = 4)
        pad_slot = int(slots0[-1])
        assert store.hot_capacity <= pad_slot < store.hot_capacity + 2
        owners = [own[pad_slot] for _, own in seen_calls]
        assert any(o != owners[0] for o in owners[1:]), \
            "pad-referenced victim slot was never evicted in a later pass"


# --------------------------------------------------------------------------
# overlapped (double-buffered) prefetch staging — the PR 9 stall fix
# --------------------------------------------------------------------------

class TestOverlappedStaging:
    def test_dispatch_proceeds_while_prefetch_copy_in_flight(self,
                                                             monkeypatch):
        """The host->device victim copy runs OFF the dispatch lock: a
        dispatch completes while a prefetch's staged-view build is stuck
        mid-copy (with the lock held across the copy this deadlocks)."""
        import threading
        rng = np.random.default_rng(12)
        store, bank = _store(rng, t=16, hot=2, victims=4)
        store.prefetch(np.array([1, 2]))       # make rows 1, 2 resident
        orig = TieredBankStore._staged_view
        started, release = threading.Event(), threading.Event()

        def slow(self, view, slots, take):
            started.set()
            assert release.wait(timeout=30)
            return orig(self, view, slots, take)

        monkeypatch.setattr(TieredBankStore, "_staged_view", slow)
        result: dict = {}
        th = threading.Thread(
            target=lambda: result.update(n=store.prefetch(np.array([5, 6]))))
        th.start()
        try:
            assert started.wait(timeout=30)
            # copy in flight, lock free: this dispatch (pure victim-hit,
            # no staging of its own) must complete NOW, not after release
            tid = np.array([1, 2, 1])
            raws = rng.uniform(0, 1, (3, 4)).astype(np.float32)
            got, _ = store.dispatch(raws, tid)
            assert _bitwise(got, _dense_scores(bank, raws, tid))
        finally:
            release.set()
            th.join(timeout=30)
        assert result["n"] == 2                # commit landed after release
        assert store.metrics["staging_conflicts"] == 0
        assert {5, 6} <= set(store.resident_rows())

    def test_conflicting_publish_invalidates_staged_view(self, monkeypatch):
        """A publish landing while the prefetch copy is in flight swaps
        the view; the commit's identity check must catch it (conflict),
        restage under the lock, and serve the NEW generation's rows."""
        rng = np.random.default_rng(13)
        store, bank = _store(rng, t=16, hot=2, victims=4)
        qm = QuantileMap(np.sort(rng.uniform(0, 1, 32)),
                         np.linspace(0.0, 1.0, 32) ** 2)
        orig = TieredBankStore._staged_view
        fired: list[int] = []

        def hostile(self, view, slots, take):
            if not fired:
                fired.append(1)
                # runs with NO lock held (that is the point of the
                # overlap) — a concurrent publish swaps the view
                store.apply_updates({5: qm})
            return orig(self, view, slots, take)

        monkeypatch.setattr(TieredBankStore, "_staged_view", hostile)
        assert store.prefetch(np.array([5, 6])) == 2   # restaged path
        assert store.metrics["staging_conflicts"] == 1
        assert store.generation == 1
        # the restaged rows carry the POST-publish host values
        tid = np.array([5, 6])
        raws = rng.uniform(0, 1, (2, 4)).astype(np.float32)
        got, gen = store.dispatch(raws, tid)
        assert gen == 1
        assert store.metrics["cold_miss_stalls"] == 0
        want_bank = store.host.dense_bank(1)
        assert _bitwise(got, _dense_scores(want_bank, raws, tid))

    def test_mark_cold_during_copy_vetoes_commit(self, monkeypatch):
        """mark_cold flips admission WITHOUT swapping the view — the
        commit must re-check eligibility, not just view identity, or a
        cold-marked tenant's stale row lands device-resident."""
        rng = np.random.default_rng(14)
        store, _ = _store(rng, t=16, hot=2, victims=4)
        orig = TieredBankStore._staged_view
        fired: list[int] = []

        def hostile(self, view, slots, take):
            if not fired:
                fired.append(1)
                store.mark_cold([5])
            return orig(self, view, slots, take)

        monkeypatch.setattr(TieredBankStore, "_staged_view", hostile)
        assert store.prefetch(np.array([5])) == 0
        assert store.metrics["staging_conflicts"] == 1
        assert 5 not in set(store.resident_rows())

    def test_legacy_locked_staging_still_correct(self):
        """overlap_staging=False keeps the old hold-the-lock-across-the-
        copy behavior (the bench's before/after baseline)."""
        rng = np.random.default_rng(15)
        store, bank = _store(rng, t=16, hot=2, victims=4,
                             overlap_staging=False)
        assert store.prefetch(np.array([3, 4, 5])) == 3
        tid = np.array([3, 4, 5, 3])
        raws = rng.uniform(0, 1, (4, 4)).astype(np.float32)
        got, _ = store.dispatch(raws, tid)
        assert _bitwise(got, _dense_scores(bank, raws, tid))
        assert store.metrics["cold_miss_stalls"] == 0
        assert store.metrics["staging_conflicts"] == 0


# --------------------------------------------------------------------------
# tiered store: publish + fencing (the control-plane contract)
# --------------------------------------------------------------------------

class TestTieredPublish:
    def test_publish_updates_hot_and_cold_rows_atomically(self):
        rng = np.random.default_rng(10)
        store, bank = _store(rng, t=16, hot=4, victims=2)
        hot_traffic = np.repeat(np.array([0, 1, 2, 3]), 4)
        raws16 = rng.uniform(0, 1, (16, 4)).astype(np.float32)
        store.dispatch(raws16, hot_traffic)
        store.rebalance()                              # 0..3 hot
        assert set(store.hot_rows()) == {0, 1, 2, 3}

        updates = {r: QuantileMap(np.sort(rng.uniform(0, 1, 32)),
                                  np.sort(rng.uniform(0, 1, 32)))
                   for r in (1, 9)}                    # one hot, one cold
        gen = store.apply_updates(updates)
        assert gen == 1 and store.generation == 1
        new_bank = bank.with_rows(updates, generation=1)
        tid = np.array([1, 9, 1, 9, 4, 0])             # hot+cold+untouched
        raws = rng.uniform(0, 1, (6, 4)).astype(np.float32)
        got, got_gen = store.dispatch(raws, tid)
        assert got_gen == 1
        assert _bitwise(got, _dense_scores(new_bank, raws, tid))

    def test_fenced_publish_rejects_stale_and_fast_forwards(self):
        rng = np.random.default_rng(11)
        store, _ = _store(rng, t=8)
        assert store.apply_updates({}, generation=5) == 5   # fast-forward
        with pytest.raises(StaleGenerationError):
            store.apply_updates({}, generation=5)           # not strictly newer
        with pytest.raises(StaleGenerationError):
            store.apply_updates(
                {0: QuantileMap.identity(32)}, generation=3)
        assert store.generation == 5
        assert store.apply_updates({}) == 5                 # empty unfenced noop

    def test_rebalance_fencing(self):
        rng = np.random.default_rng(12)
        store, _ = _store(rng, t=8)
        store.apply_updates({}, generation=4)
        with pytest.raises(StaleGenerationError):
            store.rebalance(generation=3)      # superseded control decision
        store.rebalance(generation=4)          # current stamp is fine
        store.rebalance()                      # and unfenced always is
        assert store.generation == 4           # rebalance never bumps

    def test_mark_cold_evicts_and_routes_through_prior(self):
        rng = np.random.default_rng(13)
        prior_src = np.sort(rng.uniform(0, 1, 32))
        prior = prior_bank_row(prior_src, np.linspace(0, 1, 32), 4)
        store, bank = _store(rng, t=8, hot=4, victims=2, prior=prior)
        raws = rng.uniform(0, 1, (8, 4)).astype(np.float32)
        tid = np.full(8, 2)
        store.dispatch(raws, tid)
        store.rebalance()
        assert 2 in store.hot_rows()
        store.mark_cold([2])
        assert 2 not in store.resident_rows()
        got, _ = store.dispatch(raws, tid)
        prior_bank = TransformBank.from_params([prior])
        want = _dense_scores(prior_bank, raws, np.zeros(8, np.int64))
        assert _bitwise(got, want)             # scored via the prior row
        assert store.metrics["prior_scores"] >= 8


# --------------------------------------------------------------------------
# cold start: Beta-mixture prior -> Eq.-5 gate -> admission -> promotion
# --------------------------------------------------------------------------

class TestColdStartIntegration:
    def test_new_tenant_scores_through_fitted_prior_then_promotes(self):
        """Satellite: no-history tenant serves the fitted Beta-mixture
        default quantiles; once its stream passes the Eq.-5 gate it is
        admitted and promoted, with bitwise parity against a dense bank."""
        from repro.core.coldstart import BetaMixtureFit
        rng = np.random.default_rng(14)
        fit = BetaMixtureFit(w=0.15, a0=2.0, b0=9.0, a1=7.0, b1=2.0,
                             jsd=0.0, moment_loss=0.0)
        ref = np.linspace(0.0, 1.0, 32) ** 1.5
        prior = prior_bank_row(fit, ref, num_experts=4)

        bank = _random_bank(rng, 8)
        admitted = np.ones(8, bool)
        admitted[5] = False                    # tenant 5 has no history
        host = HostBankStore.from_bank(bank, admitted=admitted)
        cfg = TieringConfig(hot_capacity=4, victim_capacity=2,
                            prior=prior, **EASY_GATE)
        store = TieredBankStore(host, cfg)
        assert store.gate_samples == required_sample_size(0.5, 1.0)

        raws = rng.uniform(0, 1, (2, 4)).astype(np.float32)
        tid = np.full(2, 5)
        got, _ = store.dispatch(raws, tid)     # 2 events < gate of 4
        prior_bank = TransformBank.from_params([prior])
        want_prior = _dense_scores(prior_bank, raws, np.zeros(2, np.int64))
        assert _bitwise(got, want_prior)
        store.rebalance()
        assert 5 not in store.hot_rows()       # still behind the gate

        got, _ = store.dispatch(raws, tid)     # 4 events total == gate
        assert _bitwise(got, want_prior)       # gate applies until rebalance
        res = store.rebalance()
        assert res["admitted"] == 1
        assert store.seen(5) >= store.gate_samples
        assert 5 in store.hot_rows()           # only recent traffic -> hot
        got, _ = store.dispatch(raws, tid)
        assert _bitwise(got, _dense_scores(bank, raws, tid))  # own row now

    def test_prior_row_from_raw_table_interpolates(self):
        src = np.sort(np.random.default_rng(15).uniform(0, 1, 16))
        ref = np.linspace(0, 1, 32)
        b, w, s, r = prior_bank_row(src, ref, num_experts=3)
        assert b.shape == (3,) and w.shape == (3,)
        assert s.shape == (32,) and r.shape == (32,)   # interpolated to ref
        assert np.all(np.diff(s) >= 0)

    def test_coldstart_module_importable_without_scipy(self):
        """Satellite: the scipy import is lazy — serving-only deployments
        construct BetaMixtureFit and build prior rows without scipy."""
        code = (
            "import sys\n"
            "class _Block:\n"
            "    def find_spec(self, name, path=None, target=None):\n"
            "        if name.split('.')[0] == 'scipy':\n"
            "            raise ImportError('scipy blocked')\n"
            "        return None\n"
            "sys.meta_path.insert(0, _Block())\n"
            "sys.modules.pop('scipy', None)\n"
            "from repro.core.coldstart import BetaMixtureFit, "
            "default_quantile_map\n"
            "fit = BetaMixtureFit(0.1, 2, 8, 8, 2, 0.0, 0.0)\n"
            "print('OK', fit.w)\n"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True,
                             env=env, cwd="/root/repo", timeout=120)
        assert out.returncode == 0, out.stderr
        assert "OK 0.1" in out.stdout


# --------------------------------------------------------------------------
# tiered server (sync data path)
# --------------------------------------------------------------------------

class TestTieredServer:
    def test_scores_match_dense_server(self):
        dense = _tenant_server(4)
        tiered = _tenant_server(4, tiering=_TIER_CFG)
        reqs = [_req(f"t{i % 4}", seed=i) for i in range(12)]
        rd = dense.score_batch(list(reqs))
        rt = tiered.score_batch(list(reqs))
        for a, b in zip(rd, rt):
            assert a.score == b.score
            assert a.bank_generation == b.bank_generation == 0
        assert tiered.metrics["tier_dispatches"] >= 1
        assert tiered.tier_metrics()["events"] == 12

    def test_publish_then_parity_and_generation_stamp(self):
        rng = np.random.default_rng(16)
        dense = _tenant_server(4)
        tiered = _tenant_server(4, tiering=_TIER_CFG)
        reqs = [_req(f"t{i % 4}", seed=i) for i in range(8)]
        dense.score_batch(list(reqs))
        tiered.score_batch(list(reqs))
        qm = QuantileMap(np.sort(rng.uniform(0, 1, 64)), REF64)
        upd = {"p1": qm, "p3": qm}
        assert dense.publish_quantile_maps(dict(upd)) == 1
        assert tiered.publish_quantile_maps(dict(upd)) == 1
        rd = dense.score_batch(list(reqs))
        rt = tiered.score_batch(list(reqs))
        for a, b in zip(rd, rt):
            assert a.score == b.score
            assert b.bank_generation == 1

    def test_fenced_publish_fast_forwards_tiered_stores(self):
        tiered = _tenant_server(2, tiering=_TIER_CFG)
        tiered.score_batch([_req("t0", 0)])
        tiered.publish_quantile_maps({}, generation=7)
        (store,) = tiered.tiered_stores().values()
        assert store.generation == 7
        resp = tiered.score_batch([_req("t0", 1)])[0]
        assert resp.bank_generation == 7
        with pytest.raises(StaleGenerationError):
            tiered.publish_quantile_maps({}, generation=7)

    def test_tiering_composes_with_sharding(self):
        # tiering + tenant_shards is the composed topology now (PR 9 lifted
        # the old mutual exclusion): the store behind the bank cache is the
        # per-shard-tiered ShardedTieredBankStore and scores stay bitwise-
        # equal to the dense server.  S=2 needs 2 devices (the tenant mesh
        # is built eagerly), so this runs under ./test.sh lanes; tier-1
        # single-device coverage is the S=1 composed path in
        # tests/test_tiered_sharded.py.
        if jax.device_count() < 2:
            pytest.skip("needs 2 devices (XLA_FLAGS host-device count)")
        from repro.serving.tiering import ShardedTieredBankStore
        rules = tuple(ScoringRule(Condition(tenants=(f"t{i}",)), f"p{i}")
                      for i in range(4)) + \
            (ScoringRule(Condition(), "p0"),)
        comp = MuseServer(RoutingTable(rules, version="v1"),
                          ServerConfig(tenant_shards=2, tiering=_TIER_CFG))
        for i in range(4):
            comp.deploy(PredictorSpec(f"p{i}", ("m1", "m2"), (0.2, 0.4),
                                      (1.0, 1.0), QuantileMap.identity(64)),
                        FACTORIES)
        dense = _tenant_server(4)
        reqs = [_req(f"t{i % 4}", seed=i) for i in range(12)]
        rd = dense.score_batch(list(reqs))
        rc = comp.score_batch(list(reqs))
        for a, b in zip(rd, rc):
            assert a.score == b.score
        (store,) = comp.tiered_stores().values()
        assert isinstance(store, ShardedTieredBankStore)
        assert store.num_shards == 2
        assert comp.metrics["shard_dispatches"] >= 1
        assert comp.metrics["tier_dispatches"] >= 1

    def test_decommission_drops_group_stores(self):
        tiered = _tenant_server(2, tiering=_TIER_CFG)
        tiered.score_batch([_req("t0", 0)])
        assert tiered.tiered_stores()
        tiered.decommission("p0")
        tiered.decommission("p1")
        assert not tiered.tiered_stores()

    def test_mark_cold_tenants_routes_through_prior(self):
        prior = prior_bank_row(np.linspace(0, 1, 64) ** 2, REF64, 2)
        cfg = TieringConfig(hot_capacity=3, victim_capacity=2,
                            prior=prior, **EASY_GATE)
        tiered = _tenant_server(4, tiering=cfg)
        dense = _tenant_server(4)
        # a dense twin whose p2 predictor IS the prior row (beta=1,
        # uniform weights, the prior's T^Q)
        oracle = _tenant_server(4)
        oracle.deploy(
            PredictorSpec("p2", ("m1", "m2"), (1.0, 1.0), (1.0, 1.0),
                          QuantileMap(np.asarray(prior[2], np.float64),
                                      np.asarray(prior[3], np.float64))),
            FACTORIES)
        tiered.mark_cold_tenants(["p2"])
        reqs = [_req("t2", seed=i) for i in range(3)]
        rt = tiered.score_batch(list(reqs))
        ro = oracle.score_batch(list(reqs))
        rd = dense.score_batch(list(reqs))
        for a, b, c in zip(rt, ro, rd):
            assert a.score == pytest.approx(b.score, abs=1e-7)
            assert a.score != c.score          # genuinely the prior, not row
        # its estimator stream still tracks (through the prior's pre-Q path)
        assert ("t2", "p2") in tiered.estimator_streams()

    def test_server_prefetch_endpoint(self):
        tiered = _tenant_server(4, tiering=_TIER_CFG)
        # one 4-predictor window builds the ("p0".."p3") store; the
        # multi-pass dispatch leaves rows {2, 3} in the victim cache
        tiered.score_batch([_req(f"t{i}", i) for i in range(4)])
        assert tiered.prefetch_enabled
        names = ["p0", "p1", "p2", "p3"]
        assert tiered.prefetch_transforms(names, create=False) == 2  # 0, 1
        # that prefetch evicted 2 and 3 — the same call stages them back
        assert tiered.prefetch_transforms(names, create=False) == 2
        (store,) = tiered.tiered_stores().values()
        assert store.metrics["prefetched_rows"] == 4
        # any subset of the group pages through the group's one store:
        # p0 was evicted by the last prefetch, so it stages back
        assert tiered.prefetch_transforms(["p0"], create=False) == 1
        assert list(tiered.tiered_stores().values()) == [store]
        # the poll path never creates a store
        fresh = _tenant_server(4, tiering=_TIER_CFG)
        assert fresh.prefetch_transforms(["p0"], create=False) == 0
        assert not fresh.tiered_stores()
        dense = _tenant_server(2)
        assert not dense.prefetch_enabled


# --------------------------------------------------------------------------
# calibration refresh through the tiers (single server, fast path)
# --------------------------------------------------------------------------

class TestTieredCalibrationRefresh:
    def test_refresh_updates_hot_cold_and_promotes_admitted(self):
        rng = np.random.default_rng(17)
        gate = required_sample_size(0.05, 0.5)
        dense = _tenant_server(3)
        tiered = _tenant_server(3, tiering=_TIER_CFG)
        streams = {f"p{i}": rng.uniform(0, 1, gate + 50) for i in range(3)}
        for i in range(3):
            _inject(dense, f"t{i}", f"p{i}", streams[f"p{i}"], seed=i)
            _inject(tiered, f"t{i}", f"p{i}", streams[f"p{i}"], seed=i)
        reqs = [_req(f"t{i % 3}", seed=i) for i in range(9)]
        dense.score_batch(list(reqs))
        tiered.score_batch(list(reqs))

        rd = CalibrationController(dense, REF64, _policy()).refresh_fleet()
        rt = CalibrationController(tiered, REF64, _policy()).refresh_fleet()
        keys = {(r.tenant, r.predictor) for r in rt.refreshed}
        assert keys == {(r.tenant, r.predictor) for r in rd.refreshed}
        assert keys
        assert tiered.bank_generation == dense.bank_generation

        out_d = dense.score_batch(list(reqs))
        out_t = tiered.score_batch(list(reqs))
        for a, b in zip(out_d, out_t):
            assert a.score == b.score
            assert b.bank_generation == tiered.bank_generation
        # the controller ran a post-publish rebalance: refreshed tenants
        # with traffic now hold hot slots
        (store,) = tiered.tiered_stores().values()
        assert store.metrics["promotions"] >= 1


# ==========================================================================
# tiering-marked campaigns
# ==========================================================================

@pytest.mark.tiering
class TestPromoteDemotePublishProperty:
    """Acceptance: across random promote/demote/publish/mark-cold schedules,
    a post-publish read of ANY tenant serves the new generation's params —
    bitwise equal to a dense bank rebuilt from the authoritative host rows,
    stamped with the store's current generation."""

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_random_schedule_serves_current_generation(self, seed):
        rng = np.random.default_rng(seed)
        t, k, n = 24, 2, 16
        bank = _random_bank(rng, t, k=k, n=n)
        admitted = rng.random(t) > 0.25
        store = TieredBankStore(
            HostBankStore.from_bank(bank, admitted=admitted.copy()),
            TieringConfig(hot_capacity=int(rng.integers(2, 8)),
                          victim_capacity=int(rng.integers(2, 5)),
                          fused_kernel=False, **EASY_GATE))
        prior_tab = (np.asarray(store._view.src_quantiles[-1]),
                     np.asarray(store._view.ref_quantiles[-1]))

        def oracle() -> TransformBank:
            return store.host.dense_bank(store.generation)

        for _ in range(25):
            op = rng.integers(0, 5)
            if op == 0:                                  # dispatch a window
                b = int(rng.integers(1, 12))
                tid = rng.integers(0, t, b)
                raws = rng.uniform(0, 1, (b, k)).astype(np.float32)
                got, gen = store.dispatch(raws, tid)
                assert gen == store.generation
                dense = oracle()
                adm = store.host.admitted[tid]
                eff_tid = np.where(adm, tid, 0)
                want = _dense_scores(dense, raws, eff_tid, fused=False)
                prior_want = _dense_scores(
                    TransformBank.from_params(
                        [(np.ones(k), np.ones(k)) + prior_tab]),
                    raws, np.zeros(b, np.int64), fused=False)
                want = np.where(adm, want, prior_want)
                assert _bitwise(got, want)
            elif op == 1:                                # rebalance
                store.rebalance()
            elif op == 2:                                # publish
                rows = rng.choice(t, size=int(rng.integers(1, 5)),
                                  replace=False)
                updates = {int(r): QuantileMap(
                    np.sort(rng.uniform(0, 1, n)),
                    np.sort(rng.uniform(0, 1, n))) for r in rows}
                before = store.generation
                assert store.apply_updates(updates) == before + 1
            elif op == 3:                                # mark cold
                store.mark_cold([int(rng.integers(0, t))])
            else:                                        # prefetch
                store.prefetch(rng.integers(0, t, 6))
            # structural invariants: slot maps stay a bijection
            owners = store._owner[store._owner >= 0]
            assert len(owners) == len(set(owners.tolist()))
            for tid_ in owners:
                assert store._owner[store._slot_of[tid_]] == tid_

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_fenced_interleaving_monotone_generations(self, seed):
        rng = np.random.default_rng(seed)
        store, _ = _store(np.random.default_rng(seed), t=12, hot=4,
                          victims=2, fused_kernel=False)
        gen = 0
        for _ in range(20):
            target = gen + int(rng.integers(-2, 4))
            try:
                store.apply_updates(
                    {int(rng.integers(0, 12)): QuantileMap.identity(32)}
                    if rng.random() < 0.5 else {},
                    generation=target)
                assert target > gen            # accepted => strictly newer
                gen = target
            except StaleGenerationError:
                assert target <= gen           # rejected => stale stamp
            assert store.generation == gen


@pytest.mark.tiering
class TestTieredFleetRefresh:
    def test_fleet_publish_lands_in_both_tiers_of_every_replica(self):
        rng = np.random.default_rng(18)
        gate = required_sample_size(0.05, 0.5)
        reps = [Replica(i, _tenant_server(3, tiering=_TIER_CFG), "v1",
                        ready=True) for i in range(2)]
        rs = ReplicaSet(reps)
        reqs = [_req(f"t{i % 3}", seed=i) for i in range(9)]
        for rep in reps:
            rep.server.score_batch(list(reqs))
            for i in range(3):
                _inject(rep.server, f"t{i}", f"p{i}",
                        rng.uniform(0, 1, gate // 2 + 40), seed=i)
        fleet = FleetCalibrationController(rs, REF64, _policy())
        res = fleet.refresh_fleet()
        assert res.refreshed and not res.nacked
        gens = {rep.server.bank_generation for rep in reps}
        assert len(gens) == 1                  # converged fleet generation
        gen = gens.pop()
        outs = [rep.server.score_batch(list(reqs)) for rep in reps]
        for a, b in zip(*outs):
            assert a.score == b.score          # replicas agree post-publish
            assert a.bank_generation == gen
        for rep in reps:
            (store,) = rep.server.tiered_stores().values()
            assert store.generation == gen


@pytest.mark.tiering
class TestEnginePrefetch:
    def test_poll_prefetches_pending_window_rows(self):
        tiered = _tenant_server(4, tiering=_TIER_CFG)
        # build the ("p0".."p3") store the pending mixed window will key to
        tiered.score_batch([_req(f"t{i}", i) for i in range(4)])
        (store,) = tiered.tiered_stores().values()
        base_staged = store.metrics["prefetched_rows"]
        engine = AsyncDispatchEngine(tiered, max_batch=64, max_wait_ms=1e9)
        assert engine._prefetchable
        try:
            futs = [engine.submit(_req(f"t{i % 4}", seed=i))
                    for i in range(8)]
            engine.poll()                      # window still accumulating
            assert store.metrics["prefetched_rows"] > base_staged
            engine.flush()
            scores = [f.result(timeout=60).score for f in futs]
        finally:
            engine.close()
        dense = _tenant_server(4)
        want = [r.score for r in dense.score_batch(
            [_req(f"t{i % 4}", seed=i) for i in range(8)])]
        assert scores == want

    def test_poll_counts_unexpected_prefetch_faults(self, monkeypatch):
        """A real prefetch bug (bad tenant id, torn store ref) must not be
        swallowed silently: poll survives, but the fault is counted in
        ``prefetch_errors`` and lands in ``errors``."""
        tiered = _tenant_server(4, tiering=_TIER_CFG)
        tiered.score_batch([_req(f"t{i}", i) for i in range(4)])
        engine = AsyncDispatchEngine(tiered, max_batch=64, max_wait_ms=1e9)
        try:
            engine.submit(_req("t1", seed=0))

            def boom(names, plane=None, *, create=False):
                raise IndexError("torn store ref")

            monkeypatch.setattr(tiered, "prefetch_transforms", boom)
            engine.poll()                      # must not raise
            assert engine.prefetch_errors == 1
            assert any(isinstance(e, IndexError) for _, e in engine.errors)
            # the window itself still dispatches (prefetch is best-effort)
            monkeypatch.undo()
            engine.flush()
            engine.drain()
        finally:
            engine.close()

    def test_poll_ignores_expected_dispatch_race(self, monkeypatch):
        """KeyError is the expected race (window dispatched / predictor
        undeployed between the locked collection and the prefetch call):
        not an error, not counted."""
        tiered = _tenant_server(4, tiering=_TIER_CFG)
        tiered.score_batch([_req(f"t{i}", i) for i in range(4)])
        engine = AsyncDispatchEngine(tiered, max_batch=64, max_wait_ms=1e9)
        try:
            engine.submit(_req("t1", seed=0))

            def race(names, plane=None, *, create=False):
                raise KeyError("p1")

            monkeypatch.setattr(tiered, "prefetch_transforms", race)
            engine.poll()
            assert engine.prefetch_errors == 0
            assert not engine.errors
        finally:
            engine.close()

    def test_model_stage_prefetch_fault_counted_window_survives(
            self, monkeypatch):
        """The model stage's create=True prefetch: an unexpected fault is
        counted but the window still scores (paying the stall the
        prefetch would have hidden); the expected KeyError race stays
        uncounted."""
        tiered = _tenant_server(4, tiering=_TIER_CFG)
        real = tiered.prefetch_transforms
        mode = {"exc": ValueError("bad tenant id")}

        def flaky(names, plane=None, *, create=False):
            if create and mode["exc"] is not None:
                raise mode["exc"]
            return real(names, plane, create=create)

        monkeypatch.setattr(tiered, "prefetch_transforms", flaky)
        engine = AsyncDispatchEngine(tiered, max_batch=4, max_wait_ms=1e9)
        try:
            futs = [engine.submit(_req(f"t{i}", seed=i)) for i in range(4)]
            engine.flush()
            scores = [f.result(timeout=60).score for f in futs]
            assert engine.prefetch_errors == 1
            assert any(isinstance(e, ValueError) for _, e in engine.errors)
            dense = _tenant_server(4)
            want = [r.score for r in dense.score_batch(
                [_req(f"t{i}", seed=i) for i in range(4)])]
            assert scores == want              # window served regardless
            mode["exc"] = KeyError("p0")       # expected race: uncounted
            futs = [engine.submit(_req(f"t{i}", seed=10 + i))
                    for i in range(4)]
            engine.flush()
            for f in futs:
                f.result(timeout=60)
            assert engine.prefetch_errors == 1
        finally:
            engine.close()

    def test_engine_pipeline_stalls_only_before_prefetch_lands(self):
        """Through the full engine pipeline the model stage's create=True
        prefetch staging means the transform stage's dispatch finds its
        rows resident (no cold-miss stalls after the first window)."""
        tiered = _tenant_server(4, tiering=_TIER_CFG)
        engine = AsyncDispatchEngine(tiered, max_batch=4, max_wait_ms=1e9)
        try:
            futs = [engine.submit(_req(f"t{i}", seed=i)) for i in range(4)]
            engine.flush()
            for f in futs:
                f.result(timeout=60)
            (store,) = tiered.tiered_stores().values()
            first = store.metrics["stalled_events"]
            tiered.rebalance_tiers()           # hot set = 3 of the 4 rows
            for batch in range(1, 4):
                futs = [engine.submit(_req(f"t{i}", seed=batch * 4 + i))
                        for i in range(4)]
                engine.flush()
                for f in futs:
                    f.result(timeout=60)
            # the model stage's create=True prefetch stages the sole
            # non-hot row before each window's dispatch: no new stalls
            assert store.metrics["stalled_events"] == first
            assert store.metrics["prefetched_rows"] >= 1
        finally:
            engine.close()


@pytest.mark.tiering
class TestRolloutWarmStart:
    def test_warm_tiers_from_adopts_hot_set(self):
        old = _tenant_server(4, tiering=_TIER_CFG)
        # one window over all four predictors, traffic concentrated on t1/t2
        reqs = [_req("t1", seed=i) for i in range(3)] + \
            [_req("t2", seed=i + 100) for i in range(3)] + \
            [_req("t0", seed=200), _req("t3", seed=201)]
        old.score_batch(list(reqs))
        old.rebalance_tiers()
        (old_store,) = old.tiered_stores().values()
        assert {1, 2} <= set(old_store.hot_rows())

        new = _tenant_server(4, tiering=_TIER_CFG, version="v2")
        assert new.warm_tiers_from(old) == 1
        (new_store,) = new.tiered_stores().values()
        assert set(new_store.hot_rows()) == set(old_store.hot_rows())
        # the adopted hot set serves the concentrated mix from hot slots;
        # only the single non-hot row can page (2 of its events at most)
        new.score_batch(list(reqs))
        assert new_store.metrics["hot_hits"] >= 6
        assert new_store.metrics["stalled_events"] <= 2
        # non-tiered servers are a no-op on either side
        assert _tenant_server(2).warm_tiers_from(old) == 0
        assert new.warm_tiers_from(_tenant_server(2)) == 0  # dense source

    def test_rolling_update_warms_surged_replicas(self):
        def make(version):
            return _tenant_server(3, tiering=_TIER_CFG, version=version)

        reps = [Replica(i, make("v1"), "v1", ready=True) for i in range(2)]
        rs = ReplicaSet(reps)
        seed_reqs = [_req(f"t{i % 3}", seed=i) for i in range(12)]
        for rep in reps:
            rep.server.score_batch(list(seed_reqs))
            rep.server.rebalance_tiers()
        update = RollingUpdate(rs, lambda: make("v2"), "v2",
                               schema_dim=DIM, warmup_batch_sizes=(1, 2))

        def traffic():
            i = 0
            while True:
                yield [_req(f"t{i % 3}", seed=i), _req(f"t{(i+1) % 3}",
                                                       seed=i + 1)]
                i += 2

        update.run_with_traffic(traffic(), batches_per_transition=1)
        assert all(rep.version == "v2" for rep in rs.replicas)
        for rep in rs.replicas:
            stores = rep.server.tiered_stores()
            assert stores                      # surged replicas are tiered
            # warm start: the victim's hotness was adopted, so at least one
            # group store promoted a hot set instead of starting from zero
            assert any(len(s.hot_rows()) >= 1 for s in stores.values())
