"""Latency and rate arithmetic of the harness."""
import math

import pytest

from bench.common import stats


def test_percentile_is_nearest_rank():
    x = list(range(1, 101))                 # 1..100
    assert stats.percentile(x, 50) == 50
    assert stats.percentile(x, 99) == 99
    assert stats.percentile(x, 100) == 100
    assert stats.percentile([7.0], 99) == 7.0


def test_a_failed_event_counts_as_missing_the_tail():
    x = [1.0] * 98 + [math.inf] * 2
    assert stats.percentile(x, 50) == 1.0
    assert stats.percentile(x, 99) == math.inf


def test_rate_and_spread():
    assert stats.rate(3000, 20.0) == 150.0
    # quartiles of 1..9 by the exclusive method: 2.5 and 7.5, median 5
    assert stats.spread(range(1, 10)) == pytest.approx(1.0)
    assert stats.spread([10.0] * 6) == 0.0



def test_the_generator_draws_tenant_runs_and_group_shares_from_data():
    import types

    import numpy as np

    from bench.common import traffic

    dep = types.SimpleNamespace(config={"features": 4}, groups=[
        {"prefix": "t", "names": ["t0", "t1", "t2"], "share": 0.5,
         "zipf_s": 1.1},
        {"prefix": "w", "names": ["w0", "w1"], "share": 0.5, "zipf_s": 0.0}])
    rng = np.random.default_rng(5)
    sorted_mix = traffic.draw_events(dep, {"run_length": 8}, rng, 64)
    tenants = [r.intent.tenant for r in sorted_mix]
    assert len(tenants) == 64
    assert all(len(set(tenants[i:i + 8])) == 1 for i in range(0, 64, 8))
    walkin_only = traffic.draw_events(
        dep, {"groups": {"t": {"share": 0.0}, "w": {"share": 1.0}}}, rng, 64,
        cover_all=True)
    tenants = [r.intent.tenant for r in walkin_only]
    assert tenants[:5] == ["t0", "t1", "t2", "w0", "w1"]
    assert set(tenants[5:]) == {"w0", "w1"}
