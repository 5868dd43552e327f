"""The banked kernel chained onto the forward's device output.

``MuseServer.run_models`` queues a window's banked T^C/A/T^Q kernel right
behind its forward when every row is fresh and the group's bank is dense,
and returns the raw scores with the kernel's scores attached;
``apply_transforms`` serves those scores when it resolves the very bank
entry and tenant rows the chain ran under, and dispatches its own kernel
otherwise.  The stage-2 path on a plain array is the reference: chained
scores must equal it bitwise.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predictor import PredictorSpec
from repro.core.routing import (
    Condition,
    Intent,
    RoutingTable,
    ScoringRule,
    ShadowRule,
)
from repro.core.transforms import QuantileMap
from repro.serving import AsyncDispatchEngine, MuseServer, ServerConfig
from repro.serving import spans
from repro.serving.server import _shape_bucket
from repro.serving.tiering import TieringConfig
from repro.serving.types import ScoringRequest

DIM = 8
LEVELS = 64
TENANTS = 3


def _model(seed: int):
    w = np.random.default_rng(seed).normal(0, 1, DIM).astype(np.float32)
    return lambda x: jnp.asarray(
        1.0 / (1.0 + np.exp(-(np.asarray(x, np.float32) @ w))))


FACTORIES = {"m1": lambda: _model(1), "m2": lambda: _model(2)}


def _curved_map(power: float) -> QuantileMap:
    grid = np.linspace(0.0, 1.0, LEVELS)
    return QuantileMap(src_quantiles=jnp.asarray(grid, jnp.float32),
                       ref_quantiles=jnp.asarray(grid ** power, jnp.float32))


def _server(shadows: bool = False, **config) -> MuseServer:
    rules = tuple(ScoringRule(Condition(tenants=(f"t{i}",)), f"p{i}")
                  for i in range(TENANTS)) + (ScoringRule(Condition(), "p0"),)
    shadow_rules = (ShadowRule(Condition(), ("p1",)),) if shadows else ()
    server = MuseServer(RoutingTable(rules, shadow_rules, version="v1"),
                        ServerConfig(**config))
    for i in range(TENANTS):
        # per-tenant weights and maps, so a wrong bank row shows
        server.deploy(PredictorSpec(f"p{i}", ("m1", "m2"), (0.1 * i, 0.4),
                                    (1.0, 0.5 + i), _curved_map(1.0 + i)),
                      FACTORIES)
    return server


def _window(n: int, offset: int = 0):
    reqs = [ScoringRequest(intent=Intent(tenant=f"t{(i * 7) % TENANTS}"),
                           features=np.random.default_rng(offset + i).normal(
                               0, 1, DIM).astype(np.float32))
            for i in range(n)]
    names = [f"p{(i * 7) % TENANTS}" for i in range(n)]
    return reqs, list(range(n)), names


@pytest.mark.parametrize("fused", [True, False], ids=["kernel", "oracle"])
@pytest.mark.parametrize("n", [1, 3, 5, 8, 13])
def test_chained_scores_equal_the_two_stage_path_bitwise(n, fused):
    server = _server(fused_kernel=fused)
    reqs, idxs, names = _window(n)
    raws = server.run_models(reqs, idxs, names)
    assert raws.chain is not None
    scores, bank, tenant_idx = server.apply_transforms(raws, names)
    ref, ref_bank, ref_idx = server.apply_transforms(np.array(raws), names)
    assert scores.shape == (n,)
    np.testing.assert_array_equal(scores, ref)
    np.testing.assert_array_equal(tenant_idx, ref_idx)
    assert bank is ref_bank
    assert server.metrics["kernels_chained"] == 1
    assert server.metrics["kernels_redispatched"] == 0
    assert server.metrics["kernel_dispatches"] == 2
    # the raw matrix is the one run_models has always returned: the
    # forward over the window padded to its bucket, pad rows dropped
    feats = np.zeros((_shape_bucket(n), DIM), np.float32)
    feats[:n] = [r.features for r in reqs]
    np.testing.assert_array_equal(
        raws, np.asarray(server.predictors["p0"].raw_scores(feats))[:n])


def test_a_publish_between_the_stages_redispatches_under_the_new_bank():
    server = _server()
    reqs, idxs, names = _window(5)
    raws = server.run_models(reqs, idxs, names)
    before = server.apply_transforms(np.array(raws), names)[0]
    gen = server.publish_quantile_maps({"p1": _curved_map(0.5)})
    record = {}
    with spans.bind(record):
        scores, bank, _ = server.apply_transforms(raws, names)
    assert bank.generation == gen == server.bank_generation
    fresh, fresh_bank, _ = server.apply_transforms(np.array(raws), names)
    assert fresh_bank is bank
    np.testing.assert_array_equal(scores, fresh)
    assert not np.array_equal(scores, before)       # the publish shows
    assert record["kernel_chained"] == 0
    assert server.metrics["kernels_chained"] == 0
    assert server.metrics["kernels_redispatched"] == 1


def test_other_tenant_rows_than_the_chain_ran_redispatch():
    server = _server()
    reqs, idxs, names = _window(4)
    raws = server.run_models(reqs, idxs, names)
    other = ["p2"] * 4
    scores, _, tenant_idx = server.apply_transforms(raws, other)
    np.testing.assert_array_equal(
        scores, server.apply_transforms(np.array(raws), other)[0])
    assert set(tenant_idx.tolist()) == {2}
    assert server.metrics["kernels_redispatched"] == 1


def test_a_rebuilt_array_views_and_slices_carry_no_chain():
    server = _server()
    reqs, idxs, names = _window(6)
    raws = server.run_models(reqs, idxs, names)
    assert not raws.flags.writeable      # the scores are of these rows
    assert raws[:3].chain is None and raws.view(type(raws)).chain is None
    assert (raws + 0.0).chain is None
    assert type(np.array(raws)) is np.ndarray
    assert type(np.concatenate([raws, raws])) is np.ndarray
    record = {}
    with spans.bind(record):
        server.apply_transforms(np.array(raws), names)
    assert record["kernel_chained"] == 0
    assert server.metrics["kernels_chained"] == 0
    assert server.metrics["kernels_redispatched"] == 0


def test_rows_from_the_raw_cache_take_the_stage_2_path():
    """A shadow window in its request's model group reuses the live rows:
    its raws are not fresh, so its transform stage dispatches."""
    server = _server(shadows=True)
    reqs, _, _ = _window(5)
    responses = server.score_batch(reqs)
    assert len(responses) == 5
    assert server.metrics["kernels_chained"] == 1     # the live window
    assert server.metrics["kernel_dispatches"] == 2   # live + shadow
    assert server.metrics["model_group_calls"] == 1
    assert len(server.sink) == sum(
        len(server.routing.resolve(r.intent).shadows) for r in reqs) > 0


def test_a_window_with_some_cached_rows_takes_the_stage_2_path():
    """Shadow rows in a model group that some of their requests' live
    windows ran: the cached rows and the fresh ones make one window."""
    server = _server(shadows=True)
    server.deploy(PredictorSpec("q", ("m2",), (0.0,), (1.0,),
                                _curved_map(1.5)), FACTORIES)
    server.publish_routing(RoutingTable(
        (ScoringRule(Condition(tenants=("t0",)), "q"),
         ScoringRule(Condition(), "p0")),
        (ShadowRule(Condition(), ("p1",)),), version="v2"))
    reqs, _, _ = _window(6)
    server.score_batch(reqs)
    assert server.metrics["kernels_chained"] == 2     # the two live windows
    assert server.metrics["kernel_dispatches"] == 3
    assert server.metrics["model_group_calls"] == 3   # q, p0, p1's fresh rows
    record = next(r for r in server.sink._records
                  if r.request_id == reqs[0].request_id)
    raws = np.asarray([record.raw_scores], np.float32)
    want = server.apply_transforms(raws, ["p1"])[0][0]
    assert record.score == pytest.approx(want, abs=1e-6)


def test_the_engine_stamps_every_chained_window():
    server = _server()
    engine = AsyncDispatchEngine(server, max_batch=4, max_wait_ms=1e9)
    reqs, _, _ = _window(12)
    futures = [engine.submit(r) for r in reqs]
    engine.flush()
    scores = [f.result(timeout=60).score for f in futures]
    engine.close()
    assert len(engine.window_log) == 3
    assert all(w["kernel_chained"] == 1 for w in engine.window_log)
    assert server.metrics["kernels_chained"] == 3
    assert server.metrics["kernels_redispatched"] == 0
    plain = _server()
    np.testing.assert_array_equal(
        scores, [r.score for k in range(0, 12, 4)
                 for r in plain.score_batch(reqs[k:k + 4])])


def test_a_tiered_topology_never_chains():
    server = _server(tiering=TieringConfig(hot_capacity=1, victim_capacity=2))
    reqs, idxs, names = _window(5)
    raws = server.run_models(reqs, idxs, names)
    assert getattr(raws, "chain", None) is None
    record = {}
    with spans.bind(record):
        server.apply_transforms(raws, names)
    assert record["kernel_chained"] == 0
    assert server.metrics["kernels_chained"] == 0
    assert server.metrics["tier_dispatches"] == 1


SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "tests")
import numpy as np
from test_chained_kernel import _server, _window
from repro.serving.tiering import TieringConfig

for config in ({}, {"tiering": TieringConfig(hot_capacity=1,
                                             victim_capacity=2)}):
    server = _server(tenant_shards=2, **config)
    reqs, idxs, names = _window(5)
    raws = server.run_models(reqs, idxs, names)
    assert getattr(raws, "chain", None) is None
    scores, _, _ = server.apply_transforms(raws, names)
    assert scores.shape == (5,)
    assert server.metrics["kernels_chained"] == 0
    assert server.metrics["shard_dispatches"] == 1
print("SHARDED_NEVER_CHAINS")
"""


def test_a_sharded_topology_never_chains():
    """Two virtual CPU devices, in a subprocess (this one keeps one)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", SHARDED], capture_output=True,
                         text=True, env=env, cwd=root, timeout=300)
    assert "SHARDED_NEVER_CHAINS" in res.stdout, res.stderr[-3000:]
