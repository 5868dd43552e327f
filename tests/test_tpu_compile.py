"""Ahead-of-time compiles of the serving hot path for a described TPU v5e.

Nothing runs: each test lowers a kernel or program with ``interpret=False``
against ``v5e:2x2`` devices the TPU compiler describes without a chip
attached, and compiles it.  What Mosaic or XLA:TPU would refuse on the chip
(unsupported primitives, tiling, VMEM budget) fails here.  The topology is
described inside a fixture, never at import, so every pytest-xdist worker
collects the same tests and only the worker that runs this file loads the
TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.transforms import TENANT_AXIS
from repro.kernels import quantile_map as qm_mod
from repro.kernels import score_pipeline as sp_mod
from repro.kernels.quantile_track import _fused_append


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # a described-device compile is written to the cache but cannot be
    # read back without a chip; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("b,k,t,n", [(1024, 3, 48, 128), (1024, 2, 1024, 256)])
def test_score_pipeline_banked_compiles(one_chip, b, k, t, n):
    f32 = jnp.float32
    args = (_spec((b, k), f32, one_chip), _spec((b,), jnp.int32, one_chip),
            _spec((t, k), f32, one_chip), _spec((t, k), f32, one_chip),
            _spec((t, n), f32, one_chip), _spec((t, n), f32, one_chip))
    text = _compiled_text(
        lambda *a: sp_mod.score_pipeline_banked(*a, interpret=False), *args)
    assert "tpu_custom_call" in text


def test_score_pipeline_compiles(one_chip):
    f32 = jnp.float32
    args = (_spec((4096, 3), f32, one_chip), _spec((3,), f32, one_chip),
            _spec((3,), f32, one_chip), _spec((256,), f32, one_chip),
            _spec((256,), f32, one_chip))
    text = _compiled_text(
        lambda *a: sp_mod.score_pipeline(*a, interpret=False), *args)
    assert "tpu_custom_call" in text


def test_quantile_map_compiles(one_chip):
    f32 = jnp.float32
    args = (_spec((4096,), f32, one_chip), _spec((256,), f32, one_chip),
            _spec((256,), f32, one_chip))
    text = _compiled_text(
        lambda *a: qm_mod.quantile_map(*a, interpret=False), *args)
    assert "tpu_custom_call" in text


def test_fused_append_compiles(one_chip):
    # plain XLA (a scatter), no kernel: the tracker's donated staging plane
    f32 = jnp.float32
    args = (_spec((48 * 4096,), f32, one_chip),
            _spec((1024,), jnp.int32, one_chip),
            _spec((1024, 3), f32, one_chip),
            _spec((1024,), jnp.int32, one_chip),
            _spec((48, 3), f32, one_chip), _spec((48, 3), f32, one_chip))
    compiled = _fused_append.lower(*args).compile()
    assert "scatter" in compiled.as_text()


def test_sharded_banked_launch_compiles(topo, monkeypatch):
    """The tenant-sharded dispatch: one shard_map launch of the banked
    kernel over a 4-chip "tenants" mesh, each chip against its own rows."""
    from repro.serving.server import ShardedBankDispatcher
    # the dispatcher's kernel picks interpret mode from the process's
    # backend (the CPU here); steer it to Mosaic for this compile
    monkeypatch.setattr(sp_mod, "resolve_interpret", lambda i: False)
    jax.clear_caches()
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), (TENANT_AXIS,))
    sharded = NamedSharding(mesh, PartitionSpec(TENANT_AXIS))
    s, b, k, t, n = 4, 256, 2, 12, 128
    f32 = jnp.float32
    args = (_spec((s, b, k), f32, sharded), _spec((s, b), jnp.int32, sharded),
            _spec((s, t, k), f32, sharded), _spec((s, t, k), f32, sharded),
            _spec((s, t, n), f32, sharded), _spec((s, t, n), f32, sharded))
    try:
        compiled = ShardedBankDispatcher(mesh)._launch().lower(*args).compile()
    finally:
        jax.clear_caches()   # drop the Mosaic trace before CPU tests reuse it
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # per-shard programs: no collective moves a shard's rows elsewhere
    assert "all-gather" not in text and "all-to-all" not in text
