"""Production mesh definitions.

Single pod: 256 chips as ("data", "model") = (16, 16).
Multi-pod:  512 chips as ("pod", "data", "model") = (2, 16, 16) — "pod" is an
outer data-parallel axis (batch sharded over pod x data; gradient all-reduce
crosses the inter-pod links once per step).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before calling.
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # per chip, FLOP/s
HBM_BW = 819e9                  # per chip, bytes/s
ICI_BW = 50e9                   # per link, bytes/s


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """1-device mesh with the same axis names (CPU tests / examples)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_tenant_mesh(num_shards: int) -> jax.sharding.Mesh:
    """1-D serving mesh over the "tenants" axis (sharded transform banks).

    Each of the ``num_shards`` devices holds one row-shard of every
    :class:`~repro.core.transforms.ShardedTransformBank` — or, under the
    tiered-over-sharded topology, one bounded hot-tier/victim-cache view of
    its shard's host rows (``serving/tiering.ShardedTieredBankStore``); the
    serving layer buckets requests by owning shard and launches the banked
    kernel per shard via ``shard_map`` over this axis.  The axis is
    ``Auto``: shardings over it are placement, not part of array types.
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    avail = jax.device_count()
    if num_shards > avail:
        raise ValueError(
            f"tenant mesh needs {num_shards} devices, have {avail} "
            "(CI: XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return jax.make_mesh((num_shards,), ("tenants",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def tenant_axis_size(mesh: jax.sharding.Mesh) -> int:
    return mesh.shape.get("tenants", 1)


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """The batch-sharding axes for this mesh (('pod','data') or ('data',))."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh: jax.sharding.Mesh) -> int:
    return mesh.shape.get("model", 1)


def data_axis_size(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n
