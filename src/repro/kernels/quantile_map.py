"""Pallas TPU kernel for the paper's Quantile Mapping T^Q (Eq. 4).

TPU adaptation (DESIGN.md §2/§6): the paper's O(log N) binary search is a
branchy scalar loop — poison for the VPU.  Here the quantile tables (N <= 2048
f32 values) sit in VMEM; the bucket index is a **branchless compare-and-sum**
(one (BLOCK, N) vector compare + row reduction), and the four table lookups
(q^S_i, q^S_{i+1}, q^R_i, q^R_{i+1}) become one-hot masked row sums — no
data-dependent control flow or addressing anywhere.

Grid: 1-D over score blocks; tables are broadcast to every grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

Array = jax.Array

DEFAULT_BLOCK = 1024


def quantile_lookup(agg, qs, qr):
    """Branchless T^Q (Eq. 4) of ``agg`` (BLOCK,) against per-row tables
    ``qs``/``qr`` of shape (1, N) (one table for the block) or (BLOCK, N).

    The bucket index is a compare-and-sum; the four table reads are one-hot
    masked row sums (exact: one nonzero term each), and the clip bounds are
    the tables' first and last columns taken as static slices — no
    data-dependent addressing, so Mosaic lowers it as plain vector ops."""
    n = qs.shape[-1]
    ge = (agg[:, None] >= qs).astype(jnp.float32)
    idx = jnp.clip(jnp.sum(ge, axis=-1) - 1.0, 0.0, n - 2.0)
    iota_n = jax.lax.broadcasted_iota(
        jnp.int32, (agg.shape[0], n), 1).astype(jnp.float32)
    onehot_i = (iota_n == idx[:, None]).astype(jnp.float32)
    onehot_ip1 = (iota_n == (idx + 1.0)[:, None]).astype(jnp.float32)
    q_s_i = jnp.sum(onehot_i * qs, axis=-1)
    q_s_n = jnp.sum(onehot_ip1 * qs, axis=-1)
    q_r_i = jnp.sum(onehot_i * qr, axis=-1)
    q_r_n = jnp.sum(onehot_ip1 * qr, axis=-1)
    denom = jnp.where(q_s_n - q_s_i > 0, q_s_n - q_s_i, 1.0)
    out = q_r_i + (agg - q_s_i) * (q_r_n - q_r_i) / denom
    lo = jnp.sum(qr[:, :1], axis=-1)
    hi = jnp.sum(qr[:, n - 1:], axis=-1)
    return jnp.clip(out, lo, hi)


def _quantile_map_kernel(scores_ref, src_ref, ref_ref, out_ref):
    s = scores_ref[...].astype(jnp.float32)          # (BLOCK,)
    qs = src_ref[...].astype(jnp.float32)            # (N,)
    qr = ref_ref[...].astype(jnp.float32)            # (N,)
    out_ref[...] = quantile_lookup(s, qs[None, :], qr[None, :]).astype(
        out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantile_map(scores: Array, src_quantiles: Array, ref_quantiles: Array,
                 *, block: int = DEFAULT_BLOCK, interpret: bool | None = None) -> Array:
    """Flat or batched scores -> mapped scores (same shape/dtype)."""
    shape = scores.shape
    flat = scores.reshape(-1)
    n = flat.shape[0]
    block = min(block, max(n, 1))
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    total = flat.shape[0]
    nq = src_quantiles.shape[-1]

    out = pl.pallas_call(
        _quantile_map_kernel,
        grid=(total // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((nq,), lambda i: (0,)),
            pl.BlockSpec((nq,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((total,), scores.dtype),
        interpret=resolve_interpret(interpret),
    )(flat, src_quantiles, ref_quantiles)
    return out[:n].reshape(shape)
