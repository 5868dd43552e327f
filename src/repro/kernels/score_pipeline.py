"""Fused Pallas kernels for the full Eq. 2 post-model pipeline:

    T^Q( A( [T^C_k(y_k)]_k ) )   —  posterior correction -> weighted
                                     aggregation -> quantile map

One VMEM pass over a (BLOCK, K) score tile instead of K+2 HBM round trips:
the correction is elementwise, the aggregation a K-term row sum, and the
quantile map reuses the branchless compare-and-sum + one-hot lookup of
kernels/quantile_map.py.  This kernel IS the paper's transformation
DAG as a single fused op — the serving hot path for every scored event.

Two entry points:

  * :func:`score_pipeline`        — one shared (betas, weights, q-tables)
                                    parameter set for the whole batch.
  * :func:`score_pipeline_banked` — tenant-indexed: parameters are (T, ·)
                                    banks and each row carries a
                                    ``tenant_idx`` gathered INSIDE the kernel,
                                    so a single ``pallas_call`` scores a
                                    mixed-tenant micro-batch.

The banked kernel distils ``tenant_idx`` into per-block scalars carried via
``pltpu.PrefetchScalarGridSpec``: for every grid block the wrapper computes
(block_tenant, block_uniform) — available in SMEM before the block body runs
(and to the block index maps).  An all-one-tenant block skips the dense
(BLOCK, T) one-hot gather matmuls entirely and loads its single parameter
row with one scalar-indexed slice; only genuinely mixed blocks pay the
one-hot path.  Real traffic is bursty per tenant (and the sharded serving
path buckets rows by owning shard, which sorts them by tenant), so most
serving blocks take the fast path — :func:`banked_skip_stats` reports the
realized skip rate for a given layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.quantile_map import quantile_lookup

Array = jax.Array

DEFAULT_BLOCK = 1024
# scoped-VMEM budget of the banked kernel: the mixed-block path holds the
# (BLOCK, T) one-hot selector, the resident banks and their exact-precision
# gather operands at once (~21 MiB at BLOCK=1024, T=1024, N=256 — over
# Mosaic's 16 MiB default, well under a v5e core's 128 MiB)
BANKED_VMEM_LIMIT = 64 * 1024 * 1024


def _round_block(n: int, block: int) -> int:
    """Next power of two >= n, capped at ``block`` — bounds the number of
    distinct (block,) jit specializations the serving layer can trigger."""
    b = 1
    while b < min(n, block):
        b *= 2
    return min(b, block)


def _score_pipeline_kernel(scores_ref, betas_ref, weights_ref, src_ref,
                           ref_ref, out_ref):
    y = scores_ref[...].astype(jnp.float32)          # (BLOCK, K)
    beta = betas_ref[...].astype(jnp.float32)        # (K,)
    w = weights_ref[...].astype(jnp.float32)         # (K,)
    qs = src_ref[...].astype(jnp.float32)            # (N,)
    qr = ref_ref[...].astype(jnp.float32)

    # --- T^C: posterior correction (Eq. 3), elementwise on the VPU
    corrected = beta[None, :] * y / (1.0 - (1.0 - beta[None, :]) * y)

    # --- A: weighted average (self-normalizing), a K-term row sum
    w_norm = w / jnp.sum(w)
    agg = jnp.sum(corrected * w_norm[None, :], axis=-1)   # (BLOCK,)

    # --- T^Q: branchless piecewise-linear quantile map (Eq. 4)
    out_ref[...] = quantile_lookup(agg, qs[None, :], qr[None, :]).astype(
        out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def score_pipeline(expert_scores: Array, betas: Array, weights: Array,
                   src_quantiles: Array, ref_quantiles: Array,
                   *, block: int = DEFAULT_BLOCK, interpret: bool | None = None
                   ) -> Array:
    """expert_scores: (..., K) -> (...) business-ready scores."""
    *batch_shape, k = expert_scores.shape
    flat = expert_scores.reshape(-1, k)
    n = flat.shape[0]
    block = min(block, max(n, 1))
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    total = flat.shape[0]
    nq = src_quantiles.shape[-1]

    out = pl.pallas_call(
        _score_pipeline_kernel,
        grid=(total // block,),
        in_specs=[
            pl.BlockSpec((block, k), lambda i: (i, 0)),
            pl.BlockSpec((k,), lambda i: (0,)),
            pl.BlockSpec((k,), lambda i: (0,)),
            pl.BlockSpec((nq,), lambda i: (0,)),
            pl.BlockSpec((nq,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((total,), expert_scores.dtype),
        interpret=resolve_interpret(interpret),
    )(flat, betas, weights, src_quantiles, ref_quantiles)
    return out[:n].reshape(batch_shape)


def _score_pipeline_banked_kernel(btenant_ref, uniform_ref, scores_ref,
                                  idx_ref, betas_ref, weights_ref,
                                  src_ref, ref_ref, out_ref):
    b = pl.program_id(0)
    y = scores_ref[...].astype(jnp.float32)          # (BLOCK, K)

    def finish(beta, w, qs, qr):
        """Eq. 2 tail on gathered parameters; row axes broadcast, so the
        uniform path passes (1, ·) rows and the mixed path (BLOCK, ·) —
        the per-row fp op sequence is IDENTICAL either way (the sharded
        serving path relies on this for bitwise dense/sharded parity)."""
        # --- T^C: per-row posterior correction (Eq. 3)
        corrected = beta * y / (1.0 - (1.0 - beta) * y)
        # --- A: per-row self-normalizing weighted average
        w_norm = w / jnp.sum(w, axis=-1, keepdims=True)
        agg = jnp.sum(corrected * w_norm, axis=-1)              # (BLOCK,)
        # --- T^Q: branchless quantile map against per-row tables (Eq. 4)
        out_ref[...] = quantile_lookup(agg, qs, qr).astype(out_ref.dtype)

    @pl.when(uniform_ref[b] == 1)
    def _uniform_block():
        # fast path: every row of this block selects the same bank row —
        # ONE scalar-indexed (1, ·) slice per table replaces four dense
        # (BLOCK, T) one-hot gather matmuls.  The row index comes from the
        # prefetched SMEM scalars, available before the block body runs.
        t0 = btenant_ref[b]
        row = (pl.ds(t0, 1), slice(None))
        finish(betas_ref[row].astype(jnp.float32),
               weights_ref[row].astype(jnp.float32),
               src_ref[row].astype(jnp.float32),
               ref_ref[row].astype(jnp.float32))

    @pl.when(uniform_ref[b] == 0)
    def _mixed_block():
        # general path: gather each row's (tenant, predictor) parameters
        # with a one-hot (BLOCK, T) matmul per (T, ·) bank — dense and
        # MXU-friendly, no data-dependent addressing.  HIGHEST precision
        # keeps the gather exact on the MXU (a default-precision f32 matmul
        # would round the gathered parameters to bf16).
        tid = idx_ref[...].astype(jnp.int32)         # (BLOCK,)
        t = betas_ref.shape[0]
        iota_t = jax.lax.broadcasted_iota(jnp.int32, (y.shape[0], t), 1)
        sel = (iota_t == tid[:, None]).astype(jnp.float32)      # (BLOCK, T)

        def gather(ref):
            return jnp.dot(sel, ref[...].astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)

        finish(gather(betas_ref), gather(weights_ref),          # (BLOCK, K)
               gather(src_ref), gather(ref_ref))                # (BLOCK, N)


def _block_summary(idx_flat: Array, block: int) -> tuple[Array, Array]:
    """Distil a padded (G·block,) tenant vector into per-block scalars:
    (block_tenant, block_uniform) — the scalar-prefetch operands."""
    blocks = idx_flat.reshape(-1, block)
    btenant = blocks[:, 0].astype(jnp.int32)
    uniform = jnp.all(blocks == btenant[:, None], axis=1).astype(jnp.int32)
    return btenant, uniform


def banked_skip_stats(tenant_idx, *, block: int = DEFAULT_BLOCK) -> dict:
    """Host-side skip-rate report for a given tenant layout.

    Mirrors the wrapper's blocking exactly (power-of-two block, edge-padded
    tail) and returns how many grid blocks take the uniform fast path —
    the fraction of blocks that skip the one-hot gather matmuls.
    """
    idx = np.asarray(tenant_idx).reshape(-1)
    n = idx.shape[0]
    blk = _round_block(max(n, 1), block)
    pad = (-n) % blk
    if pad and n:
        idx = np.concatenate([idx, np.full(pad, idx[-1], idx.dtype)])
    blocks = idx.reshape(-1, blk)
    uniform = int((blocks == blocks[:, :1]).all(axis=1).sum())
    total = blocks.shape[0]
    return {"block": blk, "blocks": total, "uniform_blocks": uniform,
            "skip_rate": uniform / total if total else 0.0}


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def score_pipeline_banked(expert_scores: Array, tenant_idx: Array,
                          betas: Array, weights: Array,
                          src_quantiles: Array, ref_quantiles: Array,
                          *, block: int = DEFAULT_BLOCK,
                          interpret: bool | None = None) -> Array:
    """Mixed-tenant Eq. 2 in ONE ``pallas_call``.

    ``expert_scores``: (..., K) raw scores; ``tenant_idx``: (...) int32 row
    index into the (T, K) / (T, N) parameter banks.  Every grid step keeps
    the full banks resident in VMEM (T·(2K+2N)·4 bytes — ~130 KB for a
    64-tenant bank with N=256; constant index maps mean they are fetched
    once, not per block) and gathers per-row parameters in-kernel, so a
    mixed-tenant micro-batch costs one dispatch instead of T.

    ``tenant_idx`` is distilled into per-block (block_tenant, block_uniform)
    scalars carried through ``PrefetchScalarGridSpec``: blocks whose rows
    all share one tenant skip the one-hot gather matmuls (see module
    docstring).  The padding tail repeats the last real tenant id so a
    uniform final block stays on the fast path (padded rows are sliced off).
    """
    *batch_shape, k = expert_scores.shape
    flat = expert_scores.reshape(-1, k)
    idx_flat = jnp.asarray(tenant_idx, jnp.int32).reshape(-1)
    if idx_flat.shape[0] != flat.shape[0]:
        raise ValueError(
            f"tenant_idx has {idx_flat.shape[0]} rows for "
            f"{flat.shape[0]} score rows")
    n = flat.shape[0]
    block = _round_block(max(n, 1), block)
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
        # edge mode: padded rows reuse the last real row's params (sliced
        # off below), keeping an otherwise-uniform tail block uniform
        idx_flat = jnp.pad(idx_flat, (0, pad), mode="edge")
    total = flat.shape[0]
    t, nq = src_quantiles.shape
    btenant, uniform = _block_summary(idx_flat, block)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(total // block,),
        in_specs=[
            pl.BlockSpec((block, k), lambda i, bt, uf: (i, 0)),
            pl.BlockSpec((block,), lambda i, bt, uf: (i,)),
            pl.BlockSpec((t, k), lambda i, bt, uf: (0, 0)),
            pl.BlockSpec((t, k), lambda i, bt, uf: (0, 0)),
            pl.BlockSpec((t, nq), lambda i, bt, uf: (0, 0)),
            pl.BlockSpec((t, nq), lambda i, bt, uf: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i, bt, uf: (i,)),
    )
    out = pl.pallas_call(
        _score_pipeline_banked_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((total,), expert_scores.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=BANKED_VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
    )(btenant, uniform, flat, idx_flat, betas, weights,
      src_quantiles, ref_quantiles)
    return out[:n].reshape(batch_shape)
