"""Pallas decode attention: one query position against a long KV cache.

The decode hot loop is memory-bound (stream the cache once); the kernel
blocks over the sequence axis of the cache with online-softmax accumulation
in VMEM scratch (flash-decoding shape), GQA-aware: the (qpk, D) query-head
group for one KV head rides along each cache tile so the MXU sees a
(qpk, D) x (D, BS) matmul per tile instead of qpk separate dot products.

``valid_len`` masks unwritten cache slots (per batch row) — ring-buffer
sliding-window caches pass their window capacity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

Array = jax.Array


def _decode_kernel(vlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_s: int):
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)               # (qpk, D)
    k = k_ref[0, 0].astype(jnp.float32)               # (BS, D)
    v = v_ref[0, 0].astype(jnp.float32)               # (BS, D)
    valid = vlen_ref[0]                               # scalar int32

    s = (q @ k.T) * scale                             # (qpk, BS)
    kpos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < valid, s, -jnp.inf)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[:, None])
    alpha = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1)
    acc_scr[...] = alpha[:, None] * acc_scr[...] + p @ v
    m_scr[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     valid_len: Array, *, block_s: int = 512,
                     interpret: bool | None = None) -> Array:
    """q: (B, Hq, D); caches: (B, S, Hkv, D); valid_len: (B,) -> (B, Hq, D)."""
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    qpk = hq // hkv
    scale = 1.0 / (d ** 0.5)

    block_s = min(block_s, s)
    pad_s = (-s) % block_s
    qg = q.reshape(b, hkv, qpk, d)
    kt = jnp.moveaxis(k_cache, 1, 2)                  # (B, Hkv, S, D)
    vt = jnp.moveaxis(v_cache, 1, 2)
    if pad_s:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
    s_p = s + pad_s
    vlen = jnp.minimum(jnp.asarray(valid_len, jnp.int32).reshape(b), s)

    kernel = functools.partial(_decode_kernel, scale=scale, block_s=block_s)
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, s_p // block_s),
        in_specs=[
            pl.BlockSpec((1,), lambda b_, h, si: (b_,)),
            pl.BlockSpec((1, 1, qpk, d), lambda b_, h, si: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, d), lambda b_, h, si: (b_, h, si, 0)),
            pl.BlockSpec((1, 1, block_s, d), lambda b_, h, si: (b_, h, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qpk, d), lambda b_, h, si: (b_, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, qpk, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qpk,), jnp.float32),
            pltpu.VMEM((qpk,), jnp.float32),
            pltpu.VMEM((qpk, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(vlen, qg, kt, vt)
    return out.reshape(b, hq, d)
