"""Pallas flash attention (GQA + causal + sliding window) for TPU.

Blocked online-softmax attention: grid (B, Hq, Tq/BQ, Tk/BK) with the K axis
innermost (sequential on TPU), carrying running max / denominator / output in
VMEM scratch.  Tiles are MXU-aligned (block sizes multiples of 128 at real
sizes); GQA maps query head h to KV head h // (Hq/Hkv) in the K/V BlockSpec
index maps, so KV tiles are fetched once per query-head group.

Numerics: masked logits are -inf; the running max is guarded so fully-masked
tiles (above the causal diagonal / outside the sliding window) contribute
exactly zero without NaNs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

Array = jax.Array


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, sliding_window: int,
                  block_q: int, block_k: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)               # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)               # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)               # (BK, D)

    s = (q @ k.T) * scale                             # (BQ, BK)
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_k
    if causal:
        mask &= qpos >= kpos
    if sliding_window > 0:
        mask &= kpos > qpos - sliding_window
    s = jnp.where(mask, s, -jnp.inf)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[:, None])                  # masked -> exp(-inf)=0
    alpha = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    l_new = alpha * l_prev + jnp.sum(p, axis=-1)
    acc_scr[...] = alpha[:, None] * acc_scr[...] + p @ v
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)[:, None]
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sliding_window", "block_q", "block_k",
                     "interpret"),
)
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    sliding_window: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None) -> Array:
    """q: (B, Tq, Hq, D); k, v: (B, Tk, Hkv, D) -> (B, Tq, Hq, D)."""
    b, tq, hq, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    qpk = hq // hkv
    scale = 1.0 / (d ** 0.5)

    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    pad_q = (-tq) % block_q
    pad_k = (-tk) % block_k

    qt = jnp.moveaxis(q, 1, 2)                        # (B, Hq, Tq, D)
    kt = jnp.moveaxis(k, 1, 2)                        # (B, Hkv, Tk, D)
    vt = jnp.moveaxis(v, 1, 2)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    tq_p, tk_p = tq + pad_q, tk + pad_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        sliding_window=sliding_window, block_q=block_q, block_k=block_k,
        seq_k=tk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, tq_p // block_q, tk_p // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, qi, ki: (b_, h // qpk, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, qi, ki: (b_, h // qpk, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, tq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    return jnp.moveaxis(out[:, :, :tq], 2, 1)         # (B, Tq, Hq, D)
