"""Pure-jnp oracles for every Pallas kernel (the allclose reference)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# quantile map (paper Eq. 4) — oracle = core.transforms.quantile_map
# ---------------------------------------------------------------------------

def quantile_map(scores: Array, src_q: Array, ref_q: Array) -> Array:
    from repro.core.transforms import quantile_map as _qm
    return _qm(scores, src_q, ref_q)


# ---------------------------------------------------------------------------
# fused score pipeline (paper Eq. 2) — oracle = core.transforms.score_pipeline
# ---------------------------------------------------------------------------

def score_pipeline(expert_scores: Array, betas: Array, weights: Array,
                   src_q: Array, ref_q: Array) -> Array:
    from repro.core.transforms import score_pipeline as _sp
    return _sp(expert_scores, betas, weights, src_q, ref_q)


# ---------------------------------------------------------------------------
# banked (tenant-indexed) score pipeline — oracle =
# core.transforms.banked_score_pipeline
# ---------------------------------------------------------------------------

def score_pipeline_banked(expert_scores: Array, tenant_idx: Array,
                          betas: Array, weights: Array,
                          src_q: Array, ref_q: Array) -> Array:
    from repro.core.transforms import banked_score_pipeline as _bsp
    return _bsp(expert_scores, tenant_idx, betas, weights, src_q, ref_q)


# ---------------------------------------------------------------------------
# flash attention (GQA, causal / sliding window)
# ---------------------------------------------------------------------------

def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    sliding_window: int = 0) -> Array:
    """Naive exact attention. q: (B,Tq,Hq,D); k,v: (B,Tk,Hkv,D)."""
    b, tq, hq, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    qpk = hq // hkv
    qh = q.reshape(b, tq, hkv, qpk, d).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qh, k.astype(jnp.float32)) * scale
    qpos = jnp.arange(tq)[:, None]
    kpos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= qpos >= kpos
    if sliding_window > 0:
        mask &= kpos > qpos - sliding_window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, tq, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention (single query position over a KV cache)
# ---------------------------------------------------------------------------

def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     valid_len: Array | int) -> Array:
    """q: (B,Hq,D); caches: (B,S,Hkv,D); attends to positions < valid_len."""
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    qpk = hq // hkv
    qh = q.reshape(b, hkv, qpk, d).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("bhgd,bshd->bhgs", qh, k_cache.astype(jnp.float32)) * scale
    mask = jnp.arange(s)[None, :] < jnp.asarray(valid_len)[..., None]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# selective scan (Mamba-1) — a sequential lax.scan over time
# ---------------------------------------------------------------------------

def selective_scan(dt: Array, x: Array, b: Array, c: Array, a: Array,
                   d: Array, h0: Array) -> tuple[Array, Array]:
    """h_t = exp(dt_t a) h_{t-1} + (dt_t x_t) b_t^T, y_t = h_t c_t + d x_t,
    in float32.  dt, x: (B, T, C); b, c: (B, T, N); a: (C, N); d: (C,);
    h0: (B, C, N).  Returns (y (B, T, C), final state (B, C, N))."""
    f32 = jnp.float32
    a, d = a.astype(f32), d.astype(f32)

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp                        # (B, C), (B, N)
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, c_t,
                             precision=jax.lax.Precision.HIGHEST) + d * x_t

    seq = [jnp.moveaxis(v.astype(f32), 1, 0) for v in (dt, x, b, c)]
    h, ys = jax.lax.scan(step, h0.astype(f32), seq)
    return jnp.moveaxis(ys, 0, 1), h
