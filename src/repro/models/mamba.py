"""Mamba (S6) selective state-space mixer.

The full-sequence forward runs the recurrence in the Pallas selective-scan
kernel (``kernels/selective_scan.py``), which keeps each (d_inner, N) state
slice in VMEM and reads only dt, x, B and C: no (B, T, d_inner, N) tensor
reaches HBM.  Semantics are exactly Mamba-1 (diagonal A, per-channel dt);
with ``MambaConfig.inner_norms`` dt, B and C pass an RMSNorm first, as in
Jamba's mixer (arXiv:2403.19887 §3).

Decode is the O(1) recurrent step with a (B, d_conv-1, d_inner) conv tail and
a (B, d_inner, N) SSM state — constant memory at 500k+ context.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.selective_scan import selective_scan
from repro.models import layers
from repro.models.config import MambaConfig

Array = jax.Array
PyTree = Any


class MambaState(NamedTuple):
    conv: Array  # (B, d_conv-1, d_inner) — trailing inputs for the causal conv
    ssm: Array   # (B, d_inner, N) — recurrent SSM state (f32)


def dt_rank_of(d_model: int, mc: MambaConfig) -> int:
    return mc.dt_rank or -(-d_model // 16)


def init_mamba(key, d_model: int, mc: MambaConfig, dtype=jnp.float32) -> PyTree:
    d_in = mc.expand * d_model
    rank = dt_rank_of(d_model, mc)
    keys = jax.random.split(key, 6)
    # S4D-real initialization for A; dt bias targets softplus^{-1}(dt)
    a = jnp.tile(jnp.arange(1, mc.d_state + 1, dtype=jnp.float32)[None, :],
                 (d_in, 1))
    dt_init = jnp.exp(
        jax.random.uniform(keys[4], (d_in,)) * (math.log(0.1) - math.log(0.001))
        + math.log(0.001)
    )
    dt_bias = jnp.log(jnp.expm1(dt_init))  # inverse softplus
    norms = {}
    if mc.inner_norms:
        norms = {"dt_norm": layers.init_rmsnorm(rank, dtype),
                 "b_norm": layers.init_rmsnorm(mc.d_state, dtype),
                 "c_norm": layers.init_rmsnorm(mc.d_state, dtype)}
    return {
        "in_proj": layers.init_linear(keys[0], d_model, 2 * d_in, dtype=dtype),
        "conv_w": jax.random.normal(keys[1], (mc.d_conv, d_in), dtype) * 0.1,
        "conv_b": jnp.zeros((d_in,), dtype),
        "x_proj": layers.init_linear(keys[2], d_in, rank + 2 * mc.d_state,
                                     dtype=dtype),
        "dt_proj": layers.init_linear(keys[3], rank, d_in, dtype=dtype),
        "dt_bias": dt_bias.astype(dtype),
        "A_log": jnp.log(a).astype(dtype),
        "D": jnp.ones((d_in,), dtype),
        "out_proj": layers.init_linear(keys[5], d_in, d_model, dtype=dtype),
        **norms,
    }


def _causal_conv(x: Array, w: Array, b: Array, tail: Array | None = None) -> Array:
    """Depthwise causal conv over time. x: (B, T, C); w: (K, C).

    ``tail``: (B, K-1, C) previous inputs (decode / chunk continuation); zeros
    if None.  Implemented as K shifted adds — K is 4, this beats conv calls on
    both TPU and in compile time.
    """
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)  # (B, T+K-1, C)
    t = x.shape[1]
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + xp[:, i : i + t] * w[i].astype(x.dtype)
    return out + b.astype(x.dtype)


def _ssm_inputs(params: PyTree, x_conv: Array, mc: MambaConfig, rank: int,
                eps: float) -> tuple[Array, Array, Array]:
    """dt (float32, after softplus), B and C from the conv output."""
    n = mc.d_state
    proj = layers.linear(params["x_proj"], x_conv)
    dt_raw, b_mat, c_mat = jnp.split(proj, [rank, rank + n], axis=-1)
    if mc.inner_norms:
        dt_raw = layers.rmsnorm(params["dt_norm"], dt_raw, eps)
        b_mat = layers.rmsnorm(params["b_norm"], b_mat, eps)
        c_mat = layers.rmsnorm(params["c_norm"], c_mat, eps)
    dt = jax.nn.softplus(
        layers.linear(params["dt_proj"], dt_raw)
        + params["dt_bias"].astype(x_conv.dtype)
    ).astype(jnp.float32)
    return dt, b_mat, c_mat


def _gated(y: Array, z: Array) -> Array:
    """The mixer's output gate, y * silu(z), in float32."""
    return y * jax.nn.silu(z.astype(jnp.float32))


def mamba_forward(
    params: PyTree,
    x: Array,
    mc: MambaConfig,
    *,
    eps: float,
    initial_state: MambaState | None = None,
    return_state: bool = False,
) -> tuple[Array, MambaState | None]:
    """Full-sequence mixer. x: (B, T, d_model) -> (B, T, d_model); ``eps``
    is the inner norms' (``mc.inner_norms``)."""
    b, _, d_model = x.shape
    d_in = mc.expand * d_model
    rank = dt_rank_of(d_model, mc)

    xz = layers.linear(params["in_proj"], x)
    x_in, z = jnp.split(xz, 2, axis=-1)

    conv_tail = initial_state.conv if initial_state is not None else None
    x_conv = jax.nn.silu(
        _causal_conv(x_in, params["conv_w"], params["conv_b"], conv_tail)
    )
    dt, b_mat, c_mat = _ssm_inputs(params, x_conv, mc, rank, eps)
    a = -jnp.exp(params["A_log"].astype(jnp.float32))       # (d_in, N)
    h0 = (
        initial_state.ssm
        if initial_state is not None
        else jnp.zeros((b, d_in, mc.d_state), jnp.float32)
    )
    y, h_final = selective_scan(dt, x_conv, b_mat, c_mat, a, params["D"], h0)
    y = _gated(y, z).astype(x.dtype)
    out = layers.linear(params["out_proj"], y)

    state = None
    if return_state:
        k = params["conv_w"].shape[0]
        tail_src = x_in if initial_state is None else jnp.concatenate(
            [initial_state.conv, x_in], axis=1
        )
        conv_tail = tail_src[:, -(k - 1):]
        if conv_tail.shape[1] < k - 1:
            conv_tail = jnp.pad(
                conv_tail, ((0, 0), (k - 1 - conv_tail.shape[1], 0), (0, 0))
            )
        state = MambaState(conv=conv_tail, ssm=h_final)
    return out, state


def mamba_decode_step(
    params: PyTree, x: Array, mc: MambaConfig, state: MambaState, *,
    eps: float
) -> tuple[Array, MambaState]:
    """One-token step. x: (B, 1, d_model)."""
    b, _, d_model = x.shape
    d_in = mc.expand * d_model
    rank = dt_rank_of(d_model, mc)

    xz = layers.linear(params["in_proj"], x)
    x_in, z = jnp.split(xz, 2, axis=-1)       # (B, 1, d_in)

    x_conv = jax.nn.silu(
        _causal_conv(x_in, params["conv_w"], params["conv_b"], state.conv)
    )
    new_conv = jnp.concatenate([state.conv, x_in], axis=1)[:, 1:]

    dt, b_mat, c_mat = _ssm_inputs(params, x_conv, mc, rank, eps)
    dt = dt[:, 0]                              # (B, d_in)
    a = -jnp.exp(params["A_log"].astype(jnp.float32))

    abar = jnp.exp(dt[..., None] * a)          # (B, d_in, N)
    bx = (dt * x_conv.astype(jnp.float32)[:, 0])[..., None] * b_mat.astype(
        jnp.float32
    )[:, 0, None, :]
    h = abar * state.ssm + bx
    y = jnp.einsum("bcn,bn->bc", h, c_mat.astype(jnp.float32)[:, 0])
    y = y + x_conv.astype(jnp.float32)[:, 0] * params["D"].astype(jnp.float32)
    y = _gated(y[:, None], z).astype(x.dtype)
    out = layers.linear(params["out_proj"], y)
    return out, MambaState(conv=new_conv, ssm=h)


def init_mamba_state(batch: int, d_model: int, mc: MambaConfig,
                     dtype=jnp.bfloat16) -> MambaState:
    d_in = mc.expand * d_model
    return MambaState(
        conv=jnp.zeros((batch, mc.d_conv - 1, d_in), dtype),
        ssm=jnp.zeros((batch, d_in, mc.d_state), jnp.float32),
    )
