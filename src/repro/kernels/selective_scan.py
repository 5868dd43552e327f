"""Pallas selective scan (Mamba-1 / S6) for TPU: the state stays in VMEM.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) B_t^T      (d_inner, N)
    y_t = h_t C_t + D * x_t                                  (d_inner,)

Channels are laid out as tiles of (ROWS, 128), so one time step of dt, x
or y for a block of ROWS * 128 channels is one vreg, and the state is N
such tiles, the state index a major axis.  Grid (batch, channel blocks,
time blocks), time innermost (sequential on TPU).  A grid step walks its
time block UNROLL steps per loop iteration, carrying the state in vregs
and across time blocks in a VMEM scratch.  B and C arrive in SMEM, so a
step's B_t[n] and C_t[n] are scalar operands: the update and the C
contraction are elementwise, with no cross-lane or cross-sublane
reduction.  HBM sees dt, x, B, C in and y out, once each; nothing of size
(T, d_inner, N) exists anywhere.

Padding: time and channels are padded with dt = 0 and x = 0, which leave
the state unchanged (exp(0) = 1, no input) and give y = 0, so the final
state is the state after the last real step.  ``selective_scan`` carries
a custom VJP whose backward recomputes through the plain ``lax.scan`` of
``kernels/ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref, resolve_interpret

Array = jax.Array

LANES = 128
ROWS = 8                # channel tiles of a block: 1,024 channels
UNROLL = 8              # time steps per loop iteration
MAX_BLOCK_T = 128


def _scan_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                 y_ref, hT_ref, h_scr, *, block_t: int, n: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    a = a_ref[...]                          # (N, R, 128)
    d = d_ref[...]                          # (R, 128)

    def step(t, h):
        dt = dt_ref[0, t]                   # (R, 128)
        x = x_ref[0, t]
        u = dt * x
        y = d * x
        out = []
        for k in range(n):
            hk = jnp.exp(dt * a[k]) * h[k] + b_ref[0, t, k] * u
            y = y + c_ref[0, t, k] * hk
            out.append(hk)
        y_ref[0, t] = y
        return out

    def steps(j, h):
        hs = [h[k] for k in range(n)]
        for i in range(UNROLL):
            hs = step(j * UNROLL + i, hs)
        return jnp.stack(hs)

    h = jax.lax.fori_loop(0, block_t // UNROLL, steps, h_scr[...])
    h_scr[...] = h

    @pl.when(ti == pl.num_programs(2) - 1)
    def _final():
        hT_ref[0] = h


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(dt, x, b, c, a, d, h0, *, interpret: bool | None):
    bsz, t, d_inner = x.shape
    n = a.shape[1]
    f32 = jnp.float32
    groups = -(-d_inner // LANES)
    rows = groups if groups <= ROWS else ROWS
    groups = _round_up(groups, rows)
    block_t = min(MAX_BLOCK_T, _round_up(t, UNROLL))
    t_pad = _round_up(t, block_t)
    c_pad = groups * LANES - d_inner

    def tiles(v):                           # (B, T, C) -> (B, T', G, 128)
        v = jnp.pad(v.astype(f32), ((0, 0), (0, t_pad - t), (0, c_pad)))
        return v.reshape(bsz, t_pad, groups, LANES)

    def rows_of(v):                         # (B, T, N) -> (B, T', N)
        return jnp.pad(v.astype(f32), ((0, 0), (0, t_pad - t), (0, 0)))

    a_t = jnp.pad(a.astype(f32).T, ((0, 0), (0, c_pad)))
    d_t = jnp.pad(d.astype(f32), (0, c_pad))
    h0_t = jnp.pad(jnp.swapaxes(h0.astype(f32), 1, 2),
                   ((0, 0), (0, 0), (0, c_pad)))

    seq = pl.BlockSpec((1, block_t, rows, LANES), lambda i, j, k: (i, k, j, 0))
    scalars = pl.BlockSpec((1, block_t, n), lambda i, j, k: (i, k, 0),
                           memory_space=pltpu.SMEM)
    state = pl.BlockSpec((1, n, rows, LANES), lambda i, j, k: (i, 0, j, 0))
    y, h_t = pl.pallas_call(
        functools.partial(_scan_kernel, block_t=block_t, n=n),
        grid=(bsz, groups // rows, t_pad // block_t),
        in_specs=[seq, seq, scalars, scalars,
                  pl.BlockSpec((n, rows, LANES), lambda i, j, k: (0, j, 0)),
                  pl.BlockSpec((rows, LANES), lambda i, j, k: (j, 0)),
                  state],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct((bsz, t_pad, groups, LANES), f32),
                   jax.ShapeDtypeStruct((bsz, n, groups, LANES), f32)],
        scratch_shapes=[pltpu.VMEM((n, rows, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="selective_scan",
    )(tiles(dt), tiles(x), rows_of(b), rows_of(c),
      a_t.reshape(n, groups, LANES), d_t.reshape(groups, LANES),
      h0_t.reshape(bsz, n, groups, LANES))
    y = y.reshape(bsz, t_pad, groups * LANES)[:, :t, :d_inner]
    h_t = h_t.reshape(bsz, n, groups * LANES)[:, :, :d_inner]
    return y, jnp.swapaxes(h_t, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def selective_scan(dt: Array, x: Array, b: Array, c: Array, a: Array,
                   d: Array, h0: Array, interpret: bool | None = None
                   ) -> tuple[Array, Array]:
    """The Mamba-1 recurrence over time, in float32.

    dt, x: (B, T, d_inner); b, c: (B, T, N); a: (d_inner, N) (negative);
    d: (d_inner,); h0: (B, d_inner, N).  Returns y (B, T, d_inner) and the
    final state (B, d_inner, N)."""
    return _scan_call(dt, x, b, c, a, d, h0, interpret=interpret)


def _fwd(dt, x, b, c, a, d, h0, interpret):
    out = _scan_call(dt, x, b, c, a, d, h0, interpret=interpret)
    return out, (dt, x, b, c, a, d, h0)


def _bwd(interpret, residuals, grads):
    _, vjp = jax.vjp(ref.selective_scan, *residuals)
    return vjp(grads)


selective_scan.defvjp(_fwd, _bwd)
