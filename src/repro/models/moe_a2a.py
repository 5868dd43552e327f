"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

The TPU-native alternative to the einsum/one-hot GShard dispatch in
``moe.py``: that formulation makes GSPMD all-reduce full (E, cap, d) expert
buffers across the token-sharded axes on *every* dispatch chunk — measured
at ~50 s/step of ICI time for llama4-maverick prefill (§Perf).  Here the
communication is what expert parallelism actually requires:

  * tokens are flat-sharded over (data x model); each device locally routes
    its n_local tokens into per-expert capacity slots (the one-hot is only
    (n_local, E, cap_local) — VMEM-scale, NO chunk scan needed);
  * one ``all_to_all`` over the "model" axis sends each expert's slots to
    the rank that owns it (bytes moved = tokens·d, the information-theoretic
    floor for EP dispatch);
  * expert FFN runs with FSDP'd weights: gate/up are column-parallel over
    the data axes (local ff shard, zero comms), down is row-parallel (one
    psum over the data axes);
  * the reverse ``all_to_all`` returns expert outputs; the combine is local.

Weight layout contract (enforced by launch/shardings.py when impl="a2a"):
  gate/up: (E@model, d, ff@data)      down: (E@model, ff@data, d)
  router:  replicated.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers
from repro.models.config import MoEConfig

Array = jax.Array
PyTree = Any


def _local_dispatch(xf, gate_vals, expert_idx, e, cap):
    """One-hot dispatch of LOCAL tokens. xf: (n, d) -> (e, cap, d) + combine."""
    n = xf.shape[0]
    k = expert_idx.shape[-1]
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)        # (n, k, e)
    flat_choice = onehot.reshape(n * k, e)
    pos = jnp.cumsum(flat_choice, axis=0) * flat_choice - 1
    pos = pos.reshape(n, k, e)
    within = (pos < cap) & (pos >= 0)
    slot = jax.nn.one_hot(jnp.where(within, pos, -1), cap, dtype=xf.dtype)
    dispatch = jnp.sum(slot, axis=1)                               # (n, e, cap)
    combine = jnp.sum(slot * gate_vals[..., None, None].astype(xf.dtype),
                      axis=1)                                      # (n, e, cap)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, xf)
    return expert_in, combine


def moe_forward_a2a(
    params: PyTree,
    x: Array,
    mo: MoEConfig,
    *,
    model_axis: str = "model",
) -> tuple[Array, Array]:
    """x: (B, T, d) -> (out, aux). Must run under ``jax.set_mesh(mesh)``."""
    b, t, d = x.shape
    e, k = mo.num_experts, mo.top_k
    mesh = jax.sharding.get_abstract_mesh()
    if model_axis not in mesh.shape:
        raise RuntimeError(
            "moe impl='a2a' needs the production mesh via jax.set_mesh(...)")
    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    m = mesh.shape[model_axis]
    e_local = e // m

    in_specs = (
        P(None, None),                      # router (d, E) replicated
        P(model_axis, None, data_axes),     # gate  (E, d, ff)
        P(model_axis, None, data_axes),     # up    (E, d, ff)
        P(model_axis, data_axes, None),     # down  (E, ff, d)
        P(data_axes, model_axis, None),     # x     (B, T, d)
    )
    out_specs = (P(data_axes, model_axis, None), P())

    @jax.shard_map(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    def inner(router_w, gate_w, up_w, down_w, xl):
        bl, tl, _ = xl.shape
        n_local = bl * tl
        xf = xl.reshape(n_local, d)

        logits = (xf @ router_w.astype(xf.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

        cap = max(int(n_local * k / e * mo.capacity_factor), k)
        expert_in, combine = _local_dispatch(xf, gate_vals, expert_idx, e, cap)

        # ---- dispatch all-to-all over the model axis --------------------
        # (e, cap, d) -> (m_dest, e_local, cap, d); after the exchange dim 0
        # indexes the SOURCE rank, so transpose it under the expert dim to
        # lay tokens out as (e_local, m*cap, d).
        send = expert_in.reshape(m, e_local, cap, d)
        recv = jax.lax.all_to_all(send, model_axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        buf = recv.reshape(m, e_local, cap, d).transpose(1, 0, 2, 3) \
            .reshape(e_local, m * cap, d)                          # (E_l, C, d)

        # ---- expert FFN: EP (model) x TP (data) hybrid --------------------
        # Each data row holds DIFFERENT tokens but only an ff-slice of the
        # expert weights, so: all-gather the token buffers over the data
        # axes (every rank sees every row's tokens), run gate/up/down with
        # the local ff shard, then psum_scatter the down partial sums back —
        # the reduce half combines ff-slices, the scatter half returns each
        # row its own tokens.
        buf_all = buf
        for ax in reversed(data_axes):
            buf_all = jax.lax.all_gather(buf_all, ax, axis=1, tiled=True)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf_all,
                                   gate_w.astype(buf.dtype)))
        u = jnp.einsum("ecd,edf->ecf", buf_all, up_w.astype(buf.dtype))
        out_all = jnp.einsum("ecf,efd->ecd", g * u,
                             down_w.astype(buf.dtype))
        out_buf = out_all
        for ax in data_axes:
            out_buf = jax.lax.psum_scatter(out_buf, ax, scatter_dimension=1,
                                           tiled=True)

        # ---- return all-to-all + local combine ---------------------------
        # (e_local, m*cap, d) -> (m_dest, e_local, cap, d); after the
        # exchange dim 0 = source rank = owner of experts r*e_local+j, which
        # is exactly the original expert-major order.
        back = out_buf.reshape(e_local, m, cap, d).transpose(1, 0, 2, 3)
        ret = jax.lax.all_to_all(back, model_axis, split_axis=0,
                                 concat_axis=0, tiled=True)
        expert_out = ret.reshape(e, cap, d)
        y = jnp.einsum("nec,ecd->nd", combine, expert_out)

        # ---- load-balance aux (global means via psum) ---------------------
        top1 = jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32)
        f_sum = jnp.sum(top1, axis=0)
        p_sum = jnp.sum(probs, axis=0)
        count = jnp.asarray(n_local, jnp.float32)
        for ax in data_axes + (model_axis,):
            f_sum = jax.lax.psum(f_sum, ax)
            p_sum = jax.lax.psum(p_sum, ax)
            count = jax.lax.psum(count, ax)
        aux = e * jnp.sum((f_sum / count) * (p_sum / count))
        return y.reshape(bl, tl, d), aux

    out, aux = inner(params["router"]["w"], params["gate"], params["up"],
                     params["down"], x)
    if mo.shared_expert:
        out = out + layers.mlp(params["shared"], x)
    return out, aux
