"""Block composition + scanned layer stacks.

A *block* = sequence mixer (attn / mamba / mLSTM / sLSTM) + optional FFN
(dense SwiGLU or MoE), pre-norm residual.  The stack scans over *groups* —
one repetition of the config's ``layer_pattern`` — keeping the HLO for a
126-layer model the size of one pattern period.

Caches are pytrees aligned with the pattern: ``cache[i]`` is the state for
pattern position i, with every leaf carrying a leading ``n_groups`` axis so
the decode scan can thread it as scan xs/ys.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import layers, mamba as mamba_mod, moe as moe_mod
from repro.models import xlstm as xlstm_mod
from repro.models.attention import KVCache
from repro.models.config import BlockSpec, ModelConfig

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, spec: BlockSpec, dtype=jnp.float32) -> PyTree:
    km, kf = jax.random.split(key)
    params: dict[str, PyTree] = {"mixer_norm": layers.init_rmsnorm(cfg.d_model, dtype)}
    if spec.mixer == "attn":
        params["mixer"] = attn_mod.init_attention(km, cfg, dtype)
    elif spec.mixer == "mamba":
        params["mixer"] = mamba_mod.init_mamba(km, cfg.d_model, cfg.mamba, dtype)
    elif spec.mixer == "mlstm":
        params["mixer"] = xlstm_mod.init_mlstm(km, cfg.d_model, cfg.n_heads,
                                               cfg.xlstm, dtype)
    elif spec.mixer == "slstm":
        params["mixer"] = xlstm_mod.init_slstm(km, cfg.d_model, cfg.xlstm, dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn == "mlp":
        params["ffn_norm"] = layers.init_rmsnorm(cfg.d_model, dtype)
        params["ffn"] = layers.init_mlp(kf, cfg.d_model, cfg.d_ff, dtype)
    elif spec.ffn == "moe":
        params["ffn_norm"] = layers.init_rmsnorm(cfg.d_model, dtype)
        params["ffn"] = moe_mod.init_moe(kf, cfg.d_model, cfg.moe, dtype)
    return params


def init_group(key, cfg: ModelConfig, dtype=jnp.float32) -> list[PyTree]:
    keys = jax.random.split(key, len(cfg.layer_pattern))
    return [init_block(k, cfg, spec, dtype)
            for k, spec in zip(keys, cfg.layer_pattern)]


def init_stack(key, cfg: ModelConfig, dtype=jnp.float32) -> list[PyTree]:
    """Stacked params: each leaf has leading dim n_groups."""
    group_keys = jax.random.split(key, cfg.n_groups)
    groups = [init_group(k, cfg, dtype) for k in group_keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *groups)


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def init_cache(
    cfg: ModelConfig, batch: int, capacity: int, dtype=jnp.bfloat16
) -> list[PyTree]:
    """Fresh decode cache for one group, leaves stacked over n_groups."""
    def one(spec: BlockSpec) -> PyTree:
        if spec.mixer == "attn":
            cap = min(capacity, cfg.sliding_window) if cfg.sliding_window else capacity
            return KVCache(
                k=jnp.zeros((cfg.n_groups, batch, cap, cfg.n_kv_heads,
                             cfg.head_dim), dtype),
                v=jnp.zeros((cfg.n_groups, batch, cap, cfg.n_kv_heads,
                             cfg.head_dim), dtype),
            )
        if spec.mixer == "mamba":
            s = mamba_mod.init_mamba_state(batch, cfg.d_model, cfg.mamba, dtype)
            return jax.tree.map(
                lambda a: jnp.zeros((cfg.n_groups,) + a.shape, a.dtype), s
            )
        if spec.mixer == "mlstm":
            s = xlstm_mod.init_mlstm_state(batch, cfg.d_model, cfg.n_heads, cfg.xlstm)
            return jax.tree.map(
                lambda a: jnp.zeros((cfg.n_groups,) + a.shape, a.dtype), s
            )
        if spec.mixer == "slstm":
            s = xlstm_mod.init_slstm_state(batch, cfg.d_model)
            return jax.tree.map(
                lambda a: jnp.zeros((cfg.n_groups,) + a.shape, a.dtype), s
            )
        raise ValueError(spec.mixer)

    return [one(spec) for spec in cfg.layer_pattern]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _mixer_forward(
    bparams: PyTree,
    x: Array,
    cfg: ModelConfig,
    spec: BlockSpec,
    *,
    angles: Array | None,
    mode: str,                # "forward" | "prefill" | "decode"
    cache: PyTree | None,
    cache_pos: Array | int,
    attn_impl: str,
) -> tuple[Array, PyTree | None]:
    if spec.mixer == "attn":
        if mode == "decode":
            return attn_mod.attention_forward(
                bparams, x, cfg, angles=angles, cache=cache,
                cache_pos=cache_pos, attn_impl=attn_impl,
            )
        out, _ = attn_mod.attention_forward(
            bparams, x, cfg, angles=angles, cache=None, attn_impl=attn_impl
        )
        new_cache = None
        if mode == "prefill":
            new_cache = attn_mod.prefill_kv(
                bparams, x, cfg, angles=angles,
                capacity=cache.k.shape[1] if cache is not None else x.shape[1],
            )
        return out, new_cache
    if spec.mixer == "mamba":
        if mode == "decode":
            return mamba_mod.mamba_decode_step(bparams, x, cfg.mamba, cache,
                                               eps=cfg.norm_eps)
        return mamba_mod.mamba_forward(
            bparams, x, cfg.mamba, eps=cfg.norm_eps,
            return_state=(mode == "prefill")
        )
    if spec.mixer == "mlstm":
        if mode == "decode":
            return xlstm_mod.mlstm_decode_step(
                bparams, x, cfg.n_heads, cfg.xlstm, cache
            )
        return xlstm_mod.mlstm_forward(
            bparams, x, cfg.n_heads, cfg.xlstm,
            return_state=(mode == "prefill"),
        )
    if spec.mixer == "slstm":
        if mode == "decode":
            return xlstm_mod.slstm_decode_step(bparams, x, cfg.xlstm, cache)
        return xlstm_mod.slstm_forward(
            bparams, x, cfg.xlstm, return_state=(mode == "prefill")
        )
    raise ValueError(spec.mixer)


def block_forward(
    bparams: PyTree,
    x: Array,
    cfg: ModelConfig,
    spec: BlockSpec,
    *,
    angles: Array | None,
    mode: str,
    cache: PyTree | None,
    cache_pos: Array | int,
    attn_impl: str,
) -> tuple[Array, PyTree | None, Array]:
    """Pre-norm residual block. Returns (x, new_cache, moe_aux)."""
    h = layers.rmsnorm(bparams["mixer_norm"], x, cfg.norm_eps)
    out, new_cache = _mixer_forward(
        bparams["mixer"], h, cfg, spec, angles=angles, mode=mode,
        cache=cache, cache_pos=cache_pos, attn_impl=attn_impl,
    )
    x = x + out
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn == "mlp":
        h2 = layers.rmsnorm(bparams["ffn_norm"], x, cfg.norm_eps)
        x = x + layers.mlp(bparams["ffn"], h2)
    elif spec.ffn == "moe":
        h2 = layers.rmsnorm(bparams["ffn_norm"], x, cfg.norm_eps)
        if cfg.moe.impl == "a2a":
            from repro.models.moe_a2a import moe_forward_a2a
            out2, aux = moe_forward_a2a(bparams["ffn"], h2, cfg.moe)
        else:
            out2, aux = moe_mod.moe_forward(bparams["ffn"], h2, cfg.moe)
        x = x + out2
    return x, new_cache, aux


def stack_forward(
    stack_params: list[PyTree],
    x: Array,
    cfg: ModelConfig,
    *,
    angles: Array | None,
    mode: str = "forward",
    cache: list[PyTree] | None = None,
    cache_pos: Array | int = 0,
    remat: bool = False,
    attn_impl: str = "reference",
    act_pspec=None,
) -> tuple[Array, list[PyTree] | None, Array]:
    """Scan the group body over n_groups repetitions of the pattern.

    ``act_pspec``: optional PartitionSpec constraint re-applied to the
    residual stream after every block — the §Perf lever for
    sequence-parallel (shard T on "model") or weight-stationary decode
    (shard d on "data") layouts.

    Returns (x, new_cache_or_None, total_moe_aux).
    """
    n_pat = len(cfg.layer_pattern)
    has_cache_out = mode in ("prefill", "decode")

    def constrain(xx):
        if act_pspec is not None:
            return jax.lax.with_sharding_constraint(xx, act_pspec)
        return xx

    x = constrain(x)

    def group_body(carry, xs):
        xx, aux_acc = carry
        gparams, gcache = xs
        new_gcache = []
        for i, spec in enumerate(cfg.layer_pattern):
            c_in = gcache[i] if gcache is not None else None
            xx, c_out, aux = block_forward(
                gparams[i], xx, cfg, spec, angles=angles, mode=mode,
                cache=c_in, cache_pos=cache_pos, attn_impl=attn_impl,
            )
            xx = constrain(xx)
            new_gcache.append(c_out)
        ys = new_gcache if has_cache_out else None
        return (xx, aux_acc + aux), ys

    body = jax.checkpoint(group_body) if remat else group_body

    if mode == "decode":
        xs = (stack_params, cache)
    elif mode == "prefill":
        # cache provides capacities; its contents are ignored (rebuilt).
        xs = (stack_params, cache)
    else:
        xs = (stack_params, None)

    (x, aux), ys = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, (ys if has_cache_out else None), aux
