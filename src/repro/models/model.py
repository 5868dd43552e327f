"""Model: config -> init / forward / prefill / decode_step entry points.

Every architecture exposes the same four callables, which is what lets the
serving layer (predictors, routing) treat heterogeneous experts uniformly —
the paper's predictor abstraction requires exactly this interface shape.

Outputs always include the **risk score head** (sigmoid scalar per sequence):
the raw expert score that MUSE's transformation pipeline (T^C -> A -> T^Q)
consumes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import layers, transformer
from repro.models.config import ModelConfig

Array = jax.Array
PyTree = Any


class ModelOutput(NamedTuple):
    logits: Array       # (B, T, vocab) — LM / frame-unit logits
    risk_score: Array   # (B,) — raw expert score in [0, 1]
    moe_aux: Array      # () — load-balance auxiliary loss
    hidden: Array       # (B, T, d) final hidden states


class DecodeOutput(NamedTuple):
    logits: Array       # (B, vocab) next-token logits
    risk_score: Array   # (B,)
    cache: Any


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- init ----------------------------------------------------------------
    def init(self, rng, dtype=jnp.float32) -> PyTree:
        cfg = self.cfg
        k_emb, k_stack, k_head, k_score = jax.random.split(rng, 4)
        params: dict[str, PyTree] = {
            "embed": layers.init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dtype),
            "stack": transformer.init_stack(k_stack, cfg, dtype),
            "final_norm": layers.init_rmsnorm(cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = layers.init_linear(
                k_head, cfg.d_model, cfg.vocab_size, dtype=dtype
            )
        if cfg.score_head:
            params["score_head"] = layers.init_linear(
                k_score, cfg.d_model, 1, bias=True, dtype=dtype
            )
        return params

    # -- shared pieces ---------------------------------------------------------
    def _embed_input(self, params, tokens, embeds, compute_dtype):
        if embeds is not None:
            return embeds.astype(compute_dtype)
        return layers.embed(params["embed"], tokens, compute_dtype)

    def _angles(self, batch: int, seq: int, offset, position_ids):
        cfg = self.cfg
        if not cfg.rope:
            return None
        if cfg.mrope:
            if position_ids is None:
                position_ids = layers.text_position_ids(batch, seq, offset)
            return layers.mrope_angles(
                position_ids, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections
            )
        pos = jnp.arange(seq) + jnp.asarray(offset)
        return layers.rope_angles(pos, cfg.head_dim, cfg.rope_theta)  # (T, half)

    def _heads(self, params, h, compute_dtype, logits_mode: str = "all"):
        cfg = self.cfg
        h_norm = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
        h_lm = h_norm[:, -1:] if logits_mode == "last" else h_norm
        if cfg.tie_embeddings:
            logits = h_lm @ params["embed"]["table"].astype(compute_dtype).T
        else:
            logits = layers.linear(params["lm_head"], h_lm)
        if cfg.score_head:
            # decoder: last-token hidden; encoder: mean pool
            pooled = (
                jnp.mean(h_norm, axis=1) if cfg.is_encoder_only else h_norm[:, -1]
            )
            raw = layers.linear(params["score_head"], pooled)[..., 0]
            score = jax.nn.sigmoid(raw.astype(jnp.float32))
        else:
            score = jnp.zeros(h.shape[0], jnp.float32)
        return logits, score, h_norm

    # -- full-sequence forward (train / eval / encoder serve) ----------------
    def forward(
        self,
        params: PyTree,
        tokens: Array | None = None,
        embeds: Array | None = None,
        *,
        position_ids: Array | None = None,
        remat: bool = False,
        compute_dtype=jnp.bfloat16,
        attn_impl: str = "reference",
        logits_mode: str = "all",
        act_pspec=None,
    ) -> ModelOutput:
        cfg = self.cfg
        x = self._embed_input(params, tokens, embeds, compute_dtype)
        b, t = x.shape[:2]
        angles = self._angles(b, t, 0, position_ids)
        x, _, aux = transformer.stack_forward(
            params["stack"], x, cfg, angles=angles, mode="forward",
            remat=remat, attn_impl=attn_impl, act_pspec=act_pspec,
        )
        logits, score, h = self._heads(params, x, compute_dtype, logits_mode)
        return ModelOutput(logits=logits, risk_score=score, moe_aux=aux, hidden=h)

    # -- prefill: build decode caches from a prompt --------------------------
    def prefill(
        self,
        params: PyTree,
        tokens: Array | None = None,
        embeds: Array | None = None,
        *,
        cache_capacity: int,
        position_ids: Array | None = None,
        compute_dtype=jnp.bfloat16,
        cache_dtype=jnp.bfloat16,
        attn_impl: str = "reference",
        logits_mode: str = "all",
        act_pspec=None,
    ) -> tuple[ModelOutput, list[PyTree]]:
        cfg = self.cfg
        if not cfg.has_decode:
            raise ValueError(f"{cfg.name} is encoder-only: no decode/prefill")
        x = self._embed_input(params, tokens, embeds, compute_dtype)
        b, t = x.shape[:2]
        angles = self._angles(b, t, 0, position_ids)
        cache = transformer.init_cache(cfg, b, cache_capacity, cache_dtype)
        x, new_cache, aux = transformer.stack_forward(
            params["stack"], x, cfg, angles=angles, mode="prefill",
            cache=cache, attn_impl=attn_impl, act_pspec=act_pspec,
        )
        logits, score, h = self._heads(params, x, compute_dtype, logits_mode)
        return ModelOutput(logits, score, aux, h), new_cache

    # -- decode: one token against an existing cache -------------------------
    def decode_step(
        self,
        params: PyTree,
        cache: list[PyTree],
        tokens: Array | None = None,
        embeds: Array | None = None,
        *,
        pos: Array | int,
        position_ids: Array | None = None,
        compute_dtype=jnp.bfloat16,
        attn_impl: str = "reference",
        act_pspec=None,
    ) -> DecodeOutput:
        cfg = self.cfg
        if not cfg.has_decode:
            raise ValueError(f"{cfg.name} is encoder-only: no decode step")
        x = self._embed_input(params, tokens, embeds, compute_dtype)
        b = x.shape[0]
        angles = self._angles(b, 1, pos, position_ids)
        x, new_cache, _ = transformer.stack_forward(
            params["stack"], x, cfg, angles=angles, mode="decode",
            cache=cache, cache_pos=pos, attn_impl=attn_impl,
            act_pspec=act_pspec,
        )
        logits, score, _ = self._heads(params, x, compute_dtype)
        return DecodeOutput(
            logits=logits[:, 0], risk_score=score, cache=new_cache
        )

    # -- convenience ----------------------------------------------------------
    def init_cache(self, batch: int, capacity: int, dtype=jnp.bfloat16):
        return transformer.init_cache(self.cfg, batch, capacity, dtype)

    def param_count(self, params: PyTree) -> int:
        return sum(int(p.size) for p in jax.tree.leaves(params))
