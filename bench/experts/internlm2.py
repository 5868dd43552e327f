"""internlm2 as a MUSE expert: a dense GQA decoder that scores an event.

An event's features hash to token ids (``tokens``: |x * 1000| mod vocab,
the schema of ``examples/serve_e2e.py``); the program's ``Model.forward``
runs the decoder over them and reads the risk score off the last token:
``sigmoid(score_head(final_norm(h)[:, -1]))``.

This file holds, for the configuration's sizes:

- ``init``: the weights, drawn on the device from a key in the type they
  are served in, laid out as the program's parameter tree;
- ``program_score_fn``: the scorer the server deploys (the program);
- ``reference``: a plain jnp forward in float32 at ``HIGHEST`` matmul
  precision, written from the published architecture (arXiv:2403.17297:
  pre-norm RMSNorm, grouped-query attention with rotary embeddings over
  split halves, SwiGLU) and independent of the program's code.  With
  ``precision="float8_e4m3fn"`` every matmul input is rounded to e4m3
  (scaled per row): the control;
- ``flops_per_event``: what one event's score needs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _sizes(spec: dict) -> tuple[int, ...]:
    return (spec["num_hidden_layers"], spec["hidden_size"],
            spec["num_attention_heads"], spec["num_key_value_heads"],
            spec["head_dim"], spec["intermediate_size"], spec["vocab_size"])


def tokens(features: np.ndarray, spec: dict) -> np.ndarray:
    feats = np.asarray(features, np.float32)[:, :spec["tokens_per_event"]]
    return (np.abs(feats * 1000).astype(np.int64)
            % spec["vocab_size"]).astype(np.int32)


# ----------------------------------------------------------------- weights
def init(key, spec: dict):
    """The program's parameter tree (``repro.models.model.Model.init``
    layout, the stack's leaves carrying a leading layer axis)."""
    n_layers, d, h, kv, hd, ff, vocab = _sizes(spec)
    dt = jnp.dtype(spec["dtype"])
    ks = iter(jax.random.split(key, 16))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def norm(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                              jnp.float32)).astype(dt)

    block = {
        "mixer_norm": {"scale": norm((n_layers, d))},
        "mixer": {"wq": {"w": dense((n_layers, d, h * hd), d)},
                  "wk": {"w": dense((n_layers, d, kv * hd), d)},
                  "wv": {"w": dense((n_layers, d, kv * hd), d)},
                  "wo": {"w": dense((n_layers, h * hd, d), h * hd)}},
        "ffn_norm": {"scale": norm((n_layers, d))},
        "ffn": {"gate": {"w": dense((n_layers, d, ff), d)},
                "up": {"w": dense((n_layers, d, ff), d)},
                "down": {"w": dense((n_layers, ff, d), ff)}},
    }
    return {
        "embed": {"table": (0.02 * jax.random.normal(
            next(ks), (vocab, d), jnp.float32)).astype(dt)},
        "stack": [block],
        "final_norm": {"scale": norm((d,))},
        "lm_head": {"w": dense((d, vocab), d)},
        "score_head": {"w": dense((d, 1), d),
                       "b": (0.1 * jax.random.normal(next(ks), (1,),
                                                     jnp.float32)).astype(dt)},
    }


# ----------------------------------------------------------------- program
def model_config(spec: dict):
    from repro.models.config import BlockSpec, ModelConfig

    n_layers, d, h, kv, hd, ff, vocab = _sizes(spec)
    return ModelConfig(
        name="internlm2", arch_type="dense", n_layers=n_layers, d_model=d,
        n_heads=h, n_kv_heads=kv, d_ff=ff, vocab_size=vocab, head_dim=hd,
        rope_theta=spec["rope_theta"], norm_eps=spec["rms_norm_eps"],
        layer_pattern=(BlockSpec("attn", "mlp"),))


@functools.lru_cache(maxsize=None)
def _scorer(sizes: tuple):
    from repro.models.model import Model

    model = Model(model_config(dict(sizes)))
    return jax.jit(lambda p, toks: model.forward(
        p, tokens=toks, logits_mode="last").risk_score)


def program_score_fn(spec: dict, params):
    """The scorer the server deploys: the program's forward, jitted once
    per set of sizes, called on the window's features."""
    scorer = _scorer(tuple(sorted(spec.items())))

    def score_fn(features):
        return scorer(params, jnp.asarray(tokens(features, spec)))
    return score_fn


# --------------------------------------------------------------- reference
def _round(x, precision: str):
    """Round a matmul input to ``precision``; e4m3 is scaled per row so
    its range holds the row's largest value."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.dtype(precision)).astype(
        jnp.float32) * scale


def _mm(x, w, precision: str):
    """x (..., k) @ w (k, n) in float32, inputs rounded to ``precision``
    (the weight per output column)."""
    return jnp.matmul(_round(x, precision), _round(w.T, precision).T,
                      precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, theta: float):
    """Rotate the two halves of each head by position-dependent angles."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv      # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "theta", "precision"))
def _layer(x, w, *, heads, kv_heads, eps, theta, precision):
    """One decoder layer over x (B, T, d), float32."""
    f32 = lambda a: a.astype(jnp.float32)                       # noqa: E731
    b, t, d = x.shape
    a = _rmsnorm(x, f32(w["mixer_norm"]["scale"]), eps)
    q = _mm(a, f32(w["mixer"]["wq"]["w"]), precision).reshape(b, t, heads, -1)
    k = _mm(a, f32(w["mixer"]["wk"]["w"]), precision).reshape(
        b, t, kv_heads, -1)
    v = _mm(a, f32(w["mixer"]["wv"]["w"]), precision).reshape(
        b, t, kv_heads, -1)
    q, k = _rotary(q, theta), _rotary(k, theta)
    group = heads // kv_heads            # query head i reads kv head i//group
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", _round(q, precision),
                   _round(k, precision), precision=HIGHEST)
    s = s / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round(p, precision),
                   _round(v, precision), precision=HIGHEST)
    x = x + _mm(o.reshape(b, t, -1), f32(w["mixer"]["wo"]["w"]), precision)
    a = _rmsnorm(x, f32(w["ffn_norm"]["scale"]), eps)
    g = _mm(a, f32(w["ffn"]["gate"]["w"]), precision)
    u = _mm(a, f32(w["ffn"]["up"]["w"]), precision)
    return x + _mm(jax.nn.silu(g) * u, f32(w["ffn"]["down"]["w"]), precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, final_scale, w, b, *, eps, precision):
    h = _rmsnorm(x[:, -1], final_scale.astype(jnp.float32), eps)
    logit = _mm(h, w.astype(jnp.float32), precision)[:, 0] \
        + b.astype(jnp.float32)[0]
    return jax.nn.sigmoid(logit)


def reference(spec: dict, params, features: np.ndarray,
              precision: str = "float32", block: int = 64) -> np.ndarray:
    """Risk scores of ``features`` (N, F) by the plain forward, in blocks
    of ``block`` events and one layer at a time."""
    n_layers = spec["num_hidden_layers"]
    toks = tokens(features, spec)
    n = len(toks)
    pad = (-n) % block
    toks = np.concatenate([toks, np.repeat(toks[-1:], pad, 0)]) if pad \
        else toks
    table = params["embed"]["table"]
    layers = [jax.tree.map(lambda a, i=i: a[i], params["stack"][0])
              for i in range(n_layers)]
    kw = dict(heads=spec["num_attention_heads"],
              kv_heads=spec["num_key_value_heads"],
              eps=spec["rms_norm_eps"], theta=spec["rope_theta"],
              precision=precision)
    out = []
    for lo in range(0, len(toks), block):
        x = table[jnp.asarray(toks[lo:lo + block])].astype(jnp.float32)
        for w in layers:
            x = _layer(x, w, **kw)
        out.append(np.asarray(_head(
            x, params["final_norm"]["scale"], params["score_head"]["w"],
            params["score_head"]["b"], eps=spec["rms_norm_eps"],
            precision=precision), np.float64))
    return np.concatenate(out)[:n]


# ------------------------------------------------------------------ counts
def flops_per_event(spec: dict) -> float:
    """Matmul FLOPs one event's score needs: the decoder's projections and
    SwiGLU over every token, causal attention (query i reads i+1 keys),
    and the score head on the last token.  The last-token LM-head logits
    the program also computes are not needed for the score."""
    n_layers, d, h, kv, hd, ff, _ = _sizes(spec)
    t = spec["tokens_per_event"]
    per_token = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    attention = 2 * 2 * h * hd * t * (t + 1) // 2
    return float(n_layers * (2 * per_token * t + attention) + 2 * d)
