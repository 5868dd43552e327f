"""Jamba as a MUSE expert: a Mamba-1 + attention hybrid that scores an event
with its card's recent history.

An event's features hash to token ids (``tokens``, internlm2's schema);
the program's ``Model.forward`` runs the hybrid stack over them and reads
the risk score off the last token: ``sigmoid(score_head(final_norm(h)[:,
-1]))``.  Layer i is attention where i mod ``attn_layer_period`` is
``attn_layer_offset`` and Mamba elsewhere; every layer has a dense SwiGLU.

This file holds, for the configuration's sizes:

- ``init``: the weights, drawn on the device from a key in the type they
  are served in, laid out as the program's parameter tree;
- ``program_score_fn``: the scorer the server deploys (the program),
  jitted as ``jamba_score``;
- ``reference``: a plain jnp forward in float32 at ``HIGHEST`` matmul
  precision, written from the published architecture (arXiv:2403.19887
  and the AI21-Jamba2-3B config: pre-norm RMSNorm; Mamba-1 mixers whose
  dt, B and C pass an RMSNorm before the discretisation, run as a
  sequential scan over time; multi-query attention with no positional
  encoding; SwiGLU; tied embeddings) and independent of the program's
  code.  With ``precision="float8_e4m3fn"`` every matmul input is rounded
  to e4m3 (scaled per row): the control;
- ``flops_per_event``, and the selective scan's own counts ``scan_flops``
  and ``scan_bytes``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.experts.internlm2 import HIGHEST, _mm, _rmsnorm, _round, tokens


def _sizes(spec: dict) -> tuple[int, ...]:
    d = spec["hidden_size"]
    return (spec["num_hidden_layers"], d, spec["num_attention_heads"],
            spec["num_key_value_heads"], d // spec["num_attention_heads"],
            spec["intermediate_size"], spec["vocab_size"],
            spec["mamba_expand"] * d, spec["mamba_d_state"],
            spec["mamba_dt_rank"], spec["mamba_d_conv"])


def _is_attention(spec: dict, i: int) -> bool:
    return i % spec["attn_layer_period"] == spec["attn_layer_offset"]


# ----------------------------------------------------------------- weights
def init(key, spec: dict):
    """The program's parameter tree (``repro.models.model.Model.init``
    layout: one block per position of the attention period, its leaves
    carrying a leading axis over the periods).  A_log is log(1..N) per
    channel with small noise and the dt bias puts softplus(dt) in [1e-3,
    0.1], as Mamba initialises them, so the state carries over tens of
    steps."""
    n_layers, d, h, kv, hd, ff, vocab, d_in, n, rank, k = _sizes(spec)
    period = spec["attn_layer_period"]
    groups = n_layers // period
    dt = jnp.dtype(spec["dtype"])
    ks = iter(jax.random.split(key, 32 * period + 8))

    def normal(shape, scale):
        return scale * jax.random.normal(next(ks), shape, jnp.float32)

    def dense(shape, fan_in):
        return (normal(shape, 1.0 / math.sqrt(fan_in))).astype(dt)

    def norm(width):
        return {"scale": (1.0 + normal((groups, width), 0.1)).astype(dt)}

    def mamba():
        step = jnp.exp(jax.random.uniform(
            next(ks), (groups, d_in), jnp.float32,
            math.log(1e-3), math.log(0.1)))
        a = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
        return {
            "in_proj": {"w": dense((groups, d, 2 * d_in), d)},
            "conv_w": dense((groups, k, d_in), k),
            "conv_b": normal((groups, d_in), 0.1).astype(dt),
            "x_proj": {"w": dense((groups, d_in, rank + 2 * n), d_in)},
            "dt_proj": {"w": dense((groups, rank, d_in), rank)},
            # inverse softplus of the starting step
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "A_log": (a + normal((groups, d_in, n), 0.01)).astype(dt),
            "D": (1.0 + normal((groups, d_in), 0.1)).astype(dt),
            "out_proj": {"w": dense((groups, d_in, d), d_in)},
            "dt_norm": norm(rank), "b_norm": norm(n), "c_norm": norm(n),
        }

    def attention():
        return {"wq": {"w": dense((groups, d, h * hd), d)},
                "wk": {"w": dense((groups, d, kv * hd), d)},
                "wv": {"w": dense((groups, d, kv * hd), d)},
                "wo": {"w": dense((groups, h * hd, d), h * hd)}}

    stack = [{"mixer_norm": norm(d),
              "mixer": attention() if _is_attention(spec, i) else mamba(),
              "ffn_norm": norm(d),
              "ffn": {"gate": {"w": dense((groups, d, ff), d)},
                      "up": {"w": dense((groups, d, ff), d)},
                      "down": {"w": dense((groups, ff, d), ff)}}}
             for i in range(period)]
    return {
        "embed": {"table": normal((vocab, d), 0.02).astype(dt)},
        "stack": stack,
        "final_norm": {"scale": (1.0 + normal((d,), 0.1)).astype(dt)},
        "score_head": {"w": dense((d, 1), d),
                       "b": normal((1,), 0.1).astype(dt)},
    }


# ----------------------------------------------------------------- program
def model_config(spec: dict):
    from repro.models.config import BlockSpec, MambaConfig, ModelConfig

    n_layers, d, h, kv, hd, ff, vocab, _, n, rank, k = _sizes(spec)
    return ModelConfig(
        name="jamba", arch_type="hybrid", n_layers=n_layers, d_model=d,
        n_heads=h, n_kv_heads=kv, d_ff=ff, vocab_size=vocab, head_dim=hd,
        rope=False, norm_eps=spec["rms_norm_eps"], tie_embeddings=True,
        layer_pattern=tuple(
            BlockSpec("attn" if _is_attention(spec, i) else "mamba", "mlp")
            for i in range(spec["attn_layer_period"])),
        mamba=MambaConfig(d_state=n, d_conv=k, expand=spec["mamba_expand"],
                          dt_rank=rank, inner_norms=True))


@functools.lru_cache(maxsize=None)
def _scorer(sizes: tuple):
    from repro.models.model import Model

    model = Model(model_config(dict(sizes)))

    def jamba_score(params, toks):
        return model.forward(params, tokens=toks,
                             logits_mode="last").risk_score
    return jax.jit(jamba_score)


def program_score_fn(spec: dict, params):
    """The scorer the server deploys: the program's forward, jitted once
    per set of sizes, called on the window's features."""
    scorer = _scorer(tuple(sorted(spec.items())))

    def score_fn(features):
        return scorer(params, jnp.asarray(tokens(features, spec)))
    return score_fn


# --------------------------------------------------------------- reference
def _swiglu(x, w, eps, precision):
    f32 = lambda a: a.astype(jnp.float32)                       # noqa: E731
    a = _rmsnorm(x, f32(w["ffn_norm"]["scale"]), eps)
    g = _mm(a, f32(w["ffn"]["gate"]["w"]), precision)
    u = _mm(a, f32(w["ffn"]["up"]["w"]), precision)
    return x + _mm(jax.nn.silu(g) * u, f32(w["ffn"]["down"]["w"]), precision)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "precision"))
def _attention_layer(x, w, *, heads, kv_heads, eps, precision):
    """Causal grouped-query attention without positional encoding, then
    the SwiGLU; x (B, T, d) float32."""
    f32 = lambda a: a.astype(jnp.float32)                       # noqa: E731
    b, t, _ = x.shape
    m = w["mixer"]
    a = _rmsnorm(x, f32(w["mixer_norm"]["scale"]), eps)
    q = _mm(a, f32(m["wq"]["w"]), precision).reshape(b, t, heads, -1)
    k = _mm(a, f32(m["wk"]["w"]), precision).reshape(b, t, kv_heads, -1)
    v = _mm(a, f32(m["wv"]["w"]), precision).reshape(b, t, kv_heads, -1)
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
    x = x + _mm(o.reshape(b, t, -1), f32(m["wo"]["w"]), precision)
    return _swiglu(x, w, eps, precision)


@functools.partial(jax.jit, static_argnames=("rank", "eps", "precision"))
def _mamba_layer(x, w, *, rank, eps, precision):
    """The Mamba-1 mixer with Jamba's inner norms, its recurrence a
    sequential scan over time, then the SwiGLU; x (B, T, d) float32."""
    f32 = lambda a: a.astype(jnp.float32)                       # noqa: E731
    t = x.shape[1]
    m = jax.tree.map(f32, w["mixer"])
    a = _rmsnorm(x, f32(w["mixer_norm"]["scale"]), eps)
    xi, z = jnp.split(_mm(a, m["in_proj"]["w"], precision), 2, axis=-1)
    # depthwise causal convolution: tap j reads the input k - 1 - j steps back
    k = m["conv_w"].shape[0]
    xp = jnp.pad(xi, ((0, 0), (k - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(xp[:, j:j + t] * m["conv_w"][j] for j in range(k))
                    + m["conv_b"])
    n = m["A_log"].shape[-1]
    dt_r, bm, cm = jnp.split(_mm(u, m["x_proj"]["w"], precision),
                             [rank, rank + n], axis=-1)
    dt_r = _rmsnorm(dt_r, m["dt_norm"]["scale"], eps)
    bm = _rmsnorm(bm, m["b_norm"]["scale"], eps)
    cm = _rmsnorm(cm, m["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(_mm(dt_r, m["dt_proj"]["w"], precision)
                         + m["dt_bias"])                     # (B, T, d_in)
    a_mat = -jnp.exp(m["A_log"])                              # (d_in, N)

    def step(h, inp):
        dt_t, u_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, :, None] * a_mat) * h \
            + (dt_t * u_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    h0 = jnp.zeros(u.shape[:1] + a_mat.shape, jnp.float32)
    _, ys = jax.lax.scan(step, h0, tuple(jnp.swapaxes(v, 0, 1)
                                         for v in (dt, u, bm, cm)))
    y = (jnp.swapaxes(ys, 0, 1) + m["D"] * u) * jax.nn.silu(z)
    x = x + _mm(y, m["out_proj"]["w"], precision)
    return _swiglu(x, w, eps, precision)


def _last_hidden(spec: dict, params, features: np.ndarray, precision: str,
                 block: int):
    """The final-normed last-token hidden state of every event (N, d), in
    blocks of ``block`` events and one layer at a time."""
    period = spec["attn_layer_period"]
    eps = spec["rms_norm_eps"]
    toks = tokens(features, spec)
    n = len(toks)
    pad = (-n) % block
    toks = np.concatenate([toks, np.repeat(toks[-1:], pad, 0)]) if pad \
        else toks
    table = params["embed"]["table"]
    final = params["final_norm"]["scale"].astype(jnp.float32)
    out = []
    for lo in range(0, len(toks), block):
        x = table[jnp.asarray(toks[lo:lo + block])].astype(jnp.float32)
        for i in range(spec["num_hidden_layers"]):
            w = jax.tree.map(lambda a, g=i // period: a[g],
                             params["stack"][i % period])
            if _is_attention(spec, i):
                x = _attention_layer(
                    x, w, heads=spec["num_attention_heads"],
                    kv_heads=spec["num_key_value_heads"], eps=eps,
                    precision=precision)
            else:
                x = _mamba_layer(x, w, rank=spec["mamba_dt_rank"], eps=eps,
                                 precision=precision)
        out.append(_rmsnorm(x[:, -1], final, eps))
    return jnp.concatenate(out)[:n]


def reference(spec: dict, params, features: np.ndarray,
              precision: str = "float32", block: int = 32) -> np.ndarray:
    """Risk scores of ``features`` (N, F) by the plain forward."""
    h = _last_hidden(spec, params, features, precision, block)
    w = params["score_head"]["w"].astype(jnp.float32)
    logit = _mm(h, w, precision)[:, 0] \
        + params["score_head"]["b"].astype(jnp.float32)[0]
    return np.asarray(jax.nn.sigmoid(logit), np.float64)


def reference_logits(spec: dict, params, features: np.ndarray,
                     precision: str = "float32", block: int = 32
                     ) -> np.ndarray:
    """Last-token LM logits (N, vocab): the tied embedding as the head."""
    h = _last_hidden(spec, params, features, precision, block)
    table = params["embed"]["table"].astype(jnp.float32)
    return np.asarray(_mm(h, table.T, precision), np.float64)


# ------------------------------------------------------------------ counts
def flops_per_event(spec: dict) -> float:
    """FLOPs one event's score needs: the projections and SwiGLU of every
    layer over every token, causal attention (query i reads i+1 keys), the
    selective scan (``scan_flops``), and the score head on the last token.
    The last-token LM-head logits the program also computes are not needed
    for the score."""
    n_layers, d, h, kv, hd, ff, _, d_in, n, rank, _ = _sizes(spec)
    t = spec["tokens_per_event"]
    n_attn = sum(_is_attention(spec, i) for i in range(n_layers))
    mlp = 3 * d * ff
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d + mlp
    mamba = d * 2 * d_in + d_in * (rank + 2 * n) + rank * d_in + d_in * d \
        + mlp
    scores = 2 * 2 * h * hd * t * (t + 1) // 2
    return float(n_attn * (2 * attn * t + scores)
                 + (n_layers - n_attn) * 2 * mamba * t
                 + scan_flops(spec, t) + 2 * d)


def _mamba_layers(spec: dict) -> int:
    return sum(not _is_attention(spec, i)
               for i in range(spec["num_hidden_layers"]))


def scan_flops(spec: dict, tokens: int) -> float:
    """The selective scan's FLOPs over ``tokens`` tokens: per token, Mamba
    layer and (channel, state) pair, exp(dt A) (2), the decay (1), the
    input (dt x) B and its sum (2), and C h with its sum (2); per channel,
    dt x (1) and the D skip (2)."""
    _, _, _, _, _, _, _, d_in, n, _, _ = _sizes(spec)
    return float(tokens * _mamba_layers(spec) * d_in * (7 * n + 3))


def scan_bytes(spec: dict, tokens: int) -> float:
    """Bytes any selective scan must move over ``tokens`` tokens: per token
    and Mamba layer, dt, x and B, C read and y written once, in float32."""
    _, _, _, _, _, _, _, d_in, n, _, _ = _sizes(spec)
    return float(tokens * _mamba_layers(spec) * (3 * d_in + 2 * n) * 4)
