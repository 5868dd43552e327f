"""A logistic expert over the event's features: ``sigmoid(x . w + b)``.

The configuration states it in float32, so the program's scorer runs its
dot product at ``HIGHEST`` precision (a default-precision float32 matmul on
a TPU rounds its inputs to bfloat16).  The reference is float64 numpy; the
control rounds x and w to bfloat16, the next precision down.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def init(key, spec: dict):
    kw, kb = jax.random.split(key)
    n = spec["features"]
    return {"w": jax.random.normal(kw, (n,), jnp.float32) / math.sqrt(n),
            "b": 0.5 * jax.random.normal(kb, (), jnp.float32)}


@functools.lru_cache(maxsize=None)
def _scorer(n: int):
    return jax.jit(lambda p, x: jax.nn.sigmoid(
        jnp.dot(x[:, :n], p["w"], precision=jax.lax.Precision.HIGHEST)
        + p["b"]))


def program_score_fn(spec: dict, params):
    scorer = _scorer(spec["features"])
    return lambda features: scorer(params, jnp.asarray(features,
                                                       jnp.float32))


def reference(spec: dict, params, features: np.ndarray,
              precision: str = "float32") -> np.ndarray:
    n = spec["features"]
    x = np.asarray(features, np.float64)[:, :n]
    w = np.asarray(params["w"], np.float64)
    if precision == "bfloat16":
        x = np.asarray(jnp.asarray(x, jnp.bfloat16), np.float64)
        w = np.asarray(jnp.asarray(w, jnp.bfloat16), np.float64)
    elif precision != "float32":
        raise ValueError(f"logistic: no {precision} path")
    return 1.0 / (1.0 + np.exp(-(x @ w + float(params["b"]))))


def flops_per_event(spec: dict) -> float:
    return float(2 * spec["features"])
