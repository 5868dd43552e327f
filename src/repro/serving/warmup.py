"""Replica warm-up (paper Sec. 3.1.2).

The paper's Java replicas suffer JIT-compilation latency on first requests;
MUSE exercises the real code path with synthetic traffic before marking the
pod ready.  The JAX analogue is exact: the first window of each shape
triggers XLA (and Mosaic) compilation, so a cold replica would blow the
latency SLO on live traffic.  ``warm_up`` compiles every program a window
of the given sizes reaches before readiness: each predictor's expert models
at every window's shape bucket, then the server's banked transform kernel
and fused tracker (:meth:`MuseServer.warm_dispatch`).
"""
from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

from repro.core.routing import Intent
from repro.serving.server import _shape_bucket
from repro.serving.types import ScoringRequest


def synthetic_requests(schema_dim: int, batch: int, tenant: str = "__warmup__",
                       seed: int = 0) -> list[ScoringRequest]:
    rng = np.random.default_rng(seed)
    return [
        ScoringRequest(
            intent=Intent(tenant=tenant),
            features=rng.normal(0, 1, schema_dim).astype(np.float32),
        )
        for _ in range(batch)
    ]


def warm_up(server, schema_dim: int, *, batch_sizes: tuple[int, ...] = (1, 8, 64),
            calls_per_shape: int = 2) -> dict[str, float]:
    """Compile every program a window of ``batch_sizes`` events reaches.

    Windows are padded to power-of-two buckets before the models and the
    kernel see them, so each size warms its bucket.  Returns {predictor:
    seconds spent on its models} — the Fig.-5 warm-up spike data; a
    predictor whose model group an earlier one already ran gets 0.0 (the
    group's executables are shared).  Calls predictors directly (catch-all
    routing rules cannot hide one), then the server's banked dispatch.
    """
    buckets = sorted({_shape_bucket(b) for b in batch_sizes if b > 0})
    timings: dict[str, float] = {}
    warmed: set[tuple[str, ...]] = set()
    for name, pred in server.predictors.items():
        t0 = time.perf_counter()
        if pred.model_names in warmed:
            timings[name] = 0.0
            continue
        warmed.add(pred.model_names)
        for bs in buckets:
            feats = np.random.default_rng(0).normal(0, 1, (bs, schema_dim)).astype(
                np.float32
            )
            for _ in range(calls_per_shape):
                np.asarray(pred.raw_scores(feats))
        timings[name] = time.perf_counter() - t0
    server.warm_dispatch(batch_sizes)
    return timings


@contextlib.contextmanager
def count_compiles():
    """Yields a list that collects one entry (seconds) per XLA backend
    compile inside the block — a warmed replica's first windows add none."""
    seen: list[float] = []

    def listener(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
