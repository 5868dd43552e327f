"""Composable score transformations (paper Sec. 2.3).

Three transformation nodes compose a predictor's post-model DAG:

  * :class:`PosteriorCorrection`  — ``T^C`` (Eq. 3), undoes undersampling bias.
  * :class:`Aggregation`          — ``A``, weighted average of calibrated experts.
  * :class:`QuantileMap`          — ``T^Q`` (Eq. 4), piecewise-linear CDF alignment.

All transforms are pure pytrees of arrays + static metadata so they can live
inside jitted serving steps, be donated, swapped (the paper's "seamless model
update" = replacing these pytrees under a stable routing intent), and sharded.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# Posterior Correction (Eq. 3)
# ---------------------------------------------------------------------------

def posterior_correction(scores: Array, beta: Array | float) -> Array:
    """Eq. 3: ``T^C(y) = beta*y / (1 - (1-beta)*y)``.

    ``beta`` is the undersampling ratio of the majority (negative) class used
    when training the expert: ``beta = P(keep negative sample)``.  Scores are
    posterior probabilities in [0, 1].  The map is monotone, fixes 0 and 1,
    and is the exact analytical inverse of the prior shift introduced by
    undersampling (Dal Pozzolo et al., 2015).
    """
    scores = jnp.asarray(scores)
    beta = jnp.asarray(beta, dtype=scores.dtype)
    return (beta * scores) / (1.0 - (1.0 - beta) * scores)


def posterior_correction_inverse(corrected: Array, beta: Array | float) -> Array:
    """Inverse of Eq. 3 — maps a true posterior back to the biased score.

    Used by the synthetic data pipeline to *induce* undersampling bias with a
    known ground truth, and in tests as the round-trip oracle.
    """
    corrected = jnp.asarray(corrected)
    beta = jnp.asarray(beta, dtype=corrected.dtype)
    return corrected / (beta + (1.0 - beta) * corrected)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PosteriorCorrection:
    """Per-expert ``T^C_k`` node: carries the training undersampling ratio."""

    beta: Array  # scalar (or broadcastable) undersampling ratio in (0, 1]

    def __call__(self, scores: Array) -> Array:
        return posterior_correction(scores, self.beta)

    @staticmethod
    def identity() -> "PosteriorCorrection":
        # beta = 1.0 means "no undersampling" -> T^C is the identity map.
        return PosteriorCorrection(beta=jnp.float32(1.0))


# ---------------------------------------------------------------------------
# Ensemble aggregation (Sec. 2.3.2)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Aggregation:
    """Weighted-average aggregation ``A`` over K calibrated expert scores.

    Weights are normalized at call time so that updating them (the paper's
    "lightweight model adaptation") never needs renormalization bookkeeping.
    """

    weights: Array  # (K,)

    def __call__(self, expert_scores: Array) -> Array:
        """``expert_scores``: (..., K) -> (...)."""
        w = self.weights / jnp.sum(self.weights)
        return jnp.einsum("...k,k->...", expert_scores, w,
                          precision=jax.lax.Precision.HIGHEST)

    @staticmethod
    def uniform(k: int) -> "Aggregation":
        return Aggregation(weights=jnp.ones((k,), dtype=jnp.float32))


# ---------------------------------------------------------------------------
# Quantile Mapping (Eq. 4)
# ---------------------------------------------------------------------------

def _searchsorted_branchless(table: Array, values: Array) -> Array:
    """TPU-friendly bucket search: index i s.t. table[i] <= v < table[i+1].

    The paper computes this with an O(log N) binary search on CPU.  On TPU a
    data-dependent branchy search is hostile to the VPU; an N-wide broadcast
    compare-and-sum is a handful of vector ops and keeps everything dense.
    Clamps to [0, N-2] so interpolation always has a right neighbour.
    """
    n = table.shape[-1]
    # sum over the table axis of (v >= q_i) gives #quantiles <= v; -1 -> index.
    idx = jnp.sum(values[..., None] >= table, axis=-1) - 1
    return jnp.clip(idx, 0, n - 2)


def quantile_map(
    scores: Array,
    src_quantiles: Array,
    ref_quantiles: Array,
) -> Array:
    """Eq. 4: piecewise-linear map aligning CDF of S onto CDF of R.

    ``src_quantiles``/``ref_quantiles``: (N,) monotone non-decreasing arrays of
    matched quantiles q^S_i, q^R_i (same quantile levels).  The map is monotone
    (non-decreasing), hence rank/ROC preserving — the paper's key invariant.
    Values outside [q^S_1, q^S_N] are linearly extended from the edge segment
    and clipped to the reference support.
    """
    scores = jnp.asarray(scores)
    dtype = scores.dtype
    qs = src_quantiles.astype(dtype)
    qr = ref_quantiles.astype(dtype)
    i = _searchsorted_branchless(qs, scores)
    q_s_i = jnp.take(qs, i)
    q_s_n = jnp.take(qs, i + 1)
    q_r_i = jnp.take(qr, i)
    q_r_n = jnp.take(qr, i + 1)
    # Guard degenerate (flat) source segments.
    denom = jnp.where(q_s_n - q_s_i > 0, q_s_n - q_s_i, jnp.asarray(1.0, dtype))
    slope = (q_r_n - q_r_i) / denom
    out = q_r_i + (scores - q_s_i) * slope
    return jnp.clip(out, qr[0], qr[-1])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantileMap:
    """``T^Q`` node: tenant-specific source quantiles -> shared reference."""

    src_quantiles: Array  # (N,)
    ref_quantiles: Array  # (N,)

    def __call__(self, scores: Array) -> Array:
        return quantile_map(scores, self.src_quantiles, self.ref_quantiles)

    @property
    def num_quantiles(self) -> int:
        return self.src_quantiles.shape[-1]

    @staticmethod
    def identity(n: int = 64) -> "QuantileMap":
        q = jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)
        return QuantileMap(src_quantiles=q, ref_quantiles=q)

    @staticmethod
    def fit(
        source_scores: np.ndarray | Array,
        ref_quantiles: Array,
        levels: np.ndarray | None = None,
    ) -> "QuantileMap":
        """Fit tenant-specific source quantiles from (unlabeled!) scores.

        This is the offline fitting path (Sec. 2.3.3): needs only raw score
        samples, no labels.  ``ref_quantiles`` must be evaluated at the same
        quantile ``levels`` (default: uniform grid of len(ref_quantiles)).
        """
        ref_q = np.asarray(ref_quantiles, dtype=np.float64)
        n = ref_q.shape[-1]
        if levels is None:
            levels = np.linspace(0.0, 1.0, n)
        src = np.quantile(np.asarray(source_scores, dtype=np.float64), levels)
        src = np.maximum.accumulate(src)  # enforce monotone vs fp jitter
        return QuantileMap(
            src_quantiles=jnp.asarray(src, dtype=jnp.float32),
            ref_quantiles=jnp.asarray(ref_q, dtype=jnp.float32),
        )


# ---------------------------------------------------------------------------
# Reference distributions (Sec. 2.3.3 / Sec. 7 of DESIGN.md)
# ---------------------------------------------------------------------------

def fraud_reference_quantiles(n: int = 256, *, a: float = 0.8, b: float = 8.0,
                              tail_w: float = 0.02, tail_a: float = 6.0,
                              tail_b: float = 1.5) -> Array:
    """A configurable reference distribution R with high density near 0 and a
    long tail toward 1 (the paper's guidance for imbalanced fraud settings:
    more resolution in the 0.1%–1% alert-rate region).

    Mixture: (1-tail_w)·Beta(a, b) + tail_w·Beta(tail_a, tail_b).
    Returns its quantiles on a uniform level grid, via numerical CDF inversion.
    """
    from scipy import stats  # offline path only

    levels = np.linspace(0.0, 1.0, n)
    grid = np.linspace(0.0, 1.0, 65537)
    cdf = (1.0 - tail_w) * stats.beta.cdf(grid, a, b) + tail_w * stats.beta.cdf(
        grid, tail_a, tail_b
    )
    q = np.interp(levels, cdf, grid)
    q = np.maximum.accumulate(q)
    return jnp.asarray(q, dtype=jnp.float32)


def uniform_reference_quantiles(n: int = 256) -> Array:
    return jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Full Eq. 2 pipeline (reference composition; fused kernel in kernels/)
# ---------------------------------------------------------------------------

def score_pipeline(
    expert_scores: Array,
    betas: Array,
    weights: Array,
    src_quantiles: Array,
    ref_quantiles: Array,
) -> Array:
    """Eq. 2 end-to-end: ``T^Q(A([T^C_k(m_k(x))]))``.

    ``expert_scores``: (..., K) raw scores from the K experts.
    Pure-jnp composition; ``kernels/score_pipeline.py`` provides the fused
    Pallas version with identical semantics (this function is its oracle).
    """
    corrected = posterior_correction(expert_scores, betas)
    w = weights / jnp.sum(weights)
    agg = jnp.einsum("...k,k->...", corrected, w,
                     precision=jax.lax.Precision.HIGHEST)
    return quantile_map(agg, src_quantiles, ref_quantiles)


def pad_quantile_tables(
    value: "QuantileMap | tuple[Array, Array]", n: int, *, row: int | None = None,
) -> tuple[Array, Array]:
    """Normalize one replacement T^Q table pair to exactly ``n`` knots.

    ``value`` is a :class:`QuantileMap` or a raw ``(src, ref)`` pair.  Tables
    narrower than ``n`` are edge-padded: the extra flat segments are
    degenerate (guarded denominator in :func:`quantile_map`) and values past
    the true support already clip to the reference edge, so padding is
    semantics-preserving.  Wider tables are a shape error.  Shared by both
    bank ``with_rows`` scatters and the tiered store's host-row writes
    (``serving/tiering.py``), which must pad identically for the tiered
    path to stay bitwise-equal to a dense bank built from the same rows.
    """
    src, ref = (value.src_quantiles, value.ref_quantiles) \
        if isinstance(value, QuantileMap) else value
    src = jnp.asarray(src, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    pad = n - src.shape[-1]
    if pad < 0:
        where = f"row {row}: " if row is not None else ""
        raise ValueError(f"{where}{src.shape[-1]} knots > bank's {n}")
    if pad:
        src = jnp.pad(src, (0, pad), mode="edge")
        ref = jnp.pad(ref, (0, pad), mode="edge")
    return src, ref


# ---------------------------------------------------------------------------
# Tenant-indexed transform bank (mixed-tenant batched Eq. 2)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TransformBank:
    """Stacked per-(tenant, predictor) transform parameters.

    One row per distinct post-model pipeline; a mixed-tenant micro-batch
    carries a per-row ``tenant_idx`` selecting its bank row, so the whole
    batch runs Eq. 2 in ONE dispatch (``kernels/score_pipeline.py::
    score_pipeline_banked``) instead of a Python loop of per-predictor calls.
    This is MUSE's multi-tenant reuse made literal on the serving hot path.

    Banks are immutable and carry a ``generation`` (static metadata, not a
    traced leaf): the calibration control plane publishes a refreshed bank as
    a NEW object with a bumped generation and swaps the reference atomically.
    In-flight dispatches that already snapshotted the old bank finish on the
    old parameters; the next window sees the new generation — never a torn
    mix of rows from two calibration versions.
    """

    betas: Array          # (T, K)
    weights: Array        # (T, K)
    src_quantiles: Array  # (T, N)
    ref_quantiles: Array  # (T, N)
    generation: int = dataclasses.field(
        default=0, metadata=dict(static=True))

    @property
    def num_rows(self) -> int:
        return int(self.betas.shape[0])

    @property
    def num_experts(self) -> int:
        return int(self.betas.shape[-1])

    @property
    def num_quantiles(self) -> int:
        return int(self.src_quantiles.shape[-1])

    def __call__(self, expert_scores: Array, tenant_idx: Array) -> Array:
        return banked_score_pipeline(
            expert_scores, tenant_idx, self.betas, self.weights,
            self.src_quantiles, self.ref_quantiles,
        )

    def pre_quantile(self, expert_scores: Array, tenant_idx: Array) -> Array:
        """Per-row T^Q input (corrected weighted aggregate) — what a
        refreshed T^Q must be fitted on; see TransformPipeline.pre_quantile.

        One jitted call: this sits on the serving hot path (quantile
        tracking, stage 3 of the banked dispatch), where an unfused chain of
        small dispatches measurably contends with the other engine stages."""
        return _banked_pre_quantile(expert_scores, tenant_idx, self.betas,
                                    self.weights)

    def with_rows(
        self,
        rows: Mapping[int, tuple[Array, Array]] | Mapping[int, "QuantileMap"],
        *,
        generation: int | None = None,
    ) -> "TransformBank":
        """Functional update: replace the T^Q tables of selected rows.

        ``rows`` maps row index -> ``QuantileMap`` (or a raw ``(src, ref)``
        pair).  Returns a NEW bank — the receiver is never mutated, so any
        dispatch holding it keeps scoring with the old parameters.  All
        replacement tables are scattered in one ``.at[idx].set`` per array.
        Tables narrower than the bank's N are edge-padded (flat segments are
        degenerate-guarded, same as ``from_params``); wider tables are a
        shape error.  ``generation`` defaults to the current one + 1.
        """
        if not rows:
            return self if generation is None else dataclasses.replace(
                self, generation=generation)
        n = self.num_quantiles
        idx, srcs, refs = [], [], []
        for row, value in sorted(rows.items()):
            if not 0 <= row < self.num_rows:
                raise IndexError(f"row {row} outside bank of {self.num_rows}")
            src, ref = pad_quantile_tables(value, n, row=row)
            idx.append(row)
            srcs.append(src)
            refs.append(ref)
        idx = jnp.asarray(idx, jnp.int32)
        return dataclasses.replace(
            self,
            src_quantiles=self.src_quantiles.at[idx].set(jnp.stack(srcs)),
            ref_quantiles=self.ref_quantiles.at[idx].set(jnp.stack(refs)),
            generation=self.generation + 1 if generation is None else generation,
        )

    @staticmethod
    def from_params(params: Sequence[tuple[Array, Array, Array, Array]],
                    *, generation: int = 0) -> "TransformBank":
        """Stack (betas, weights, src_q, ref_q) rows, padding ragged axes.

        Expert axes are padded with ``beta=1, weight=0`` columns (identity
        correction, zero aggregation mass).  Quantile tables are padded by
        repeating the last knot: the extra flat segments are degenerate
        (guarded denominator) and values past the true support already clip
        to the reference edge, so padding is semantics-preserving.
        """
        if not params:
            raise ValueError("cannot build an empty TransformBank")
        rows = [(jnp.atleast_1d(jnp.asarray(b, jnp.float32)),
                 jnp.atleast_1d(jnp.asarray(w, jnp.float32)),
                 jnp.asarray(qs, jnp.float32), jnp.asarray(qr, jnp.float32))
                for b, w, qs, qr in params]
        k_max = max(b.shape[-1] for b, _, _, _ in rows)
        n_max = max(qs.shape[-1] for _, _, qs, _ in rows)

        def _pad_k(x, fill):
            pad = k_max - x.shape[-1]
            return jnp.pad(x, (0, pad), constant_values=fill) if pad else x

        def _pad_n(x):
            pad = n_max - x.shape[-1]
            return jnp.pad(x, (0, pad), mode="edge") if pad else x

        return TransformBank(
            betas=jnp.stack([_pad_k(b, 1.0) for b, _, _, _ in rows]),
            weights=jnp.stack([_pad_k(w, 0.0) for _, w, _, _ in rows]),
            src_quantiles=jnp.stack([_pad_n(qs) for _, _, qs, _ in rows]),
            ref_quantiles=jnp.stack([_pad_n(qr) for _, _, _, qr in rows]),
            generation=generation,
        )


@jax.jit
def _banked_pre_quantile(expert_scores: Array, tenant_idx: Array,
                         betas: Array, weights: Array) -> Array:
    tenant_idx = jnp.asarray(tenant_idx, jnp.int32)
    b = jnp.take(betas, tenant_idx, axis=0)       # (B, K)
    w = jnp.take(weights, tenant_idx, axis=0)     # (B, K)
    corrected = posterior_correction(expert_scores, b)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.sum(corrected * w, axis=-1)


# ---------------------------------------------------------------------------
# Tenant-sharded transform bank (mesh row partition, ROADMAP "Sharded
# transform banks")
# ---------------------------------------------------------------------------

TENANT_AXIS = "tenants"  # mesh axis name the bank rows are partitioned over


def shard_rows(num_rows: int, num_shards: int,
               shard_of: np.ndarray | None = None
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Row-partition rule shared by every sharded container.

    Assigns each of ``num_rows`` global rows an owning shard (default:
    round-robin ``t % S``, occupancy within one row of even) and a local
    id in global-row order within the shard.  Both
    :meth:`ShardedTransformBank.from_dense` and the tiered-over-sharded
    store (``serving/tiering.ShardedTieredBankStore``) derive their
    global↔local remaps from THIS function, so a hotness snapshot or a
    publish addressed by global row id lands on the same (shard, local)
    coordinates whichever container serves it.

    Returns ``(shard_of, local_of, row_counts)``; local ids are assigned
    vectorized (publishes run under the control-plane lock, so an O(T)
    Python loop would serialize the fleet at large T).
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    assign = (np.arange(num_rows) % num_shards if shard_of is None
              else np.asarray(shard_of, np.int64).reshape(-1))
    if assign.shape[0] != num_rows:
        raise ValueError(
            f"shard_of has {assign.shape[0]} entries for {num_rows} rows")
    if assign.size and (assign.min() < 0 or assign.max() >= num_shards):
        raise ValueError("shard_of entries outside [0, num_shards)")
    counts = np.bincount(assign, minlength=num_shards).astype(np.int64)
    order = np.argsort(assign, kind="stable")
    starts = np.cumsum(counts) - counts
    local = np.empty(num_rows, np.int64)
    local[order] = np.arange(num_rows) - np.repeat(starts, counts)
    return assign, local, counts


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedTransformBank:
    """A :class:`TransformBank` row-partitioned over a mesh "tenants" axis.

    The dense bank stacks EVERY (tenant, predictor) row on every replica —
    the wall past ~10k tenants.  This container splits the rows over S
    shards: parameter arrays carry a leading shard axis ((S, Tl, K) /
    (S, Tl, N), ``Tl = max shard occupancy``) so ``shard_map`` placement
    over the "tenants" axis leaves each device holding ONLY its local rows
    (``per_shard_bytes`` ≈ dense/S).  ``shard_of``/``local_of`` are the
    host-side global↔local tenant-id remap the serving layer buckets
    requests with; occupancy may be uneven and shards may be empty (rows
    beyond ``row_counts[s]`` are inert identity padding — no request ever
    selects them).

    Like the dense bank, a sharded bank is immutable and generation-stamped:
    ``with_rows`` scatters refreshed T^Q tables ONLY into each row's owning
    shard and returns a NEW object under one bumped generation, so a
    calibration publish swaps every shard's sub-bank in the same single
    control-plane assignment — per-shard generations can never diverge.
    """

    betas: Array          # (S, Tl, K)
    weights: Array        # (S, Tl, K)
    src_quantiles: Array  # (S, Tl, N)
    ref_quantiles: Array  # (S, Tl, N)
    shard_of: np.ndarray  # (T,) owning shard per global bank row
    local_of: np.ndarray  # (T,) local row within the owning shard
    row_counts: np.ndarray  # (S,) occupied rows per shard
    generation: int = 0

    # ------------------------------------------------------------ geometry
    @property
    def num_shards(self) -> int:
        return int(self.betas.shape[0])

    @property
    def num_rows(self) -> int:
        return int(self.shard_of.shape[0])

    @property
    def rows_per_shard(self) -> int:
        return int(self.betas.shape[1])

    @property
    def num_experts(self) -> int:
        return int(self.betas.shape[-1])

    @property
    def num_quantiles(self) -> int:
        return int(self.src_quantiles.shape[-1])

    @property
    def per_shard_bytes(self) -> int:
        """Bank bytes RESIDENT on one shard (the 1/S residency headline)."""
        tl, k, n = self.rows_per_shard, self.num_experts, self.num_quantiles
        return tl * (2 * k + 2 * n) * 4

    def locate(self, tenant_idx) -> tuple[np.ndarray, np.ndarray]:
        """Global row ids -> (owning shard, local row) — the dispatch remap."""
        tid = np.asarray(tenant_idx, np.int64).reshape(-1)
        return self.shard_of[tid], self.local_of[tid]

    # --------------------------------------------------------- conversions
    @staticmethod
    def from_dense(bank: TransformBank, num_shards: int,
                   shard_of: np.ndarray | None = None, *,
                   sharding: Any = None) -> "ShardedTransformBank":
        """Partition a dense bank's rows over ``num_shards`` shards.

        ``shard_of`` (optional, (T,)) assigns each global row an owning
        shard — any assignment is legal, including empty shards.  Default is
        round-robin (``t % S``), which keeps occupancy within one row of
        even.  Local ids are assigned in global-row order within each shard;
        shards are padded to the max occupancy with identity rows
        (beta=1, weight=1, identity quantile table) that no request selects.
        ``sharding`` (the dispatcher's tenant-axis ``NamedSharding``) places
        each shard's rows on its own device; None leaves them on the
        default device.
        """
        t = bank.num_rows
        assign, local, counts = shard_rows(t, num_shards, shard_of)
        tl = max(int(counts.max()) if counts.size else 0, 1)
        k, n = bank.num_experts, bank.num_quantiles

        betas = np.ones((num_shards, tl, k), np.float32)
        weights = np.ones((num_shards, tl, k), np.float32)
        ident = np.linspace(0.0, 1.0, n, dtype=np.float32)
        src = np.broadcast_to(ident, (num_shards, tl, n)).copy()
        ref = src.copy()
        b_np = np.asarray(bank.betas)
        w_np = np.asarray(bank.weights)
        qs_np = np.asarray(bank.src_quantiles)
        qr_np = np.asarray(bank.ref_quantiles)
        betas[assign, local] = b_np
        weights[assign, local] = w_np
        src[assign, local] = qs_np
        ref[assign, local] = qr_np
        betas, weights, src, ref = (jax.device_put(a, sharding)
                                    for a in (betas, weights, src, ref))
        return ShardedTransformBank(
            betas=betas, weights=weights, src_quantiles=src,
            ref_quantiles=ref, shard_of=assign, local_of=local,
            row_counts=counts, generation=bank.generation)

    def shard_bank(self, shard: int) -> TransformBank:
        """The dense sub-bank one shard serves (its occupied local rows)."""
        c = int(self.row_counts[shard])
        c = max(c, 1)  # empty shard: expose one (inert) identity row
        return TransformBank(
            betas=self.betas[shard, :c], weights=self.weights[shard, :c],
            src_quantiles=self.src_quantiles[shard, :c],
            ref_quantiles=self.ref_quantiles[shard, :c],
            generation=self.generation)

    def to_dense(self) -> TransformBank:
        """Reassemble the global dense bank (parity/inspection path)."""
        sh = jnp.asarray(self.shard_of)
        lo = jnp.asarray(self.local_of)
        return TransformBank(
            betas=self.betas[sh, lo], weights=self.weights[sh, lo],
            src_quantiles=self.src_quantiles[sh, lo],
            ref_quantiles=self.ref_quantiles[sh, lo],
            generation=self.generation)

    # ------------------------------------------------------------- updates
    def with_rows(
        self,
        rows: Mapping[int, tuple[Array, Array]] | Mapping[int, "QuantileMap"],
        *,
        generation: int | None = None,
    ) -> "ShardedTransformBank":
        """Functional T^Q update addressed by GLOBAL row id.

        Each replacement table is scattered only into its row's owning
        shard ((shard, local) indices, one ``.at[].set`` per array); every
        other shard's rows are carried over untouched.  Semantics otherwise
        match :meth:`TransformBank.with_rows` (edge-padding of narrow
        tables, generation defaulting to current + 1).
        """
        if not rows:
            return self if generation is None else dataclasses.replace(
                self, generation=generation)
        n = self.num_quantiles
        s_idx, l_idx, srcs, refs = [], [], [], []
        for row, value in sorted(rows.items()):
            if not 0 <= row < self.num_rows:
                raise IndexError(f"row {row} outside bank of {self.num_rows}")
            src, ref = pad_quantile_tables(value, n, row=row)
            s_idx.append(int(self.shard_of[row]))
            l_idx.append(int(self.local_of[row]))
            srcs.append(src)
            refs.append(ref)
        s_idx = jnp.asarray(s_idx, jnp.int32)
        l_idx = jnp.asarray(l_idx, jnp.int32)
        return dataclasses.replace(
            self,
            src_quantiles=self.src_quantiles.at[s_idx, l_idx].set(
                jnp.stack(srcs)),
            ref_quantiles=self.ref_quantiles.at[s_idx, l_idx].set(
                jnp.stack(refs)),
            generation=self.generation + 1 if generation is None else generation,
        )


def banked_score_pipeline(
    expert_scores: Array,
    tenant_idx: Array,
    betas: Array,
    weights: Array,
    src_quantiles: Array,
    ref_quantiles: Array,
) -> Array:
    """Mixed-tenant Eq. 2: row ``i`` uses parameter row ``tenant_idx[i]``.

    ``expert_scores``: (..., K); ``tenant_idx``: (...) int; bank params are
    (T, K) / (T, N).  Pure-jnp reference — the oracle for the banked Pallas
    kernel.  Weights are normalized per row (so padded expert columns with
    weight 0 contribute nothing).
    """
    expert_scores = jnp.asarray(expert_scores)
    tenant_idx = jnp.asarray(tenant_idx, jnp.int32)
    b = jnp.take(betas, tenant_idx, axis=0)            # (..., K)
    w = jnp.take(weights, tenant_idx, axis=0)          # (..., K)
    qs = jnp.take(src_quantiles, tenant_idx, axis=0)   # (..., N)
    qr = jnp.take(ref_quantiles, tenant_idx, axis=0)   # (..., N)
    corrected = posterior_correction(expert_scores, b)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    agg = jnp.sum(corrected * w, axis=-1)              # (...)

    dtype = agg.dtype
    qs = qs.astype(dtype)
    qr = qr.astype(dtype)
    n = qs.shape[-1]
    i = jnp.clip(jnp.sum(agg[..., None] >= qs, axis=-1) - 1, 0, n - 2)
    q_s_i = jnp.take_along_axis(qs, i[..., None], axis=-1)[..., 0]
    q_s_n = jnp.take_along_axis(qs, i[..., None] + 1, axis=-1)[..., 0]
    q_r_i = jnp.take_along_axis(qr, i[..., None], axis=-1)[..., 0]
    q_r_n = jnp.take_along_axis(qr, i[..., None] + 1, axis=-1)[..., 0]
    denom = jnp.where(q_s_n - q_s_i > 0, q_s_n - q_s_i, jnp.asarray(1.0, dtype))
    out = q_r_i + (agg - q_s_i) * (q_r_n - q_r_i) / denom
    return jnp.clip(out, qr[..., 0], qr[..., -1])
