"""Fused on-device quantile tracking: score -> transform -> track, one dispatch.

The track stage (``MuseServer.track``) was the last serial host loop on the
data plane: every window synced its posterior-corrected aggregate back to
host (``np.asarray``) and then ran one numpy reservoir update per (tenant,
predictor) stream under the estimator lock.  This module moves the hot path
onto the device:

* :func:`fused_track_append` is ONE jitted program that computes the banked
  ``pre_quantile`` aggregate (the exact op sequence of
  :func:`repro.core.transforms._banked_pre_quantile` — it is inlined, so the
  two can never drift) and scatters each row into a per-stream device
  staging buffer.  No host transfer, no per-stream Python on the hot path.
* :class:`DeviceQuantileTracker` owns the staging buffers (control-plane
  state) and the bookkeeping that makes the deferred host materialization
  BITWISE identical to eager tracking, including RNG state.

Why vectorized segment ops instead of a Pallas grid: the scatter targets are
data-dependent (stream slot x pending offset), which maps naturally onto one
XLA scatter with host-planned unique indices, while the aggregate reuses the
already-fused banked math.  A Pallas kernel would re-implement the same
scatter without the bitwise-parity guarantee that inlining
``_banked_pre_quantile`` gives for free.

Exactness contract (why replay is bitwise, not approximate):

* Per-stream estimators are independent, and a
  :class:`~repro.core.quantiles.StreamingQuantileEstimator`'s state after a
  sequence of ``update`` calls depends only on the sample values and the
  UPDATE-CALL BOUNDARIES (the recent ring resets on >=capacity bulk writes
  and the PCG64 draws are consumed per overflow batch).  The eager path
  issues exactly one ``update`` per stream per window.
* The tracker therefore records, per stream, the cumulative sample count at
  every window boundary.  Draining replays ``update`` once per ORIGINAL
  window chunk, in arrival order, against the same host estimator class —
  reservoir, recent ring, pointers, seen counts and RNG state come out
  bit-for-bit equal to eager tracking (asserted by
  ``tests/test_device_tracking.py``).
* Scatter indices are ``slot * capacity + pending + within-window rank`` —
  unique by construction, so the scatter is deterministic and needs no
  device RNG (``unique_indices=True`` lets XLA skip the dedup path).
* Windows are padded to a power-of-two row count, so the set of compiled
  programs stays bounded (one per bucket, warmed by :meth:`warm`); pad rows
  scatter past the end of the plane and are dropped.

Host pulls happen ONLY at the calibration boundary: a stream spills when its
staging would overflow, and the calibration plane (Eq.-5 gating, snapshots,
fleet merge) calls :meth:`DeviceQuantileTracker.sync` before reading
estimators.  Thread-safety: the owner (``MuseServer``) serializes every
tracker call under its estimator lock; the tracker itself is not locked.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.transforms import _banked_pre_quantile

# chunks replayed at drain time must respect the estimator's documented
# per-update-call bound; windows are far smaller in practice (engine cap)
DEFAULT_STAGING = 4096


@functools.partial(jax.jit, donate_argnums=(0,))
def _fused_append(staging: jax.Array, flat_idx: jax.Array,
                  expert_scores: jax.Array, tenant_idx: jax.Array,
                  betas: jax.Array, weights: jax.Array) -> jax.Array:
    """score -> transform -> track in one XLA program.

    ``staging`` is the flat ``(slots * capacity,)`` f32 staging plane
    (donated: updated in place, the caller rebinds the result).  The
    aggregate is the inlined ``_banked_pre_quantile`` jaxpr — bitwise the
    value the eager host path would have pulled.  Out-of-range indices
    (bucket padding) are dropped."""
    agg = _banked_pre_quantile(expert_scores, tenant_idx, betas, weights)
    return staging.at[flat_idx].set(agg, mode="drop", unique_indices=True)


@functools.partial(jax.jit, donate_argnums=(0,))
def _staged_append(staging: jax.Array, flat_idx: jax.Array,
                   agg: jax.Array) -> jax.Array:
    """Scatter an already-computed aggregate (tiered stores compute
    ``pre_quantile`` against host-paged rows, so only the append fuses)."""
    return staging.at[flat_idx].set(
        jnp.asarray(agg, jnp.float32), mode="drop", unique_indices=True)


def _bucketed(flat_idx: np.ndarray, size: int, *rows: np.ndarray
              ) -> tuple[np.ndarray, ...]:
    """Pad a window to the next power-of-two row count: pad indices point
    past the ``size``-element plane (dropped by the scatter), pad rows
    repeat the last real row."""
    b = len(flat_idx)
    pad = (1 << max(b - 1, 0).bit_length()) - b
    if not pad:
        return (flat_idx, *rows)
    idx = np.concatenate([flat_idx, size + np.arange(pad, dtype=np.int64)])
    return (idx.astype(np.int32),
            *(np.concatenate([r, np.repeat(r[-1:], pad, axis=0)])
              for r in rows))


def _segment_plan(slots: np.ndarray) -> tuple[np.ndarray, np.ndarray,
                                              np.ndarray]:
    """Vectorized per-window segment bookkeeping over the row->slot vector.

    Returns ``(ranks, uniq_slots, incoming)``: each row's 0-based arrival
    rank within its stream, the unique slots present, and the per-unique-
    slot row counts.  Stable sort keeps arrival order inside a stream —
    the property the bitwise replay contract rests on."""
    b = len(slots)
    order = np.argsort(slots, kind="stable")
    sorted_slots = slots[order]
    new_seg = np.r_[True, sorted_slots[1:] != sorted_slots[:-1]]
    seg_start = np.flatnonzero(new_seg)
    ranks_sorted = np.arange(b, dtype=np.int64) - \
        np.repeat(seg_start, np.diff(np.r_[seg_start, b]))
    ranks = np.empty(b, dtype=np.int64)
    ranks[order] = ranks_sorted
    return ranks, sorted_slots[seg_start], np.diff(np.r_[seg_start, b])


class DeviceQuantileTracker:
    """Device staging plane for per-(tenant, predictor) quantile streams.

    ``apply(key, chunks)`` is the host-materialization callback: it must
    route each chunk list into the stream's estimator via one
    ``update`` call per chunk (see
    :meth:`~repro.core.quantiles.StreamingQuantileEstimator.apply_chunks`).
    The owner calls every method under one lock.
    """

    def __init__(self, apply: Callable[[tuple, list[np.ndarray]], None], *,
                 staging_capacity: int = DEFAULT_STAGING,
                 initial_slots: int = 64) -> None:
        if staging_capacity < 1:
            raise ValueError("staging_capacity must be >= 1")
        self.capacity = int(staging_capacity)
        self._apply = apply
        self._slots: dict[tuple, int] = {}        # stream key -> slot
        self._slot_key: dict[int, tuple] = {}
        self._free: list[int] = []
        self._num_slots = int(initial_slots)
        self._counts = np.zeros(self._num_slots, dtype=np.int64)
        # per-slot cumulative sample counts at each appended window's end —
        # the replay boundaries that make drain bitwise-equal to eager
        self._bounds: list[list[int]] = [[] for _ in range(self._num_slots)]
        self._staging = jnp.zeros((self._num_slots * self.capacity,),
                                  jnp.float32)
        # observability: spills (staging-full drains) and windows that fell
        # back to the eager host path because one stream outsized the plane
        self.spills = 0
        self.host_fallbacks = 0
        self.appends = 0

    # ------------------------------------------------------------- capacity
    def _alloc(self, key: tuple) -> int:
        if self._free:
            slot = self._free.pop()
        else:
            slot = len(self._slots)
            if slot >= self._num_slots:
                self._grow(slot + 1)
        self._slots[key] = slot
        self._slot_key[slot] = key
        return slot

    def _grow(self, needed: int) -> None:
        new_n = self._num_slots
        while new_n < needed:
            new_n *= 2   # doubling bounds recompiles to O(log streams)
        pad = (new_n - self._num_slots) * self.capacity
        self._staging = jnp.concatenate(
            [self._staging, jnp.zeros((pad,), jnp.float32)])
        self._counts = np.concatenate(
            [self._counts, np.zeros(new_n - self._num_slots, np.int64)])
        self._bounds.extend([] for _ in range(new_n - self._num_slots))
        self._num_slots = new_n

    # --------------------------------------------------------------- append
    def _plan(self, keys: list[tuple]) -> np.ndarray | None:
        """Spill-aware scatter plan for one window; None => host fallback.

        Updates counts/bounds as if the append already happened, so the
        caller MUST follow a non-None plan with the device scatter."""
        slots = np.empty(len(keys), dtype=np.int64)
        for j, key in enumerate(keys):
            s = self._slots.get(key)
            slots[j] = self._alloc(key) if s is None else s
        ranks, uniq, incoming = _segment_plan(slots)
        if int(incoming.max()) > self.capacity:
            # one stream's share of this window outsizes the whole staging
            # plane — drain its history first (order!), then let the caller
            # take the eager path for the entire window
            self.host_fallbacks += 1
            self._drain_slots(uniq)
            return None
        over = uniq[self._counts[uniq] + incoming > self.capacity]
        if len(over):
            self.spills += 1
            self._drain_slots(over)
        flat_idx = slots * self.capacity + self._counts[slots] + ranks
        self._counts[uniq] += incoming
        for s, inc in zip(uniq, incoming):
            self._bounds[s].append(int(self._counts[s]))
        self.appends += 1
        return flat_idx.astype(np.int32)

    def append_fused(self, keys: list[tuple], raws: np.ndarray,
                     tenant_idx: np.ndarray, bank: Any) -> bool:
        """Stage one window through the fused program (dense banks).

        Returns False when the window must take the eager host path (a
        single stream larger than the staging plane)."""
        if not keys:
            return True
        flat_idx = self._plan(keys)
        if flat_idx is None:
            return False
        flat_idx, raws, tenant_idx = _bucketed(
            flat_idx, self._staging.size, np.asarray(raws, np.float32),
            np.asarray(tenant_idx, np.int32))
        self._staging = _fused_append(
            self._staging, jnp.asarray(flat_idx), jnp.asarray(raws),
            jnp.asarray(tenant_idx), bank.betas, bank.weights)
        return True

    def append_agg(self, keys: list[tuple], agg: Any) -> bool:
        """Stage one window whose aggregate is already computed (tiered
        stores page ``pre_quantile`` through host rows)."""
        if not keys:
            return True
        flat_idx = self._plan(keys)
        if flat_idx is None:
            return False
        flat_idx, agg = _bucketed(flat_idx, self._staging.size,
                                  np.asarray(agg, np.float32))
        self._staging = _staged_append(
            self._staging, jnp.asarray(flat_idx), jnp.asarray(agg))
        return True

    def warm(self, rows: int, bank: Any = None) -> None:
        """Compile the append program for ``rows``-row windows against
        ``bank`` (the fused program) or for precomputed aggregates (None),
        on a scratch plane: no stream, count or staged sample changes."""
        size = self._staging.size
        k = 1 if bank is None else bank.betas.shape[-1]
        idx, raws, tid = _bucketed(
            (size + np.arange(rows)).astype(np.int32), size,  # all dropped
            np.zeros((rows, k), np.float32), np.zeros(rows, np.int32))
        scratch = jnp.zeros_like(self._staging)
        if bank is None:
            _staged_append(scratch, jnp.asarray(idx), jnp.asarray(raws[:, 0]))
        else:
            _fused_append(scratch, jnp.asarray(idx), jnp.asarray(raws),
                          jnp.asarray(tid), bank.betas, bank.weights)

    # ---------------------------------------------------------------- drain
    def _drain_slots(self, slots: Any) -> int:
        todo = [int(s) for s in slots if self._counts[s] > 0]
        if not todo:
            return 0
        host = np.asarray(self._staging)   # ONE device->host pull
        drained = 0
        for s in todo:
            n = int(self._counts[s])
            scores = host[s * self.capacity : s * self.capacity + n].copy()
            chunks = np.split(scores, self._bounds[s][:-1])
            self._apply(self._slot_key[s], chunks)
            self._counts[s] = 0
            self._bounds[s] = []
            drained += n
        return drained

    def sync(self) -> int:
        """Materialize every staged sample into its host estimator (the
        calibration plane's host-pull boundary).  Returns samples drained."""
        return self._drain_slots(np.flatnonzero(self._counts > 0))

    # ------------------------------------------------------------ ownership
    def pending(self, key: tuple) -> int:
        """Samples staged on device but not yet in the host estimator."""
        s = self._slots.get(key)
        return 0 if s is None else int(self._counts[s])

    def pending_total(self) -> int:
        return int(self._counts.sum())

    def drop_where(self, predicate: Callable[[tuple], bool]) -> int:
        """Discard streams (staged data included) whose key matches —
        decommission support: a dead predictor's staged samples must never
        materialize into a revived stream."""
        dead = [k for k in self._slots if predicate(k)]
        for key in dead:
            slot = self._slots.pop(key)
            del self._slot_key[slot]
            self._counts[slot] = 0
            self._bounds[slot] = []
            self._free.append(slot)
        return len(dead)
