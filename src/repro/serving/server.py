"""MuseServer: the scoring data plane (paper Fig. 1).

Request path:  intent -> routing (live + shadows) -> feature enrichment ->
expert models -> T^C -> A -> T^Q -> response; shadow scores go to the sink.

A mixed-tenant micro-batch is grouped by *model group* (the predictor's
expert-model set): one model executable call produces raw scores for the
whole group, and one tenant-indexed banked kernel dispatch
(:func:`repro.kernels.ops.score_pipeline_banked`) applies every predictor's
T^C/A/T^Q in a single ``pallas_call`` — no per-predictor Python loop.

The banked dispatch is split into three independently schedulable stages so
the async engine (``serving/engine.py``) can pipeline them across windows:

  * :meth:`MuseServer.run_models`       — expert-model execution (raw scores)
  * :meth:`MuseServer.apply_transforms` — ONE banked T^C/A/T^Q kernel call
  * :meth:`MuseServer.track`            — quantile-estimator reservoir updates

On a dense bank the first two stages meet on the device: ``run_models``
queues the window's banked kernel on the forward's output and fetches raw
and transformed scores in one transfer, and ``apply_transforms`` serves
those scores unless the bank it resolves is no longer the one they ran
under.

Each stage reads served state through a :class:`_ControlPlane` snapshot —
ONE attribute read yields a mutually consistent (predictors, banks,
generation) triple, because every control-plane operation (deploy,
decommission, calibration publish) swaps the whole plane in a single
reference assignment.  A stage that snapshotted the old plane finishes on
the old generation; the next stage pickup sees the complete new one — no
torn reads, even with a concurrent publish from another thread.

The server is the *data plane*; control-plane operations (deploying
predictors, publishing routing tables, triggering calibration refreshes) are
explicit methods invoked by the rollout controller — never by clients.

Sharded serving topology
------------------------

With ``ServerConfig(tenant_shards=S)`` the server serves every model-group
bank as a :class:`~repro.core.transforms.ShardedTransformBank` row-
partitioned over an S-way "tenants" mesh axis
(:func:`repro.launch.mesh.make_tenant_mesh`): each device holds ONLY its
tenant rows (~1/S of the dense bank).  ``apply_transforms`` then routes
through :class:`ShardedBankDispatcher` — rows of a window are bucketed by
owning shard on the host, every shard runs the banked Pallas kernel on its
LOCAL sub-bank inside one ``shard_map`` launch, and results gather back in
request order.  The per-row compute is the same kernel as the dense path,
so sharded and dense scores agree bitwise on f32.

Calibration publishes keep their atomicity across shards: the fleet refresh
fits candidates globally, and ``publish_quantile_maps`` rebuilds the dense
bank AND its per-shard sub-banks (scattering only into each row's owning
shard) inside the same single control-plane swap — one fleet-monotone
generation, never a torn per-shard mix.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import zlib
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.predictor import Predictor, PredictorSpec, deploy_predictor
from repro.core.quantiles import StreamingQuantileEstimator, required_sample_size
from repro.core.registry import ModelPool
from repro.core.routing import Intent, RoutingTable
from repro.core.transforms import (
    QuantileMap,
    ShardedTransformBank,
    TENANT_AXIS,
    TransformBank,
    banked_score_pipeline,
)
from repro.kernels import ops
from repro.kernels.quantile_track import DeviceQuantileTracker
from repro.serving.shadow import ShadowSink
from repro.serving.spans import dispatched, span, stamp, timed
from repro.serving.tiering import (
    HostBankStore,
    ShardedTieredBankStore,
    TieredBankStore,
    TieringConfig,
)
from repro.serving.types import (
    ScoringRequest,
    ScoringResponse,
    ShadowRecord,
    StaleGenerationError,
)

__all__ = [
    "FeatureStore", "MuseServer", "ServerConfig", "ShardedBankDispatcher",
    "StaleGenerationError",  # canonical home is serving/types.py
]


class FeatureStore:
    """Per-tenant derived-feature lookup (paper's 'Easy Feature Evolution').

    Models may require wider feature vectors than the client payload carries;
    the store supplies the model-specific derived features so new model
    versions deploy without client payload changes.
    """

    def __init__(self) -> None:
        self._store: dict[str, np.ndarray] = {}

    def put(self, tenant: str, derived: np.ndarray) -> None:
        self._store[tenant] = np.asarray(derived, np.float32)

    def enrich(self, intent: Intent, features: np.ndarray, target_dim: int
               ) -> np.ndarray:
        features = np.asarray(features, np.float32)
        if features.shape[-1] >= target_dim:
            return features[..., :target_dim]
        derived = self._store.get(intent.tenant)
        pad_width = target_dim - features.shape[-1]
        if derived is None:
            pad = np.zeros(features.shape[:-1] + (pad_width,), np.float32)
        else:
            reps = -(-pad_width // len(derived))
            pad = np.tile(derived, reps)[:pad_width]
            pad = np.broadcast_to(pad, features.shape[:-1] + (pad_width,))
        return np.concatenate([features, pad], axis=-1)


def stream_seed(key: tuple[str, str]) -> int:
    """Deterministic RNG seed for a (tenant, predictor) estimator stream.

    The old derivation hashed ``"/".join(key)`` unconditionally, which
    collided for ``("a/b", "c")`` vs ``("a", "b/c")`` — identical seeds
    mean identical reservoir acceptance sequences for supposedly
    independent streams.  The join IS injective when no component
    contains the separator (split on "/" inverts it), so that case keeps
    the legacy digest — existing deployments with ordinary tenant /
    predictor names don't have every stream's acceptance sequence
    reshuffled.  Ambiguous keys (a "/" inside a component) switch to
    length-prefix framing, led by a ``0xff`` byte: 0xff never occurs in
    UTF-8 output, so the framed namespace is disjoint from every legacy
    payload and the combined map is injective.  Checkpointed streams
    carry their full RNG state, so restores of old checkpoints stay
    exact across this change."""
    if any("/" in part for part in key):
        payload = b"\xff" + b"".join(
            len(part := p.encode()).to_bytes(4, "big") + part for p in key)
    else:
        payload = "/".join(key).encode()
    return zlib.crc32(payload)


@dataclasses.dataclass
class ServerConfig:
    track_quantiles: bool = True
    quantile_capacity: int = 131072
    # newest-samples ring per estimator stream: sized so a "recent"-window
    # refresh (RefreshPolicy.fit_window) sees roughly the drift timescale
    # of interest (e.g. ~a day of a tenant's traffic for the adversarial
    # campaign suite), not the all-time reservoir
    recent_capacity: int = 4096
    refresh_alert_rate: float = 0.01   # Eq. 5 gating for auto-refresh readiness
    refresh_rel_error: float = 0.2
    # fused tenant-indexed Pallas dispatch; False falls back to the pure-jnp
    # banked oracle (same semantics, no pallas_call)
    fused_kernel: bool = True
    # row-shard every model-group bank over an S-way "tenants" mesh axis
    # (1 = dense single-replica banks, the default).  Requires >= S jax
    # devices; see the module docstring's "Sharded serving topology".
    tenant_shards: int = 1
    # tiered tenant-bank store (serving/tiering.py): hot rows on device,
    # cold rows host-paged through a bounded victim cache, un-gated tenants
    # through the cold-start prior.  None = fully device-resident banks.
    # Composes with tenant_shards > 1: each shard of the tenant mesh gets
    # its own hot tier + victim cache over a per-shard host store
    # (ShardedTieredBankStore — bounded residency PER SHARD).
    tiering: TieringConfig | None = None
    # fused device tracking (kernels/quantile_track.py): the track stage
    # becomes one device dispatch (banked pre_quantile aggregate + scatter
    # into per-stream staging buffers); host estimators materialize only at
    # the calibration plane's pull boundary (Eq.-5 gating, checkpoint
    # snapshots, fleet merge).  Bitwise-identical estimator state to eager
    # host tracking — see the exactness contract in quantile_track.py.
    track_device: bool = False
    # per-stream device staging capacity (samples buffered between pulls);
    # a stream spills to host when its staging would overflow
    track_staging: int = 4096


def _shape_bucket(n: int) -> int:
    """Next power of two >= n: serving batches are padded up to a bucket so
    the set of XLA specializations stays bounded (one per bucket, not one
    per arbitrary window length — an adaptive engine window or a remainder
    flush would otherwise each pay a fresh compile on the hot path)."""
    b = 1
    while b < n:
        b *= 2
    return b


def _edge_pad(tenant_idx: np.ndarray, n: int) -> np.ndarray:
    """The tenant vector edge-padded to ``n`` rows, so an otherwise-uniform
    tail block keeps the banked kernel's scalar-prefetch fast path (the
    padded rows are sliced off)."""
    pad = n - len(tenant_idx)
    if not pad:
        return tenant_idx
    return np.concatenate([tenant_idx, np.full(pad, tenant_idx[-1], np.int32)])


@dataclasses.dataclass(frozen=True)
class _BankEntry:
    """A cached model-group bank pinned to the pipelines it was built from.

    ``pipelines`` is the identity witness: a ``publish_quantile_maps`` /
    redeploy replaces pipeline objects, so a stale entry fails the identity
    check and is rebuilt.  The bank itself carries the generation it was
    published under (see :class:`~repro.core.transforms.TransformBank`).
    ``sharded`` is the row-partitioned view served when
    ``ServerConfig.tenant_shards > 1`` — always built/updated alongside the
    dense bank in the SAME control-plane swap, so their generations agree.
    ``tiered`` is the hot/victim/prior tiered store served when
    ``ServerConfig.tiering`` is set; it replaces the dense bank entirely
    (``bank`` is None) so device residency stays bounded by the configured
    hot-tier capacity instead of the group's tenant count."""

    pipelines: tuple[Any, ...]
    bank: TransformBank | None
    sharded: ShardedTransformBank | None = None
    tiered: TieredBankStore | ShardedTieredBankStore | None = None


@dataclasses.dataclass(frozen=True)
class _Chain:
    """The banked kernel ``run_models`` queued on a window's forward
    output: the dense bank entry and the (unpadded) tenant vector it ran
    under, and its (bucket,) scores: on the device until ``run_models``
    fetches them with the raw scores, on the host after."""

    entry: _BankEntry
    tenant_idx: np.ndarray
    scores: Any


class _ChainedRaws(np.ndarray):
    """The (B, K) raw-score matrix ``run_models`` returns with its chained
    kernel attached (``chain``).  Read-only, and the attachment stays on
    this object alone: a view, a slice or a rebuilt array carries none,
    so ``apply_transforms`` dispatches its own kernel for it."""

    chain: _Chain | None = None

    def __array_finalize__(self, obj) -> None:
        self.chain = None


@dataclasses.dataclass(frozen=True)
class _TieredWindowBank:
    """The per-window 'bank' a tiered dispatch hands downstream stages.

    A :class:`TieredBankStore` is mutable (a publish can land right after a
    window scores), so ``apply_transforms`` wraps the store with the
    generation the window ACTUALLY scored under — ``build_responses`` reads
    a dispatch-time provenance stamp, exactly like the immutable dense
    bank's, and ``track`` fits estimators through the same rows the window
    served."""

    store: TieredBankStore | ShardedTieredBankStore
    generation: int

    def pre_quantile(self, expert_scores, tenant_idx):
        return self.store.pre_quantile(expert_scores, tenant_idx)


class ShardedBankDispatcher:
    """shard_map-driven banked dispatch over a tenant-sharded bank.

    The data-plane half of the sharded topology: a window's rows are
    bucketed by owning shard on the host (the bank's global→local remap),
    packed into one (S, Bs, K) batch padded per shard, and every shard runs
    the banked kernel against ONLY its local (Tl, ·) sub-bank inside a
    single ``shard_map`` launch over the "tenants" axis.  Results gather
    back into request order on the host.  Shard buckets pad their tenant
    vector edge-wise so a single-tenant bucket keeps the kernel's uniform-
    block fast path.

    Per-row compute is the identical kernel the dense path runs, and rows
    are computed independently of batch/bank shape — sharded scores match
    the dense path BITWISE on f32 (asserted by tests/test_sharded_bank.py).
    """

    def __init__(self, mesh: Any, *, fused: bool = True) -> None:
        self.mesh = mesh
        self.fused = fused
        # placement of every (S, ·) launch operand: row s on shard s's device
        self.sharding = NamedSharding(mesh, PartitionSpec(TENANT_AXIS))
        self._launch_fn: Any = None

    @property
    def devices(self) -> list:
        """Shard s's device, in shard order."""
        return list(self.mesh.devices.flat)

    def _launch(self) -> Any:
        if self._launch_fn is None:
            fused = self.fused

            def per_shard(sc, ti, b, w, qs, qr):
                impl = ops.score_pipeline_banked if fused \
                    else banked_score_pipeline
                return impl(sc[0], ti[0], b[0], w[0], qs[0], qr[0])[None]

            spec = PartitionSpec(TENANT_AXIS)
            self._launch_fn = jax.jit(jax.shard_map(
                per_shard, mesh=self.mesh, in_specs=(spec,) * 6,
                out_specs=spec, check_vma=False))
        return self._launch_fn

    def run_packed(self, packed: np.ndarray, pidx: np.ndarray,
                   betas: Any, weights: Any, src_quantiles: Any,
                   ref_quantiles: Any) -> np.ndarray:
        """One shard_map launch over an already-packed (S, Bs, ·) window
        against explicit (S, R, ·) per-shard parameter stacks.

        The raw launch entry: ``__call__`` buckets/packs a window against
        a :class:`ShardedTransformBank` and lands here; the tiered-over-
        sharded store (``serving/tiering.ShardedTieredBankStore``) packs
        slot-remapped buckets itself and calls this directly with its
        stacked per-shard tier views — same mesh, same compiled launch.
        """
        return np.asarray(self._launch()(
            jax.device_put(packed, self.sharding),
            jax.device_put(pidx, self.sharding), betas,
            weights, src_quantiles, ref_quantiles))

    def _run(self, packed: np.ndarray, pidx: np.ndarray,
             sbank: ShardedTransformBank) -> np.ndarray:
        """One shard_map launch over the packed (S, Bs, ·) window."""
        return self.run_packed(packed, pidx, sbank.betas, sbank.weights,
                               sbank.src_quantiles, sbank.ref_quantiles)

    @staticmethod
    def _pack_bucket(packed, pidx, shard, rows_raws, rows_idx, bs):
        """Place one shard's rows, edge-padding the tenant vector so a
        single-tenant bucket keeps the kernel's uniform-block fast path."""
        n = len(rows_idx)
        packed[shard, :n] = rows_raws
        pidx[shard, :n] = rows_idx
        if n and n < bs:
            pidx[shard, n:] = pidx[shard, n - 1]

    def __call__(self, raws: np.ndarray, tenant_idx: np.ndarray,
                 sbank: ShardedTransformBank) -> np.ndarray:
        raws = np.asarray(raws, np.float32)
        shard_ids, local_ids = sbank.locate(tenant_idx)
        s = sbank.num_shards
        if s == 1:
            # single-shard degenerate case: skip the bucketing entirely
            # (no argsort, no fancy-index gather) so S=1 costs the same as
            # the dense path — the bench's no-regression bar
            b = len(local_ids)
            bs = _shape_bucket(b) if b else 1
            packed = np.zeros((1, bs, raws.shape[-1]), np.float32)
            pidx = np.zeros((1, bs), np.int32)
            self._pack_bucket(packed, pidx, 0, raws, local_ids, bs)
            return self._run(packed, pidx, sbank)[0, :b]
        counts = np.bincount(shard_ids, minlength=s)
        bs = _shape_bucket(int(counts.max())) if counts.max() else 1
        order = np.argsort(shard_ids, kind="stable")
        packed = np.zeros((s, bs, raws.shape[-1]), np.float32)
        pidx = np.zeros((s, bs), np.int32)
        buckets: list[np.ndarray] = []
        start = 0
        for shard in range(s):
            rows = order[start:start + counts[shard]]
            start += counts[shard]
            buckets.append(rows)
            if len(rows):
                self._pack_bucket(packed, pidx, shard, raws[rows],
                                  local_ids[rows], bs)
        out = self._run(packed, pidx, sbank)
        result = np.empty(len(shard_ids), np.float32)
        for shard, rows in enumerate(buckets):
            result[rows] = out[shard, :len(rows)]
        return result


@dataclasses.dataclass(frozen=True)
class _ControlPlane:
    """One immutable view of everything a dispatch stage reads.

    ``predictors`` and ``banks`` are plain dicts, but the PLANE object is
    what gets swapped: every control-plane mutation builds fresh dicts and
    replaces ``MuseServer._plane`` in a single reference assignment, so a
    stage that reads ``server.plane`` once can never observe predictors of
    one generation with banks of another.  ``banks`` doubles as the lazy
    bank-build cache; inserting a missing entry is idempotent and therefore
    safe to do from a dispatch stage (a concurrently swapped-out plane just
    drops the cached entry — never serves stale parameters).
    """

    predictors: dict[str, Predictor]
    banks: dict[tuple[str, ...], _BankEntry]
    generation: int


class MuseServer:
    def __init__(self, routing: RoutingTable,
                 config: ServerConfig | None = None) -> None:
        self.pool = ModelPool()
        self.routing = routing
        self.sink = ShadowSink()
        self.features = FeatureStore()
        self.config = config or ServerConfig()
        # per (tenant, predictor) streaming estimators for calibration refresh
        self._estimators: dict[tuple[str, str], StreamingQuantileEstimator] = {}
        # estimator MUTATION (track stage) vs whole-state SNAPSHOT
        # (save_estimators) must not interleave: a checkpoint written while
        # an update is mid-flight would pair arrays with meta (seen counts,
        # ring pointer, RNG state) from different moments — a torn restore
        self._estimator_lock = threading.Lock()
        # fused device tracking: staged aggregates live in device buffers
        # owned by this control plane; every tracker call (append on the
        # track stage, sync at calibration pulls) runs under the estimator
        # lock, which is what serializes staging against materialization
        self._tracker: DeviceQuantileTracker | None = None
        if self.config.track_quantiles and self.config.track_device:
            self._tracker = DeviceQuantileTracker(
                self._apply_tracked,
                staging_capacity=self.config.track_staging)
        # THE served control-plane state: swapped wholesale on every deploy /
        # decommission / calibration publish (never mutated across a publish).
        # A dispatch stage snapshots it once, so an in-flight window finishes
        # on the old generation and the next stage sees the new one — no
        # torn reads.
        self._plane = _ControlPlane(predictors={}, banks={}, generation=0)
        # sharded topology: one mesh + dispatcher per server when configured.
        # With tiering ALSO set, the dispatcher serves the composed
        # tiered-over-sharded stores (per-shard hot tiers, one shard_map
        # launch per window) instead of fully-resident sharded banks.
        self._sharded_dispatch: ShardedBankDispatcher | None = None
        if self.config.tenant_shards > 1:
            from repro.launch.mesh import make_tenant_mesh
            self._sharded_dispatch = ShardedBankDispatcher(
                make_tenant_mesh(self.config.tenant_shards),
                fused=self.config.fused_kernel)
        # tiered topology: stateful stores OUTSIDE the plane (hotness, seen
        # counts and victim-cache residency survive plane swaps); the plane's
        # bank entries hold references, _tier_lock guards the dict itself
        self._tiered_stores: dict[
            tuple[str, ...], TieredBankStore | ShardedTieredBankStore] = {}
        self._tier_lock = threading.Lock()
        # predictors routed through the cold-start prior until their stream
        # re-passes the Eq.-5 gate (applied to stores built later, too)
        self._cold_names: set[str] = set()
        self.metrics: dict[str, float] = {
            "requests": 0, "kernel_dispatches": 0,
            "model_group_calls": 0, "model_calls": 0, "bank_generation": 0,
            "shard_dispatches": 0, "tier_dispatches": 0,
            # dense windows whose kernel run_models queued behind the
            # forward and apply_transforms served, and those whose chained
            # kernel it dropped for the bank a publish brought meanwhile
            "kernels_chained": 0, "kernels_redispatched": 0,
            # uniform-block fast-path coverage of the fused banked kernel:
            # blocks whose rows all share one tenant skip the one-hot gather
            # matmuls (see kernels/score_pipeline.py).  uniform/total over
            # all dense fused dispatches = the serving-side skip rate.
            "skip_blocks_uniform": 0, "skip_blocks_total": 0,
            # windows staged by the fused device tracker (vs eager host
            # fallbacks; spills/fallbacks also count on the tracker itself)
            "track_staged_windows": 0}
        # dict `+=` is load/add/store — racy once the engine runs stages on
        # several threads (e.g. two model-group lanes); serialize the bumps
        self._metrics_lock = threading.Lock()
        # control-plane mutations are read-modify-writes of _plane; two
        # concurrent mutators (e.g. deploy on the main thread vs a refresh
        # publish on the engine's track thread) must not lose each other's
        # update, so every mutator holds this lock across its RMW.  Dispatch
        # stages never take it — they only snapshot the reference.
        self._control_lock = threading.Lock()

    def bump_metric(self, key: str, n: float = 1) -> None:
        with self._metrics_lock:
            self.metrics[key] += n

    # ------------------------------------------------------------ plane views
    @property
    def plane(self) -> _ControlPlane:
        """The current control-plane snapshot (ONE consistent read)."""
        return self._plane

    @property
    def predictors(self) -> dict[str, Predictor]:
        return self._plane.predictors

    @property
    def _banks(self) -> dict[tuple[str, ...], _BankEntry]:
        return self._plane.banks

    @property
    def bank_generation(self) -> int:
        """Monotone counter of atomic calibration publishes."""
        return self._plane.generation

    # ------------------------------------------------------------------ control
    def deploy(self, spec: PredictorSpec,
               model_factories: Mapping[str, Callable[[], Any]],
               model_costs: Mapping[str, float] | None = None) -> Predictor:
        pred = deploy_predictor(spec, self.pool, model_factories, model_costs)
        with self._control_lock:
            plane = self._plane
            # an in-place redeploy changes served parameters under an
            # existing name, so it must bump the generation: otherwise two
            # responses scored before/after it would carry the same
            # ``bank_generation`` stamp for different T^C/A/T^Q.  (Cached
            # banks pinned to the dead pipeline fail the identity check and
            # rebuild lazily.)  First-time deploys leave the counter alone.
            gen = plane.generation + (1 if spec.name in plane.predictors
                                      else 0)
            predictors = dict(plane.predictors)
            predictors[spec.name] = pred
            # a model group's bank holds every predictor of the group, so
            # the group's cached banks no longer match its row set
            banks = {k: v for k, v in dict(plane.banks).items()
                     if plane.predictors[k[0]].model_names
                     != pred.model_names}
            self._plane = dataclasses.replace(
                plane, predictors=predictors, banks=banks, generation=gen)
            self.metrics["bank_generation"] = gen
        return pred

    def decommission(self, name: str) -> None:
        with self._control_lock:
            plane = self._plane
            predictors = dict(plane.predictors)
            pred = predictors.pop(name)
            # drop cached banks referencing the dead predictor's pipeline.
            # dict() first: a concurrent dispatch stage may lazily insert a
            # cache entry mid-iteration (the copy itself is GIL-atomic).
            # Generation bumps so a later deploy under the same name cannot
            # serve different parameters under an already-used stamp.
            banks = {k: v for k, v in dict(plane.banks).items()
                     if name not in k}
            gen = plane.generation + 1
            self._plane = dataclasses.replace(plane, predictors=predictors,
                                              banks=banks, generation=gen)
            self.metrics["bank_generation"] = gen
        pred.release(self.pool)
        # and its estimator streams: a future predictor redeployed under the
        # same name has a different score distribution — refitting T^Q from
        # the dead model's stream would publish a miscalibrated map.  Staged
        # device samples die with the streams (drop_where), so a redeploy
        # under the same name can never materialize the dead model's scores.
        with self._estimator_lock:
            if self._tracker is not None:
                self._tracker.drop_where(lambda k: k[1] == name)
            self._estimators = {k: v for k, v in self._estimators.items()
                                if k[1] != name}
        # tiered stores holding the dead predictor's host row die with it
        # (row indices are positions in the names tuple — unpatchable)
        with self._tier_lock:
            self._tiered_stores = {k: v for k, v in self._tiered_stores.items()
                                   if name not in k}
        self._cold_names.discard(name)

    def publish_routing(self, table: RoutingTable) -> None:
        """Atomic routing swap — the transparent model switching primitive."""
        missing = [n for n in table.referenced_predictors()
                   if n not in self.predictors]
        if missing:
            raise KeyError(f"routing references undeployed predictors: {missing}")
        self.routing = table

    def swap_transformation(self, predictor_name: str, qm: QuantileMap) -> None:
        """T^Q_v0 -> T^Q_v1 without touching models (Sec. 3.1)."""
        self.publish_quantile_maps({predictor_name: qm})

    def publish_quantile_maps(self, updates: Mapping[str, QuantileMap],
                              *, generation: int | None = None) -> int:
        """Atomically publish refreshed T^Q maps for MANY predictors at once.

        The fleet-wide calibration refresh (Sec. 3.1, `serving/calibration.py`)
        lands here: every updated predictor pipeline AND every affected
        model-group bank is rebuilt first, then the whole control plane is
        swapped in one reference assignment under a bumped generation.  A
        dispatch stage that already snapshotted the old plane finishes on the
        old parameters; the next stage sees the complete new generation —
        a batch can never mix rows from two calibration versions.

        ``generation`` is the fleet fencing hook: when given (a fleet-stamped
        broadcast), the publish lands under exactly that generation and is
        REJECTED with :class:`StaleGenerationError` unless it is strictly
        newer than the replica's current one — a late ack from a superseded
        fleet pass can never roll transformations backwards.  A fenced
        publish also re-stamps every cached bank (touched or not) to the
        fleet generation, so response provenance stamps are fleet-monotone,
        and an EMPTY fenced publish fast-forwards a lagging replica (e.g. a
        freshly surged one) to the fleet generation without changing maps.

        Returns the new bank generation.
        """
        with self._control_lock, span("muse.publish"):
            return self._publish_quantile_maps_locked(updates, generation)

    def _publish_quantile_maps_locked(self, updates: Mapping[str, QuantileMap],
                                      generation: int | None = None) -> int:
        plane = self._plane
        missing = [n for n in updates if n not in plane.predictors]
        if missing:
            raise KeyError(f"unknown predictors: {missing}")
        if generation is None:
            if not updates:
                return plane.generation
            gen = plane.generation + 1
        else:
            # generation fencing: only strictly-forward fleet publishes land
            if generation <= plane.generation:
                raise StaleGenerationError(generation, plane.generation)
            gen = generation

        new_predictors = dict(plane.predictors)
        for name, qm in updates.items():
            pred = new_predictors[name]
            new_predictors[name] = pred.with_updated_pipeline(
                pred.pipeline.with_quantile_map(qm))

        with span("muse.publish.bank"):
            new_banks = self._rebuild_banks(plane, updates, new_predictors,
                                            gen, generation)

        # the publish point: ONE whole-plane swap, never in-place edits
        self._plane = _ControlPlane(new_predictors, new_banks, gen)
        self.metrics["bank_generation"] = gen
        return gen

    def _rebuild_banks(self, plane: _ControlPlane,
                       updates: Mapping[str, QuantileMap],
                       new_predictors: dict[str, Predictor], gen: int,
                       generation: int | None
                       ) -> dict[tuple[str, ...], _BankEntry]:
        """The publish's banks at generation ``gen``: refreshed rows
        scattered into each cached bank (or the bank rebuilt), untouched
        banks kept or re-stamped for a fenced publish."""
        new_banks: dict[tuple[str, ...], _BankEntry] = {}
        # dict() first: a dispatch stage on another thread may lazily insert
        # a bank-cache entry mid-iteration (the copy itself is GIL-atomic)
        for key, entry in dict(plane.banks).items():
            touched = {i: updates[n] for i, n in enumerate(key) if n in updates}
            if entry.tiered is not None:
                store = entry.tiered
                entry_fresh = len(entry.pipelines) == len(key) and all(
                    ep is plane.predictors[n].pipeline
                    for ep, n in zip(entry.pipelines, key))
                if not entry_fresh:
                    # host rows came from a dead pipeline — drop the entry;
                    # the next dispatch rebuilds the store from the live
                    # pipelines (re-adopting its hotness state)
                    continue
                try:
                    if touched:
                        # publish into BOTH tiers in ONE locked store op:
                        # host rows rewritten + every device-resident copy
                        # (hot or victim) scattered under the new generation
                        store.apply_updates(touched, generation=gen)
                    elif generation is not None:
                        # fenced publish: fast-forward untouched stores so
                        # later provenance stamps stay fleet-monotone
                        store.apply_updates({}, generation=gen)
                except ValueError:
                    continue  # a table wider than the store: rebuild lazily
                pipelines = tuple(new_predictors[n].pipeline for n in key)
                store.source_pipelines = pipelines
                new_banks[key] = _BankEntry(pipelines, None, tiered=store)
                continue
            if not touched:
                if generation is None:
                    new_banks[key] = entry
                else:
                    # fenced publish: even untouched banks re-stamp to the
                    # fleet generation, so every response served after the
                    # ack carries a fleet-monotone provenance stamp
                    new_banks[key] = _BankEntry(
                        entry.pipelines,
                        entry.bank.with_rows({}, generation=gen),
                        None if entry.sharded is None
                        else entry.sharded.with_rows({}, generation=gen))
                continue
            pipelines = tuple(new_predictors[n].pipeline for n in key)
            # the with_rows fast path (scatter only the refreshed T^Q rows)
            # is sound only if the cached bank was built from the predictors'
            # CURRENT pipelines; a predictor redeployed in place leaves a
            # stale entry whose other rows carry the dead pipeline's T^C/A —
            # patching and re-pinning it would serve stale parameters forever
            entry_fresh = len(entry.pipelines) == len(key) and all(
                ep is plane.predictors[n].pipeline
                for ep, n in zip(entry.pipelines, key))
            bank = sharded = None
            if entry_fresh:
                try:
                    bank = entry.bank.with_rows(touched, generation=gen)
                    # the sharded sub-banks take the SAME refreshed rows,
                    # scattered into their owning shards, under the SAME
                    # generation — published in the one plane swap below
                    if entry.sharded is not None:
                        sharded = entry.sharded.with_rows(
                            touched, generation=gen)
                except ValueError:
                    bank = sharded = None  # a table wider than the bank
            if bank is None:
                bank = TransformBank.from_params(
                    [(p.betas, p.weights, p.src_quantiles, p.ref_quantiles)
                     for p in pipelines], generation=gen)
            if sharded is None and self._sharded_dispatch is not None:
                sharded = ShardedTransformBank.from_dense(
                    bank, self.config.tenant_shards,
                    sharding=self._sharded_dispatch.sharding)
            new_banks[key] = _BankEntry(pipelines, bank, sharded)
        return new_banks

    # ------------------------------------------------------------------- data
    def _model_dim(self, pred: Predictor) -> int:
        dims = [h.metadata.get("feature_dim") for h in pred._handles]
        dims = [d for d in dims if d]
        return max(dims) if dims else 0

    def batch_key(self, intent: Intent) -> str:
        """Micro-batching key: the resolved predictor's model group.

        Requests from different tenants/predictors that share the same
        expert-model set batch together — one executable call plus one
        banked kernel dispatch serves the whole window."""
        return self.group_key(self.routing.resolve(intent))

    @staticmethod
    def bank_names(pred_names, plane: _ControlPlane) -> tuple[str, ...]:
        """A window's bank key: every deployed predictor of its model
        group, sorted.  One bank (one kernel shape) serves the group
        whatever subset of its predictors a window carries."""
        group = plane.predictors[pred_names[0]].model_names
        return tuple(sorted(n for n, p in plane.predictors.items()
                            if p.model_names == group))

    def group_key(self, resolution) -> str:
        """``batch_key`` for an already-resolved intent — the async engine
        resolves once at submit and derives the key from the resolution
        (no double resolve), through this one source of truth."""
        return "+".join(self.predictors[resolution.live].model_names)

    def build_responses(self, requests, idxs: list[int],
                        pred_names: list[str], scores: np.ndarray,
                        raws: np.ndarray, bank: TransformBank,
                        routing_version: str, latency_ms: float,
                        window: int = -1) -> list[ScoringResponse]:
        """Assemble one window's responses (shared by sync + async drivers;
        ``tolist`` conversions are C-speed).  Row ``j`` answers request
        ``requests[idxs[j]]``; ``window`` is the engine window's ``seq``."""
        score_list = scores.tolist()
        raw_rows = np.atleast_2d(raws).tolist()
        return [
            ScoringResponse(
                request_id=requests[i].request_id,
                score=score_list[j],
                predictor=pred_names[j],
                routing_version=routing_version,
                latency_ms=latency_ms,
                raw_scores=tuple(raw_rows[j]),
                bank_generation=bank.generation,
                window=window,
            )
            for j, i in enumerate(idxs)
        ]

    def write_shadow_records(self, requests, idxs: list[int],
                             shadow_names: list[str], scores: np.ndarray,
                             raws: np.ndarray, routing_version: str) -> None:
        """Sink one shadow window's records (shared by sync + async)."""
        score_list = scores.tolist()
        raw_rows = np.atleast_2d(raws).tolist()
        for j, i in enumerate(idxs):
            self.sink.write(ShadowRecord(
                request_id=requests[i].request_id,
                tenant=requests[i].intent.tenant,
                predictor=shadow_names[j],
                score=score_list[j],
                raw_scores=tuple(raw_rows[j]),
                routing_version=routing_version,
            ))

    def _bank_for(self, names: tuple[str, ...],
                  plane: _ControlPlane | None = None) -> _BankEntry:
        """Build (or fetch) the stacked transform bank for these predictors.

        Cache entries pin the source pipelines; a ``publish_quantile_maps`` /
        redeploy replaces the pipeline object, failing the identity check
        and rebuilding the bank — banks never serve stale parameters.
        ``plane`` is the stage-time snapshot; lookups go through it so a
        concurrent publish can't produce a torn read.  Under a sharded
        topology the entry carries the row-partitioned sub-banks too (built
        in the same insertion, same generation)."""
        plane = self._plane if plane is None else plane
        pipelines = tuple(plane.predictors[n].pipeline for n in names)
        cached = plane.banks.get(names)
        if cached is not None and len(cached.pipelines) == len(pipelines) \
                and all(a is b for a, b in zip(cached.pipelines, pipelines)):
            return cached
        if self.config.tiering is not None:
            entry = _BankEntry(pipelines, None,
                               tiered=self._tiered_store_for(names, pipelines))
            plane.banks[names] = entry
            return entry
        bank = TransformBank.from_params(
            [(p.betas, p.weights, p.src_quantiles, p.ref_quantiles)
             for p in pipelines], generation=plane.generation)
        sharded = None
        if self._sharded_dispatch is not None:
            sharded = ShardedTransformBank.from_dense(
                bank, self.config.tenant_shards,
                sharding=self._sharded_dispatch.sharding)
        entry = _BankEntry(pipelines, bank, sharded)
        plane.banks[names] = entry
        return entry

    def _tiered_store_for(
            self, names: tuple[str, ...], pipelines: tuple[Any, ...]
    ) -> TieredBankStore | ShardedTieredBankStore:
        """Fetch (or build) the stateful tiered store for a model group.

        Stores live OUTSIDE the control plane so hotness/admission state
        survives plane swaps; ``source_pipelines`` is the same identity
        witness the bank cache uses, so a redeploy-stale store is rebuilt
        from the live pipelines here — adopting the old store's hotness so
        the hot set carries over.  Under a sharded topology the store is
        the composed :class:`ShardedTieredBankStore` (per-shard hot tiers
        over per-shard host slices, dispatched through this server's
        mesh dispatcher); its global-indexed hotness snapshot lets the
        adoption below cross topologies too."""
        with self._tier_lock:
            store = self._tiered_stores.get(names)
            if store is not None \
                    and store.source_pipelines is not None \
                    and len(store.source_pipelines) == len(pipelines) \
                    and all(a is b for a, b in
                            zip(store.source_pipelines, pipelines)):
                return store
            host = HostBankStore.from_rows(
                [(p.betas, p.weights, p.src_quantiles, p.ref_quantiles)
                 for p in pipelines])
            if self._sharded_dispatch is not None:
                fresh: TieredBankStore | ShardedTieredBankStore = \
                    ShardedTieredBankStore(
                        host, self.config.tenant_shards, self.config.tiering,
                        dispatcher=self._sharded_dispatch,
                        generation=self._plane.generation)
            else:
                fresh = TieredBankStore(host, self.config.tiering,
                                        generation=self._plane.generation)
            fresh.source_pipelines = pipelines
            if store is not None:
                fresh.adopt_hotness(store.hotness_snapshot())
            cold = [i for i, n in enumerate(names) if n in self._cold_names]
            if cold:
                fresh.mark_cold(cold)
            fresh.rebalance()
            self._tiered_stores[names] = fresh
            return fresh

    def score(self, request: ScoringRequest) -> ScoringResponse:
        return self.score_batch([request])[0]

    # ----------------------------------------------------- dispatch stages
    def run_models(self, requests: list[ScoringRequest], idxs: list[int],
                   pred_names: list[str],
                   raw_cache: dict[tuple[tuple[str, ...], int], np.ndarray]
                   | None = None,
                   plane: _ControlPlane | None = None) -> np.ndarray:
        """Stage 1 of a banked dispatch: execute the window's expert models.

        One model executable call produces raw scores for the whole
        (possibly multi-predictor) window; ``pred_names[j]`` is the predictor
        for row ``j``.  ``raw_cache`` carries (model group, request index)
        -> raw-score rows across dispatches of one batch, so live and shadow
        windows sharing a model group run the experts once (shadow dedup).
        Returns the (B, K) raw-score matrix.

        When every row is fresh (no ``raw_cache`` hit) and the stage-time
        ``plane`` gives the group a dense bank, the window's banked kernel
        is queued on the forward's device output right behind the forward,
        and raws and scores come back in one fetch.  The matrix then
        carries the scores (:class:`_ChainedRaws`), which
        ``apply_transforms`` takes in place of a dispatch of its own.
        """
        plane = self._plane if plane is None else plane
        pred0 = plane.predictors[pred_names[0]]
        group = pred0.model_names
        dim = self._model_dim(pred0) or len(requests[idxs[0]].features)
        rows: list[np.ndarray | None] = [None] * len(idxs)
        fresh = list(range(len(idxs)))
        if raw_cache is not None:
            fresh = []
            for j, i in enumerate(idxs):
                hit = raw_cache.get((group, i))
                if hit is None:
                    fresh.append(j)
                else:
                    rows[j] = hit
        if fresh:
            with span("muse.models.features"):
                feats = self._window_features(requests, idxs, fresh, dim)
                pad = _shape_bucket(len(fresh)) - len(fresh)
                stamp("model_bucket", len(fresh) + pad)
                if pad:  # bucketed batch shape: no per-length recompiles
                    feats = np.concatenate(
                        [feats, np.zeros((pad,) + feats.shape[1:],
                                         np.float32)])
            with span("muse.models.forward"):
                out = pred0.raw_scores(feats)
                chain = self._chain_kernel(out, pred_names, plane) \
                    if len(fresh) == len(idxs) else None
            with timed("muse.models.fetch", "model_fetch_ms"):
                if chain is None:
                    computed = np.asarray(out)[:len(fresh)]
                else:
                    host, scores = jax.device_get((out, chain.scores))
                    computed = host[:len(fresh)]
                    chain = dataclasses.replace(chain, scores=scores)
            with self._metrics_lock:
                self.metrics["model_group_calls"] += 1
                self.metrics["model_calls"] += len(group)
            for r, j in enumerate(fresh):
                rows[j] = computed[r]
                if raw_cache is not None:
                    raw_cache[(group, idxs[j])] = computed[r]
            if chain is not None:
                raws = np.stack(rows).view(_ChainedRaws)
                raws.flags.writeable = False  # the scores are of these rows
                raws.chain = chain
                return raws
        return np.stack(rows)                                # (B, K)

    def _chain_kernel(self, out, pred_names: list[str],
                      plane: _ControlPlane) -> _Chain | None:
        """Queue the window's banked kernel on its forward's device output
        ``out`` (bucket, K), against the dense bank ``plane`` resolves;
        None under a tiered or sharded topology.  The returned chain holds
        the device scores, not yet fetched."""
        if self.config.tiering is not None \
                or self._sharded_dispatch is not None:
            return None
        names = self.bank_names(pred_names, plane)
        entry = self._bank_for(names, plane)
        tenant_idx = self._tenant_vector(names, pred_names)
        scores = self._dense_kernel(
            entry.bank, jnp.asarray(out, jnp.float32),
            _edge_pad(tenant_idx, out.shape[0]))
        return _Chain(entry, tenant_idx, scores)

    def _window_features(self, requests, idxs: list[int], fresh: list[int],
                         dim: int) -> np.ndarray:
        """Assemble the (len(fresh), dim) model-input matrix.

        Fast path: when every row already carries >= dim features of the
        right dtype, ONE stack+slice replaces the per-row enrich calls —
        the per-row Python otherwise dominates the model stage under the
        async engine (GIL contention with the other stage threads).
        """
        try:
            feats = np.stack([requests[idxs[j]].features for j in fresh])
            if feats.dtype == np.float32 and feats.ndim == 2 \
                    and feats.shape[1] >= dim:
                return feats[:, :dim]
        except ValueError:
            pass  # ragged rows: fall through to per-row enrichment
        return np.stack([
            self.features.enrich(requests[idxs[j]].intent,
                                 requests[idxs[j]].features, dim)
            for j in fresh
        ])

    def apply_transforms(self, raws: np.ndarray, pred_names: list[str],
                         plane: _ControlPlane | None = None
                         ) -> tuple[np.ndarray, Any, np.ndarray]:
        """Stage 2: the whole window through ONE banked T^C/A/T^Q kernel call.

        The bank is resolved from the stage-time ``plane`` snapshot — a
        calibration publish landing between stage 1 and stage 2 is picked up
        here wholesale (raw expert scores are generation-independent), and
        every row of the window scores under exactly one bank generation.
        Returns (scores, bank, tenant_idx); the bank's ``generation`` is the
        window's provenance stamp.  On every topology, once the kernel call
        returns and before the result is fetched, ``spans.dispatched()``
        makes the ``on_dispatch`` call the engine bound to the window.

        ``raws`` that ``run_models`` returned with a chained kernel are
        served from its scores when this stage resolves the very bank entry
        and tenant rows the chain ran under; otherwise (a publish since
        stage 1, or an array without the chain) the kernel is dispatched
        here.  The window record's ``kernel_chained`` stamp says which.
        """
        plane = self._plane if plane is None else plane
        with span("muse.transforms.bank"):
            bank_names = self.bank_names(pred_names, plane)  # cache key
            entry = self._bank_for(bank_names, plane)
            tenant_idx = self._tenant_vector(bank_names, pred_names)
        chain = getattr(raws, "chain", None)
        if chain is not None and (
                chain.entry is not entry
                or not np.array_equal(chain.tenant_idx, tenant_idx)):
            # the chained kernel ran under a bank this stage no longer serves
            self.bump_metric("kernels_redispatched")
            chain = None
        stamp("kernel_chained", int(chain is not None))
        if entry.tiered is not None:
            # tiered topology: slot-remapped banked dispatch against the
            # bounded device view; cold rows stage through the victim cache
            # (normally prefetched by the engine before this stage runs)
            with span("muse.transforms.kernel"):
                scores, gen = entry.tiered.dispatch(raws, tenant_idx)
            dispatched()
            self.bump_metric("kernel_dispatches")
            self.bump_metric("tier_dispatches")
            if isinstance(entry.tiered, ShardedTieredBankStore):
                self.bump_metric("shard_dispatches")
            return scores, _TieredWindowBank(entry.tiered, gen), tenant_idx
        bank = entry.bank
        b = len(tenant_idx)
        if entry.sharded is not None and self._sharded_dispatch is not None:
            # sharded topology: bucket by owning shard, one shard_map launch
            # of the banked kernel per window (the dispatcher pads per
            # shard, so no outer shape-bucket pad is needed here)
            with span("muse.transforms.kernel"):
                scores = self._sharded_dispatch(raws, tenant_idx,
                                                entry.sharded)
            dispatched()
            self.bump_metric("kernel_dispatches")
            self.bump_metric("shard_dispatches")
            return scores, bank, tenant_idx
        with span("muse.transforms.kernel"):
            if chain is not None:
                scores = chain.scores    # queued and fetched in stage 1
            else:
                pad = _shape_bucket(b) - b
                kraws = raws
                if pad:  # bucketed kernel shape, same reasoning as run_models
                    kraws = np.concatenate(
                        [raws, np.zeros((pad,) + raws.shape[1:], raws.dtype)])
                scores = self._dense_kernel(
                    bank, jnp.asarray(kraws, jnp.float32),
                    _edge_pad(tenant_idx, b + pad))
        # the kernel is queued: the engine may launch the next window's
        # forward behind it, before this stage blocks on the result
        dispatched()
        if self.config.fused_kernel:
            # serving-side skip-rate accounting: banked_skip_stats mirrors
            # the kernel's own blocking (pow-2 block, edge-padded tail), so
            # feeding it the UNPADDED tenant vector reports exactly the
            # uniform-block fast-path coverage this dispatch just got
            stats = ops.banked_skip_stats(tenant_idx)
            with self._metrics_lock:
                self.metrics["skip_blocks_uniform"] += stats["uniform_blocks"]
                self.metrics["skip_blocks_total"] += stats["blocks"]
        self.bump_metric("kernel_dispatches")
        if chain is not None:
            self.bump_metric("kernels_chained")
        with timed("muse.transforms.fetch", "kernel_wait_ms"):
            scores = np.asarray(scores)[:b]
        return scores, bank, tenant_idx

    @staticmethod
    def _tenant_vector(bank_names: tuple[str, ...],
                       pred_names: list[str]) -> np.ndarray:
        """Each row's bank row: its predictor's place in ``bank_names``."""
        row_of = {n: r for r, n in enumerate(bank_names)}
        return np.asarray([row_of[n] for n in pred_names], np.int32)

    def _dense_kernel(self, bank: TransformBank, kraws: jax.Array,
                      kidx: np.ndarray) -> jax.Array:
        """Queue the banked kernel (or, without ``fused_kernel``, the bank's
        jnp oracle) on a bucket of float32 raws; the scores stay on the
        device."""
        if self.config.fused_kernel:
            return ops.score_pipeline_banked(
                kraws, jnp.asarray(kidx), bank.betas, bank.weights,
                bank.src_quantiles, bank.ref_quantiles)
        return bank(kraws, jnp.asarray(kidx))

    def track(self, requests: list[ScoringRequest], idxs: list[int],
              pred_names: list[str], raws: np.ndarray, bank: TransformBank,
              tenant_idx: np.ndarray) -> None:
        """Stage 3: batched per-(tenant, predictor) reservoir updates.

        Tracks the T^Q INPUT distribution — the posterior-corrected weighted
        aggregate through the window's OWN bank snapshot; fitting a refreshed
        T^Q on raw means would mismatch the pipeline (the bug class the
        paper's Sec.-3.1 update avoids).  Order-insensitive, so the async
        engine may run it a stage behind the response path.
        """
        if not self.config.track_quantiles:
            return
        keys = [(requests[i].intent.tenant, pred_names[j])
                for j, i in enumerate(idxs)]
        if self._tracker is not None:
            # device-fused mode: dense banks stage score -> transform ->
            # track as ONE device dispatch (the aggregate never syncs to
            # host); tiered stores compute pre_quantile through host-paged
            # rows, so only the scatter-append fuses.  Host estimators
            # materialize at the calibration plane's pull boundary.
            with self._estimator_lock:
                if isinstance(bank, TransformBank):
                    staged = self._tracker.append_fused(
                        keys, raws, tenant_idx, bank)
                    if not staged:
                        agg = np.asarray(bank.pre_quantile(
                            jnp.asarray(raws, jnp.float32),
                            jnp.asarray(tenant_idx)))
                else:
                    agg = np.asarray(bank.pre_quantile(
                        jnp.asarray(raws, jnp.float32),
                        jnp.asarray(tenant_idx)))
                    staged = self._tracker.append_agg(keys, agg)
                if not staged:
                    # one stream outsized the whole staging plane: its
                    # staged history was drained first (arrival order), so
                    # an eager update here keeps per-stream sequences exact
                    self._update_streams(keys, agg)
            self.bump_metric("track_staged_windows", int(staged))
            return
        agg = np.asarray(bank.pre_quantile(
            jnp.asarray(raws, jnp.float32), jnp.asarray(tenant_idx)))
        # one batched reservoir update per (tenant, predictor) stream,
        # serialized with estimator checkpoints (see _estimator_lock)
        with self._estimator_lock:
            self._update_streams(keys, agg)

    def _update_streams(self, keys: list[tuple[str, str]],
                        agg: np.ndarray) -> None:
        """Eager host tracking (caller holds ``_estimator_lock``): one
        batched reservoir update per stream present in the window."""
        by_stream: dict[tuple[str, str], list[int]] = {}
        for j, key in enumerate(keys):
            by_stream.setdefault(key, []).append(j)
        for key, rows in by_stream.items():
            self._stream_estimator(key).update(agg[rows])

    def _stream_estimator(self, key: tuple[str, str]
                          ) -> StreamingQuantileEstimator:
        """Get-or-create under ``_estimator_lock`` — the single construction
        site, so eager tracking and device-tracker drains seed identically."""
        est = self._estimators.get(key)
        if est is None:
            est = StreamingQuantileEstimator(
                self.config.quantile_capacity, seed=stream_seed(key),
                recent_capacity=self.config.recent_capacity)
            self._estimators[key] = est
        return est

    def _apply_tracked(self, key: tuple[str, str],
                       chunks: list[np.ndarray]) -> None:
        """Device-tracker materialization callback (runs under
        ``_estimator_lock``): replay staged windows as the separate update
        calls they were (see the bitwise contract in quantile_track.py)."""
        self._stream_estimator(key).apply_chunks(chunks)

    def _sync_tracker_locked(self) -> None:
        """Materialize staged device samples (caller holds the lock) —
        every calibration host-pull boundary funnels through this."""
        if self._tracker is not None:
            self._tracker.sync()

    # -------------------------------------------------------- sync data path
    def score_batch(self, requests: list[ScoringRequest]) -> list[ScoringResponse]:
        """Scores a mixed-tenant batch: requests are grouped by model group
        (shared expert-model set); each group costs one model executable
        call plus ONE tenant-indexed banked kernel dispatch, whatever mix of
        tenants and predictors the group contains.

        This is the synchronous driver: it runs the three dispatch stages
        back-to-back per group against ONE plane snapshot for the whole
        batch (live + shadows), so even a refresh landing mid-flight from
        another thread cannot mix generations.  ``serving/engine.py``
        pipelines the same stages across windows instead.
        """
        plane = self._plane  # dispatch-time snapshot
        resolutions = [self.routing.resolve(r.intent) for r in requests]
        by_group: dict[tuple[str, ...], list[int]] = {}
        for i, res in enumerate(resolutions):
            key = plane.predictors[res.live].model_names
            by_group.setdefault(key, []).append(i)

        # per-call raw-score cache: (model group, request index) -> (K,) row.
        # Live and shadow dispatches sharing a model group reuse expert
        # outputs instead of re-running the models (shadow dedup).
        raw_cache: dict[tuple[tuple[str, ...], int], np.ndarray] = {}
        responses: list[ScoringResponse | None] = [None] * len(requests)
        for idxs in by_group.values():
            t0 = time.perf_counter()  # per-dispatch latency, not cumulative
            pred_names = [resolutions[i].live for i in idxs]
            raws = self.run_models(requests, idxs, pred_names, raw_cache,
                                   plane)
            scores, bank, tenant_idx = self.apply_transforms(
                raws, pred_names, plane)
            latency_ms = (time.perf_counter() - t0) * 1000.0
            built = self.build_responses(requests, idxs, pred_names, scores,
                                         raws, bank, self.routing.version,
                                         latency_ms)
            for i, resp in zip(idxs, built):
                responses[i] = resp
            self.track(requests, idxs, pred_names, raws, bank, tenant_idx)

        # shadow evaluations (never affect the response)
        self._run_shadows(requests, resolutions, raw_cache, plane)
        self.bump_metric("requests", len(requests))
        return responses  # type: ignore[return-value]

    def _run_shadows(self, requests, resolutions,
                     raw_cache: dict | None = None,
                     plane: _ControlPlane | None = None) -> None:
        # shadow rows are (request, shadow-predictor) pairs, grouped by the
        # shadow's model group and dispatched through the same staged path.
        # ``raw_cache`` carries the live dispatches' expert outputs: a shadow
        # sharing its request's live model group reuses them (no re-run).
        plane = self._plane if plane is None else plane
        by_group: dict[tuple[str, ...], tuple[list[int], list[str]]] = {}
        for i, res in enumerate(resolutions):
            for s in res.shadows:
                key = plane.predictors[s].model_names
                idxs, names = by_group.setdefault(key, ([], []))
                idxs.append(i)
                names.append(s)
        for idxs, shadow_names in by_group.values():
            raws = self.run_models(requests, idxs, shadow_names, raw_cache,
                                   plane)
            scores, _, _ = self.apply_transforms(raws, shadow_names, plane)
            self.write_shadow_records(requests, idxs, shadow_names, scores,
                                      raws, self.routing.version)

    # --------------------------------------------------------------- refresh
    def estimator_streams(self) -> dict[tuple[str, str],
                                        StreamingQuantileEstimator]:
        """Live (tenant, predictor) -> estimator map (control-plane view).

        Streams whose predictor has since been decommissioned are excluded —
        the calibration controller must never refit a dead pipeline.  The
        scan copies the dict first: the track stage may insert a stream for
        a newly seen (tenant, predictor) from another thread mid-scan.
        Under device tracking this is a host-pull boundary: staged samples
        materialize first, so the scan never reads a stale estimator."""
        if self._tracker is not None:
            with self._estimator_lock:
                self._sync_tracker_locked()
        return {k: est for k, est in dict(self._estimators).items()
                if k[1] in self.predictors}

    def snapshot_estimator_checkpoints(
        self) -> dict[tuple[str, str], tuple[dict, dict]]:
        """One consistent (tenant, predictor) -> (arrays, meta) snapshot.

        The fleet calibration plane's PULL endpoint: each live stream is
        captured in the exact PR-5 checkpoint serialization (reservoir +
        recent ring + RNG state), taken under the estimator lock so no
        stream pairs arrays with meta from different moments even while the
        track stage keeps appending.  The fleet controller merges these per
        key across replicas (``StreamingQuantileEstimator.merge_checkpoints``)
        and fits once on the union.  Streams of decommissioned predictors
        are excluded, same as :meth:`estimator_streams`.
        """
        live = self.predictors
        with self._estimator_lock:
            self._sync_tracker_locked()
            return {key: (est.checkpoint_arrays(), est.checkpoint_meta())
                    for key, est in self._estimators.items()
                    if key[1] in live}

    # ------------------------------------------------- estimator persistence
    def save_estimators(self, directory: str, step: int = 0) -> str:
        """Checkpoint every (tenant, predictor) estimator stream.

        Uses the ``training/checkpoint.py`` layout (flat npz + json meta):
        reservoir + recent-ring arrays land in ``arrays.npz`` under integer
        stream keys; tenants/predictors and scalar state (seen counts, ring
        pointers, RNG state) ride in ``meta.json``.  A surged replica
        restores this and starts PAST the Eq.-5 gate instead of cold.
        The whole snapshot is taken under the estimator lock, serialized
        with the track stage's reservoir updates — every stream's arrays
        and scalar state (seen count, ring pointer, RNG state) come from
        ONE consistent moment, never a torn mix.  Only the npz/json write
        happens outside the lock.
        """
        from repro.training.checkpoint import save_checkpoint

        with self._estimator_lock:
            self._sync_tracker_locked()
            snaps = [(key, est.checkpoint_arrays(), est.checkpoint_meta())
                     for key, est in sorted(self._estimators.items())]
        tree = {str(i): arrays for i, (_, arrays, _) in enumerate(snaps)}
        meta = {"streams": [
            {"tenant": t, "predictor": p, **m}
            for (t, p), _, m in snaps]}
        return save_checkpoint(directory, step, tree, metadata=meta)

    def restore_estimators(self, directory: str, step: int | None = None
                           ) -> int:
        """Restore streams saved by :meth:`save_estimators`; returns the
        number restored.  Existing streams with the same (tenant,
        predictor) key are replaced wholesale (the checkpoint is the
        warmer state)."""
        from repro.training.checkpoint import (
            latest_step,
            load_arrays,
            load_metadata,
        )

        if step is None:
            step = latest_step(directory)
            if step is None:
                raise FileNotFoundError(f"no checkpoint under {directory}")
        meta = load_metadata(directory, step)
        specs = meta["streams"]
        # raw numpy leaves: the generic restore_checkpoint path round-trips
        # through jax arrays, which truncates float64 reservoirs to float32
        # without x64 enabled
        arrays = load_arrays(directory, step)
        with self._estimator_lock:
            # flush staged device samples into the OLD streams first: they
            # predate the restore decision and die with the replaced state
            # (the checkpoint is the warmer state) — they must never drain
            # into a freshly restored estimator later
            self._sync_tracker_locked()
            for i, m in enumerate(specs):
                est = StreamingQuantileEstimator.from_checkpoint(
                    {"buf": arrays[f"{i}/buf"],
                     "recent": arrays[f"{i}/recent"]}, m)
                self._estimators[(m["tenant"], m["predictor"])] = est
        return len(specs)

    def calibration_ready(self, tenant: str, predictor: str) -> bool:
        """Eq. 5 gate: enough live events for a trustworthy custom T^Q?

        A calibration host-pull boundary: staged device samples for the
        stream materialize before the gate reads the count."""
        key = (tenant, predictor)
        if self._tracker is not None and self._tracker.pending(key):
            with self._estimator_lock:
                self._sync_tracker_locked()
        est = self._estimators.get(key)
        return est is not None and est.ready(
            self.config.refresh_alert_rate, self.config.refresh_rel_error
        )

    def fit_custom_quantile_map(self, tenant: str, predictor: str,
                                ref_quantiles, n_levels: int = 256) -> QuantileMap:
        """Refresh path: fit T^Q_v1 from the live (unlabeled) score stream."""
        import jax.numpy as jnp
        if self._tracker is not None:
            with self._estimator_lock:
                self._sync_tracker_locked()
        est = self._estimators[(tenant, predictor)]
        levels = np.linspace(0.0, 1.0, n_levels)
        src = est.quantiles(levels)
        return QuantileMap(
            src_quantiles=jnp.asarray(src, jnp.float32),
            ref_quantiles=jnp.asarray(np.asarray(ref_quantiles), jnp.float32),
        )

    # ---------------------------------------------------------------- warm-up
    def warm_dispatch(self, window_sizes) -> int:
        """Compile the transform and track programs that windows of these
        sizes reach, for every model group, before live traffic does.

        Each window size warms its shape bucket; a sharded or tiered
        group also warms every smaller bucket (per-shard buckets and
        multi-pass sub-windows are at most the window's).  Inert rows run
        against the live banks: no metric, hotness count, estimator
        stream or staged sample changes.  Returns the programs run."""
        plane = self._plane
        sizes = [int(b) for b in window_sizes if b > 0]
        if not sizes or not plane.predictors:
            return 0
        top = _shape_bucket(max(sizes))
        every = [1 << i for i in range(top.bit_length())]
        windows = sorted({_shape_bucket(b) for b in sizes})
        groups = sorted({self.bank_names([n], plane)
                         for n in plane.predictors})
        runs = 0
        for names in groups:
            entry = self._bank_for(names, plane)
            k = len(plane.predictors[names[0]].model_names)
            spread = entry.tiered is not None or entry.sharded is not None
            for b in every if spread else windows:
                raws = np.zeros((b, k), np.float32)
                tid = np.zeros(b, np.int32)
                if entry.tiered is not None:
                    entry.tiered.warm(raws)
                elif entry.sharded is not None:
                    s = entry.sharded.num_shards
                    self._sharded_dispatch.run_packed(
                        np.zeros((s, b, k), np.float32),
                        np.zeros((s, b), np.int32), entry.sharded.betas,
                        entry.sharded.weights, entry.sharded.src_quantiles,
                        entry.sharded.ref_quantiles)
                else:
                    np.asarray(self._dense_kernel(entry.bank,
                                                  jnp.asarray(raws), tid))
                if self._tracker is not None:
                    with self._estimator_lock:
                        self._tracker.warm(b, entry.bank)
                runs += 1
        return runs

    # ----------------------------------------------------- tiering control
    @property
    def prefetch_enabled(self) -> bool:
        """Whether the engine should prefetch pending windows' bank rows
        (true only under a tiered topology — prefetch is a no-op and pure
        overhead against fully-resident banks)."""
        return self.config.tiering is not None

    def tiered_stores(self) -> dict[tuple[str, ...], TieredBankStore]:
        """Snapshot of the live model-group -> tiered-store map."""
        with self._tier_lock:
            return dict(self._tiered_stores)

    def prefetch_transforms(self, pred_names, plane: Any = None, *,
                            create: bool = False) -> int:
        """Stage a pending window's cold bank rows into the victim cache
        BEFORE its transform stage dispatches (the engine's anti-stall
        hook).  ``create=False`` (the poll path) only touches stores that
        already exist — speculative window contents must not build a
        heavyweight store for a predictor subset that may never dispatch;
        the model stage passes ``create=True`` because ITS names-tuple is
        exactly what the transform stage will use.  Returns rows staged."""
        if self.config.tiering is None or not pred_names:
            return 0
        plane = self._plane if plane is None else plane
        if any(n not in plane.predictors for n in pred_names):
            return 0
        names = self.bank_names(pred_names, plane)
        if create:
            store = self._bank_for(names, plane).tiered
        else:
            with self._tier_lock:
                store = self._tiered_stores.get(names)
        if store is None:
            return 0
        row_of = {n: r for r, n in enumerate(names)}
        return store.prefetch(
            np.asarray([row_of[n] for n in pred_names], np.int64))

    def rebalance_tiers(self) -> dict[str, dict]:
        """Run one promotion/demotion/admission pass on every tiered store
        (the calibration controllers call this right after a publish so
        newly admitted tenants get real slots).  Returns per-group stats."""
        return {"+".join(k): s.rebalance()
                for k, s in self.tiered_stores().items()}

    def mark_cold_tenants(self, names) -> None:
        """Route these predictors through the cold-start prior until their
        streams re-pass the Eq.-5 gate (new-tenant onboarding: scores come
        from the fitted Beta-mixture default T^Q, not an uncalibrated row).
        Applies to live stores now and to stores built later."""
        names = set(names)
        self._cold_names |= names
        for key, store in self.tiered_stores().items():
            rows = [i for i, n in enumerate(key) if n in names]
            if rows:
                store.mark_cold(rows)

    def warm_tiers_from(self, other: Any) -> int:
        """Adopt a predecessor replica's hotness/admission state (rollout
        surge): for every model group the old replica served, build this
        replica's store, adopt the old hot statistics, and promote — the
        surged replica starts with a warm hot tier instead of paging its
        entire working set through the victim cache.  Returns the number
        of stores warmed."""
        if self.config.tiering is None:
            return 0
        source = getattr(other, "tiered_stores", None)
        if source is None:
            return 0
        plane = self._plane
        warmed = 0
        for names, theirs in source().items():
            if any(n not in plane.predictors for n in names):
                continue
            store = self._bank_for(names, plane).tiered
            if store is None:
                continue
            store.adopt_hotness(theirs.hotness_snapshot())
            store.rebalance()
            warmed += 1
        self._cold_names |= set(getattr(other, "_cold_names", ()))
        return warmed

    def tier_metrics(self) -> dict[str, int]:
        """Tiered-store counters aggregated across model groups."""
        agg: dict[str, int] = {}
        for store in self.tiered_stores().values():
            for k, v in store.metrics.items():
                agg[k] = agg.get(k, 0) + v
        return agg
