"""The predictor abstraction (paper Sec. 2.2, Eq. 2).

A predictor is the tuple ``p = <M, A, T^Q>``:

  * ``M``  — subset of expert models, each paired with its posterior
             correction ``T^C_k`` (a beta ratio from its training config);
  * ``A``  — aggregation (weighted average);
  * ``T^Q`` — quantile map to the stable reference distribution.

``PredictorSpec`` is the declarative half (model names + transform params —
what lives in the control plane / routing config).  ``Predictor`` is the bound
half: specs resolved against a :class:`~repro.core.registry.ModelPool`, with
the whole Eq. 2 pipeline jit-compiled.  Single-model predictors skip ``T^C``
and use identity aggregation, per the paper.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import transforms
from repro.core.registry import ModelPool
from repro.core.transforms import Aggregation, PosteriorCorrection, QuantileMap

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TransformPipeline:
    """The post-model half of Eq. 2 as one pytree (swap = model update)."""

    betas: Array          # (K,) per-expert undersampling ratios
    weights: Array        # (K,) aggregation weights
    src_quantiles: Array  # (N,)
    ref_quantiles: Array  # (N,)

    def __call__(self, expert_scores: Array) -> Array:
        """expert_scores: (..., K) raw scores -> (...) business-ready score."""
        return transforms.score_pipeline(
            expert_scores, self.betas, self.weights,
            self.src_quantiles, self.ref_quantiles,
        )

    def pre_quantile(self, expert_scores: Array) -> Array:
        """The T^Q *input*: posterior-corrected weighted aggregate.

        This is the distribution whose quantiles a refreshed T^Q must be
        fitted on (fitting on raw scores would mismatch the pipeline)."""
        corrected = transforms.posterior_correction(expert_scores, self.betas)
        w = self.weights / jnp.sum(self.weights)
        return jnp.einsum("...k,k->...", corrected, w,
                          precision=jax.lax.Precision.HIGHEST)

    @property
    def num_experts(self) -> int:
        return int(self.betas.shape[-1])

    def with_quantile_map(self, qm: QuantileMap) -> "TransformPipeline":
        return dataclasses.replace(
            self, src_quantiles=qm.src_quantiles, ref_quantiles=qm.ref_quantiles
        )

    def with_weights(self, weights: Array) -> "TransformPipeline":
        return dataclasses.replace(self, weights=jnp.asarray(weights, jnp.float32))


@dataclasses.dataclass(frozen=True)
class PredictorSpec:
    """Declarative predictor definition (control-plane object)."""

    name: str
    model_names: tuple[str, ...]
    betas: tuple[float, ...]          # per-model undersampling ratio (1.0 = none)
    weights: tuple[float, ...]        # aggregation weights
    quantile_map: QuantileMap
    metadata: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self) -> None:
        k = len(self.model_names)
        if len(self.betas) != k or len(self.weights) != k:
            raise ValueError(
                f"predictor {self.name}: {k} models but "
                f"{len(self.betas)} betas / {len(self.weights)} weights"
            )

    @property
    def is_ensemble(self) -> bool:
        return len(self.model_names) > 1

    def pipeline(self) -> TransformPipeline:
        # Single-model predictors skip posterior correction (Sec. 2.2.2):
        # beta is forced to 1.0 (identity) and aggregation is identity.
        betas = self.betas if self.is_ensemble else (1.0,) * len(self.betas)
        return TransformPipeline(
            betas=jnp.asarray(betas, jnp.float32),
            weights=jnp.asarray(self.weights, jnp.float32),
            src_quantiles=self.quantile_map.src_quantiles,
            ref_quantiles=self.quantile_map.ref_quantiles,
        )

    @staticmethod
    def single(name: str, model_name: str, quantile_map: QuantileMap,
               **metadata: Any) -> "PredictorSpec":
        return PredictorSpec(
            name=name, model_names=(model_name,), betas=(1.0,), weights=(1.0,),
            quantile_map=quantile_map, metadata=metadata,
        )


class Predictor:
    """Spec bound to a model pool; callable on feature batches.

    Scoring (Eq. 2): run every expert, stack raw scores on the last axis,
    then apply the jitted transformation pipeline.  Raw scores are also
    returned for shadow logging / calibration analysis.
    """

    def __init__(self, spec: PredictorSpec, pool: ModelPool) -> None:
        self.spec = spec
        self._handles = [pool.acquire(n) for n in spec.model_names]
        self.pipeline = spec.pipeline()
        self._apply = jax.jit(lambda pipe, raw: pipe(raw))

    @property
    def name(self) -> str:
        return self.spec.name

    @property
    def model_names(self) -> tuple[str, ...]:
        return self.spec.model_names

    def raw_scores(self, features: Any) -> Array:
        """(..., K) stack of raw expert scores."""
        outs = [h.score_fn(features) for h in self._handles]
        return jnp.stack([jnp.asarray(o) for o in outs], axis=-1)

    def __call__(self, features: Any) -> Array:
        return self._apply(self.pipeline, self.raw_scores(features))

    def score_with_raw(self, features: Any) -> tuple[Array, Array]:
        raw = self.raw_scores(features)
        return self._apply(self.pipeline, raw), raw

    # -- seamless updates ----------------------------------------------------
    def with_updated_pipeline(self, pipeline: TransformPipeline) -> "Predictor":
        """Hot-swap the transformation pipeline (e.g. T^Q_v0 -> T^Q_v1).

        Returns a new predictor sharing the same model handles — no model
        re-provisioning, which is exactly the paper's cheap-update path.
        """
        clone = object.__new__(Predictor)
        clone.spec = self.spec
        clone._handles = self._handles
        clone.pipeline = pipeline
        clone._apply = self._apply
        return clone

    def release(self, pool: ModelPool) -> None:
        for n in self.spec.model_names:
            pool.release(n)


def deploy_predictor(spec: PredictorSpec, pool: ModelPool,
                     model_factories: Mapping[str, Callable[[], Any]],
                     model_costs: Mapping[str, float] | None = None) -> Predictor:
    """Deploy a predictor, provisioning only the models the pool lacks.

    ``model_factories`` maps model name -> zero-arg callable building the
    scoring fn (expensive: loads weights / compiles).  The factory is invoked
    only for models not already in the pool — Sec. 2.2.1's marginal-cost
    deployment.
    """
    costs = dict(model_costs or {})
    for name in spec.model_names:
        if name not in pool:
            pool.deploy(name, model_factories[name](),
                        resource_cost=costs.get(name, 1.0))
    return Predictor(spec, pool)
