"""What decides ``correct``: the served answers against plain references.

Four stages are compared, each against a reference that imports nothing
of the program and takes none of its tables:

- ``expert_gap.<expert>``: each sampled response's raw score of that
  expert against the expert's plain reference (``bench/experts``), run on
  the same features with the same weights;
- ``refit_gap``: every T^Q table a calibration pass published (its source
  and reference knots) against the benchmark's own refit: ``np.quantile``
  in float64, at the policy's levels, of the float64 T^C -> A aggregates
  of the served raw scores of each stream the pass pooled.  A pass's
  report says how many samples each stream had; that count has to lie
  between the stream's answers completed before the pass was scheduled
  and those due before it ended, and the Eq. 5 gate is applied to it
  anew: a stale, foreign or mis-gated pool reads ``inf``;
- ``score_gap``: each response's score against Eq. 2 in float64 numpy,
  applied to its served raw scores under the benchmark's own tables at the
  bank generation stamped on the response: the drawn T^C/A and starting
  T^Q, then the benchmark's refits from the generation each pass
  published;
- ``track_gap``: every sample the device tracker staged and drained into a
  stream's recent ring, against the float64 aggregate of that stream's
  served raw scores (both sorted, as the tracker keeps no order across
  streams); ``track_count_gap`` counts samples missing or extra over all
  streams (exact, limit 0).

The control for each number is the same reference computed one precision
below the one the configuration states (``CONTROL_BELOW``).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from bench.common import registry

CONTROL_BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}
# a window's answers complete before its track stage is queued; a pass
# scheduled this long after an answer completed has that answer's sample
QUEUE_SLACK_S = 0.5


@dataclasses.dataclass
class Pass:
    """One ``refresh_fleet`` pass of a run, as the benchmark saw it."""

    scheduled: float                 # perf_counter at schedule_refresh
    done: float | None = None        # perf_counter when its result landed
    result: object = None            # the RefreshResult (None: failed)
    published: dict = dataclasses.field(default_factory=dict)
    # ^ predictor -> (src, ref) knots the plane held right after the pass
    future: object = None            # the pass's Future


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    import jax.numpy as jnp

    if precision == "float64":
        return x
    return np.asarray(jnp.asarray(x, jnp.dtype(precision)), np.float64)


def reference_quantiles(n: int, *, a: float = 0.8, b: float = 8.0,
                        tail_w: float = 0.02, tail_a: float = 6.0,
                        tail_b: float = 1.5) -> np.ndarray:
    """The reference distribution R the deployments map onto, in float64:
    (1 - tail_w) Beta(a, b) + tail_w Beta(tail_a, tail_b), its quantiles on
    ``n`` uniform levels by inverting the CDF on a fine grid."""
    from scipy import stats

    levels = np.linspace(0.0, 1.0, n)
    grid = np.linspace(0.0, 1.0, 65537)
    cdf = (1.0 - tail_w) * stats.beta.cdf(grid, a, b) \
        + tail_w * stats.beta.cdf(grid, tail_a, tail_b)
    return np.maximum.accumulate(np.interp(levels, cdf, grid))


def required_samples(refresh: dict) -> int:
    """Eq. 5: n = z^2 (1 - a) / (delta^2 a)."""
    a, d, z = refresh["alert_rate"], refresh["rel_error"], refresh["z"]
    return int(math.ceil(z * z * (1.0 - a) / (d * d * a)))


def eq2(raws: np.ndarray, params: tuple, precision: str = "float64"
        ) -> np.ndarray:
    """Eq. 2 (T^C -> A -> T^Q) on (n, K) raw scores; every intermediate is
    rounded to ``precision``."""
    betas, weights, src, ref = (_round(np.asarray(a, np.float64), precision)
                                for a in params)
    y = _round(np.asarray(raws, np.float64), precision)
    corrected = _round(betas * y / _round(1 - (1 - betas) * y, precision),
                       precision)
    agg = _round(corrected @ _round(weights / weights.sum(), precision),
                 precision)
    return _round(np.interp(agg, src, ref), precision)


def pre_quantile(raws: np.ndarray, params: tuple,
                 precision: str = "float64") -> np.ndarray:
    """The tracked aggregate: T^C then A, without T^Q."""
    betas, weights = (_round(np.asarray(a, np.float64), precision)
                      for a in params[:2])
    y = _round(np.asarray(raws, np.float64), precision)
    corrected = _round(betas * y / _round(1 - (1 - betas) * y, precision),
                       precision)
    return _round(corrected @ _round(weights / weights.sum(), precision),
                  precision)


def refit(samples: np.ndarray, levels: np.ndarray,
          precision: str = "float64") -> np.ndarray:
    """T^Q's source knots: the samples' quantiles at ``levels`` (linear
    interpolation), made monotone."""
    q = np.quantile(_round(np.asarray(samples, np.float64), precision),
                    levels)
    return _round(np.maximum.accumulate(q), precision)


def expert_gaps(dep, served: dict, sample: np.ndarray, *,
                control: bool = False) -> dict[str, float]:
    """Largest |served raw - reference| per expert over the sampled
    responses.  ``served`` holds the responses' arrays (``features``,
    ``raws``, ``predictor``); an expert is compared on the responses whose
    predictor runs it."""
    out = {}
    precisions = dep.config["precision"]["experts"]
    for name, entry in dep.experts.items():
        kind = registry.expert_kind(entry["kind"])
        rows, cols = [], []
        for pred, experts in dep.predictors.items():
            if name in experts:
                r = sample[served["predictor"][sample] == pred]
                rows.append(r)
                cols.append(np.full(len(r), experts.index(name)))
        rows, cols = np.concatenate(rows), np.concatenate(cols)
        if not len(rows):
            out[f"expert_gap.{name}"] = np.inf
            continue
        want = kind.reference(entry, dep.weights[name],
                              served["features"][rows], "float32")
        if control:
            got = kind.reference(entry, dep.weights[name],
                                 served["features"][rows],
                                 CONTROL_BELOW[precisions[name]])
        else:
            got = served["raws"][rows, cols]
        out[f"expert_gap.{name}"] = float(np.max(np.abs(got - want)))
    return out


def _stream_rows(served: dict) -> dict[tuple[str, str], np.ndarray]:
    """Rows of each (tenant, predictor) stream, in submission order (the
    order its windows, and so its samples, reach the tracker)."""
    out: dict[tuple[str, str], list[int]] = {}
    names = served["predictor_names"]
    for i, (t, p) in enumerate(zip(served["tenant"], served["predictor_id"])):
        out.setdefault((t, names[p]), []).append(i)
    return {k: np.asarray(v, int) for k, v in out.items()}


def reference_tables(dep, served: dict, passes: list[Pass], gen0: int,
                     precision: str = "float64"):
    """The benchmark's own T^C/A/T^Q parameters for every bank generation
    of the run: its draw at ``gen0``, then, for each pass that published,
    its own refit of every predictor the pass shipped.

    Returns (tables {generation: {predictor: params}}, refits [(pass,
    predictor, src, ref)], sound): ``sound`` is false where a pass failed,
    pooled a count outside what the run's answers allow, gated a stream
    otherwise than Eq. 5 does, or shipped without a new generation."""
    refresh = dep.config["refresh"]
    need = required_samples(refresh)
    levels = np.linspace(0.0, 1.0, refresh["n_levels"])
    ref_q = dep.ref_quantiles
    ref = _round(np.interp(levels, np.linspace(0.0, 1.0, len(ref_q)), ref_q),
                 precision)
    rows_of = _stream_rows(served)
    current = dict(dep.params)
    tables, refits, sound = {gen0: current}, [], True
    generation = gen0
    for k, p in enumerate(passes):
        if p.result is None:
            sound = False
            continue
        pools: dict[str, list[np.ndarray]] = {}
        for rep in p.result.reports:
            rows = rows_of.get((rep.tenant, rep.predictor),
                               np.zeros(0, int))
            lo = int(np.sum(served["done"][rows]
                            <= p.scheduled - QUEUE_SLACK_S))
            hi = int(np.sum(served["due"][rows] <= p.done))
            if not lo <= rep.samples <= hi \
                    or (rep.samples >= need) != (rep.status != "not_ready"):
                sound = False
            if rep.status == "refreshed":
                take = rows[:rep.samples]
                params = dep.params[rep.predictor]
                pools.setdefault(rep.predictor, []).append(pre_quantile(
                    served["raws"][take, :len(params[0])], params))
        if not pools:
            continue
        if p.result.generation == generation:
            sound = False
        generation = p.result.generation
        current = dict(current)
        for pred, pool in sorted(pools.items()):
            src = refit(np.concatenate(pool), levels, precision)
            current[pred] = current[pred][:2] + (src, ref)
            refits.append((k, pred, src, ref))
        tables[generation] = current
    return tables, refits, sound


def refit_gap(dep, served: dict, passes: list[Pass], gen0: int, *,
              control: bool = False) -> float:
    """Largest |published T^Q knot - the benchmark's refit| over every
    table the run's passes published; ``inf`` for an unsound pass."""
    _, want, sound = reference_tables(dep, served, passes, gen0)
    if not sound:
        return math.inf
    if control:
        below = CONTROL_BELOW[dep.config["precision"]["transform"]]
        got = {(k, pred): (src, ref) for k, pred, src, ref in
               reference_tables(dep, served, passes, gen0, below)[1]}
    else:
        got = {(k, pred): passes[k].published.get(pred)
               for k, pred, _, _ in want}
    gap = 0.0
    for k, pred, src, ref in want:
        pub = got[(k, pred)]
        if pub is None:
            return math.inf
        gap = max(gap, float(np.max(np.abs(pub[0] - src))),
                  float(np.max(np.abs(pub[1] - ref))))
    return gap


def score_gap(dep, served: dict, passes: list[Pass], gen0: int, *,
              control: bool = False) -> float:
    """Largest |served score - Eq. 2| over every response, under the
    benchmark's own tables at the response's stamped generation."""
    tables, _, _ = reference_tables(dep, served, passes, gen0)
    precision = (CONTROL_BELOW[dep.config["precision"]["transform"]]
                 if control else "float64")
    gap = 0.0
    keys = np.stack([served["generation"], served["predictor_id"]], 1)
    uniq, inverse = np.unique(keys, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    names = served["predictor_names"]
    for g, (gen, pid) in enumerate(uniq):
        rows = np.flatnonzero(inverse == g)
        params = tables.get(int(gen), {}).get(names[pid])
        if params is None:
            return math.inf     # a generation no pass of the run published
        raws = served["raws"][rows, :len(params[0])]
        want = eq2(raws, params)
        got = (eq2(raws, params, precision) if control
               else served["score"][rows])
        gap = max(gap, float(np.max(np.abs(got - want))))
    return gap


def track_gaps(dep, served: dict, streams: dict, *,
               control: bool = False) -> dict[str, float]:
    """Tracked samples against the served raw scores, stream by stream.
    ``served`` must hold every response of the run in submission order;
    ``streams``: (tenant, predictor) -> estimator, after the final drain."""
    precision = (CONTROL_BELOW[dep.config["precision"]["transform"]]
                 if control else None)
    gap, missing = 0.0, 0
    seen = set()
    for key, rows in _stream_rows(served).items():
        seen.add(key)
        est = streams.get(key)
        if est is None:
            missing += len(rows)
            continue
        missing += abs(int(est.count) - len(rows))
        recent = np.sort(np.asarray(est.recent(), np.float64))
        tail = rows[-len(recent):] if len(recent) else rows[:0]
        params = dep.params[key[1]]
        raws = served["raws"][tail, :len(params[0])]
        want = np.sort(pre_quantile(raws, params))
        got = (np.sort(pre_quantile(raws, params, precision))
               if control else recent)
        if len(got) != len(want):
            missing += abs(len(got) - len(want))
            continue
        if len(got):
            gap = max(gap, float(np.max(np.abs(got - want))))
    missing += sum(int(e.count) for k, e in streams.items() if k not in seen)
    return {"track_gap": gap, "track_count_gap": float(missing)}
