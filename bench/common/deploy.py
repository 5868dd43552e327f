"""Build a configuration's deployment: weights, server, tenants.

Everything is drawn from the seed.  The configuration's ``groups`` lay the
tenants out, in routing order; each group is data:

- ``prefix``, ``tenants``: the group's tenant names, ``<prefix><i>``;
- ``experts``: the experts its predictors run;
- ``predictor``: ``"own"`` gives every tenant a predictor of its own
  (``<tenant>-ens``, betas, weights and a starting T^Q drawn per tenant)
  behind a rule of its own; any other value names one predictor that the
  whole group shares (unit betas and weights, the identity T^Q), behind
  one rule for the group, or a rule that matches every tenant where
  ``catch_all`` is set (the last group only);
- ``share``, ``zipf_s``: the group's share of events and its skew, which
  a traffic mix may override.

``server`` in the configuration passes further ``ServerConfig`` settings.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from bench.common import check, registry

OWN = "own"


@dataclasses.dataclass
class Deployment:
    config: dict
    experts: dict          # expert name -> its entry in the configuration
    weights: dict          # expert name -> weight tree on the device
    server: object
    ref_quantiles: np.ndarray   # the reference distribution R, float64
    groups: list[dict]     # the configuration's groups, with ``names``
    predictors: dict       # predictor name -> the experts it runs
    # the benchmark's own record of each predictor's T^C/A parameters and
    # starting T^Q, as drawn from the seed: {predictor: (betas, weights,
    # src_quantiles, ref_quantiles)} in float64
    params: dict
    timings: dict


def expert_entries(config: dict) -> dict:
    return {e["name"]: e for e in config["experts"]}


def draw_weights(config: dict, seed: int) -> tuple[dict, dict]:
    """Every expert's weights in one jitted call from the seed, on the
    device, in the type they are served in.  Returns (weights, seconds of
    compile and of drawing)."""
    entries = expert_entries(config)
    kinds = {n: registry.expert_kind(e["kind"]) for n, e in entries.items()}
    names = sorted(entries)

    def init(key):
        keys = jax.random.split(key, len(names))
        return {n: kinds[n].init(k, entries[n]) for n, k in zip(names, keys)}

    key = jax.random.key(seed)
    t0 = time.perf_counter()
    compiled = jax.jit(init).lower(key).compile()
    t1 = time.perf_counter()
    weights = jax.block_until_ready(compiled(key))
    t2 = time.perf_counter()
    return weights, {"weights_compile_s": t1 - t0, "weights_draw_s": t2 - t1}


def tenant_names(group: dict) -> list[str]:
    width = len(str(max(group["tenants"] - 1, 1)))
    return [f"{group['prefix']}{i:0{width}d}" for i in range(group["tenants"])]


def build(config: dict, seed: int, weights: dict) -> Deployment:
    """The server with every predictor deployed (``fused_kernel=True``,
    ``track_device=True``)."""
    from repro.core.predictor import PredictorSpec
    from repro.core.routing import Condition, RoutingTable, ScoringRule
    from repro.core.transforms import QuantileMap
    from repro.serving.server import MuseServer, ServerConfig

    t0 = time.perf_counter()
    entries = expert_entries(config)
    factories = {
        n: (lambda n=n: registry.expert_kind(entries[n]["kind"])
            .program_score_fn(entries[n], weights[n]))
        for n in entries}
    n_q = config["quantile_knots"]
    ref_q = check.reference_quantiles(n_q)
    ref32 = np.asarray(ref_q, np.float32)
    levels = np.linspace(0.0, 1.0, n_q)
    groups = [dict(g, names=tenant_names(g)) for g in config["groups"]]
    rules, specs, params, predictors = [], [], {}, {}
    rng = np.random.default_rng([seed, 1])
    for g in groups:
        experts = tuple(g["experts"])
        k = len(experts)
        if g["predictor"] == OWN:
            for t in g["names"]:
                src = np.asarray(levels ** rng.uniform(0.6, 1.6), np.float32)
                betas = np.asarray(rng.uniform(0.05, 1.0, k), np.float32)
                wts = np.asarray(rng.uniform(0.5, 2.0, k), np.float32)
                name = f"{t}-ens"
                rules.append(ScoringRule(Condition(tenants=(t,)), name))
                specs.append(PredictorSpec(
                    name, experts, betas=tuple(map(float, betas)),
                    weights=tuple(map(float, wts)),
                    quantile_map=QuantileMap(src, ref32)))
                # a one-expert predictor skips T^C (PredictorSpec.pipeline)
                params[name] = (betas if k > 1 else np.ones(1, np.float32),
                                wts, src, ref32)
                predictors[name] = list(experts)
        else:
            name = g["predictor"]
            if g.get("catch_all") and g is not groups[-1]:
                raise ValueError(f"group {g['prefix']}: only the last group "
                                 "may catch all tenants")
            rules.append(ScoringRule(
                Condition() if g.get("catch_all")
                else Condition(tenants=tuple(g["names"])), name))
            src = np.asarray(levels, np.float32)
            ones = np.ones(k, np.float32)
            specs.append(PredictorSpec(
                name, experts, betas=tuple(map(float, ones)),
                weights=tuple(map(float, ones)),
                quantile_map=QuantileMap(src, ref32)))
            params[name] = (ones, ones, src, ref32)
            predictors[name] = list(experts)
    refresh = config["refresh"]
    server = MuseServer(
        RoutingTable(tuple(rules), (), version="v1"),
        ServerConfig(track_device=True, fused_kernel=True,
                     refresh_alert_rate=refresh["alert_rate"],
                     refresh_rel_error=refresh["rel_error"],
                     **config.get("server", {})))
    for spec in specs:
        server.deploy(spec, factories)
    params = {k: tuple(np.asarray(a, np.float64) for a in v)
              for k, v in params.items()}
    return Deployment(config, entries, weights, server, ref_q, groups,
                      predictors, params,
                      {"deploy_s": time.perf_counter() - t0})
