"""Chip smoke test: MUSE's serving path end to end on a TPU.

    python3 chip_smoke.py              # one chip (the default phase)
    python3 chip_smoke.py --chips 4    # tenant-sharded banks on four chips

Default phase: a ``MuseServer`` with fused device tracking and the banked
Pallas kernel serves 56 tenants (48 with their own ensemble predictor over
a full-width internlm2-1.8b expert in bf16 plus a linear expert, 8 walk-in
tenants on a single-expert predictor).  After warm-up it serves mixed
traffic through ``AsyncDispatchEngine.submit_many``, lands one
``CalibrationController.refresh_fleet`` publish while windows are in
flight, serves more, then a 1024-event ``score_batch``.  Every score is
checked against the jnp oracle pipeline applied to the window's raw
expert scores, under the bank generation the response was stamped with.

``--chips 4``: the same traffic through a dense server, a
``tenant_shards=4`` server and a ``tenant_shards=4`` tiered server, with a
calibration publish between two rounds; the scores must agree, and each
shard's bank rows must live on their own chip.

Weights and traffic are drawn from ``--seed``.  The script needs a TPU: on
any other backend it exits non-zero without a result.  The last stdout
line is one JSON object, ``{"ok": true, "device": {...}}``; everything else
it reports comes before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

N_ENSEMBLE_TENANTS = 48
N_WALKIN_TENANTS = 8
DIM = 32                # features per event = tokens per expert input
N_Q = 128               # knots per quantile table
WINDOW = 256            # engine window (max_batch)
ALERT_RATE, REL_ERROR = 0.2, 0.5   # Eq.-5 gate of the mid-run refresh
# |kernel - jnp oracle| bound on business-ready scores in [0, 1]; a TPU
# v5e measured at most 5.96e-8 (one f32 ulp near 0.5) at the default seed
SCORE_TOL = 1e-6
# cross-topology bound after a publish: each topology refits its maps on
# its own tracked aggregates, which may differ in the last bits
REFIT_TOL = 1e-4


def report(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


# ----------------------------------------------------------------- experts
def transformer_expert(cfg, seed: int):
    """A risk-scoring transformer at ``cfg``'s widths, bf16 weights from
    ``seed``; features hash to token ids (the serve_e2e schema)."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import Model

    model = Model(cfg)
    params = jax.jit(model.init, static_argnums=1)(jax.random.key(seed),
                                                   jnp.bfloat16)
    scorer = jax.jit(lambda p, tokens: model.forward(
        p, tokens=tokens, logits_mode="last").risk_score)

    def score_fn(features):
        feats = np.asarray(features, np.float32)
        tokens = np.abs(feats[:, :DIM] * 1000).astype(np.int64) \
            % cfg.vocab_size
        return scorer(params, jnp.asarray(tokens, jnp.int32))

    return score_fn, params


def linear_expert(seed: int):
    """A logistic scorer over the raw features, on the device."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(0, 1 / np.sqrt(DIM), DIM), jnp.float32)
    b = jnp.float32(rng.normal(0, 0.5))
    scorer = jax.jit(lambda x: jax.nn.sigmoid(x @ w + b))
    return lambda features: scorer(jnp.asarray(features, jnp.float32))


# ------------------------------------------------------------------ server
def tenant_names() -> tuple[list[str], list[str]]:
    return ([f"t{i:02d}" for i in range(N_ENSEMBLE_TENANTS)],
            [f"walkin{i}" for i in range(N_WALKIN_TENANTS)])


def build_server(factories: dict, seed: int, **config):
    """48 per-tenant ensemble predictors over ("big", "lin") plus one
    catch-all single-expert predictor over ("lin",)."""
    from repro.core.predictor import PredictorSpec
    from repro.core.routing import Condition, RoutingTable, ScoringRule
    from repro.core.transforms import QuantileMap, fraud_reference_quantiles
    from repro.serving.server import MuseServer, ServerConfig

    rng = np.random.default_rng(seed + 1)
    ref_q = np.asarray(fraud_reference_quantiles(N_Q))
    ens, _ = tenant_names()
    rules = [ScoringRule(Condition(tenants=(t,)), f"{t}-ens") for t in ens]
    rules.append(ScoringRule(Condition(), "global-v1"))
    server = MuseServer(
        RoutingTable(tuple(rules), (), version="v1"),
        ServerConfig(track_device=True, fused_kernel=True,
                     refresh_alert_rate=ALERT_RATE,
                     refresh_rel_error=REL_ERROR, **config))
    levels = np.linspace(0.0, 1.0, N_Q)
    for t in ens:
        src = levels ** rng.uniform(0.6, 1.6)      # per-tenant T^Q_v0
        server.deploy(PredictorSpec(
            f"{t}-ens", ("big", "lin"),
            betas=tuple(rng.uniform(0.05, 1.0, 2)),
            weights=tuple(rng.uniform(0.5, 2.0, 2)),
            quantile_map=QuantileMap(np.asarray(src, np.float32),
                                     np.asarray(ref_q, np.float32))),
            factories)
    server.deploy(PredictorSpec.single(
        "global-v1", "lin", QuantileMap(np.asarray(levels, np.float32),
                                        np.asarray(ref_q, np.float32))),
        factories)
    return server, ref_q


def traffic(rng, n_ensemble: int, n_walkin: int) -> list:
    """Mixed-tenant events: ensemble tenants Zipf-skewed (t00 hottest),
    walk-ins uniform, interleaved at random."""
    from repro.core.routing import Intent
    from repro.serving.types import ScoringRequest

    ens, walk = tenant_names()
    p = 1.0 / np.arange(1, len(ens) + 1) ** 1.1
    tenants = list(rng.choice(ens, n_ensemble, p=p / p.sum())) \
        + list(rng.choice(walk, n_walkin))
    order = rng.permutation(len(tenants))
    feats = rng.normal(0, 1, (len(tenants), DIM)).astype(np.float32)
    return [ScoringRequest(intent=Intent(tenant=str(tenants[i])),
                           features=feats[i]) for i in order]


# ------------------------------------------------------------------ checks
def check_engine(engine) -> list[str]:
    problems = [f"engine error in {key}: {exc!r}"
                for key, exc in engine.errors]
    for name in ("tick_errors", "track_errors", "prefetch_errors",
                 "completed_dropped"):
        if getattr(engine, name):
            problems.append(f"engine {name}={getattr(engine, name)}")
    return problems


def oracle_scores(responses, pipelines: dict) -> np.ndarray:
    """The jnp oracle pipeline (``TransformPipeline``) applied to each
    response's raw expert scores, under the generation it was stamped
    with.  ``pipelines``: generation -> {predictor: pipeline}."""
    import jax
    import jax.numpy as jnp

    apply = jax.jit(lambda pipe, raw: pipe(raw))
    out = np.full(len(responses), np.nan)
    groups: dict[tuple[int, str], list[int]] = {}
    for i, r in enumerate(responses):
        groups.setdefault((r.bank_generation, r.predictor), []).append(i)
    for (gen, name), rows in groups.items():
        raws = np.asarray([responses[i].raw_scores for i in rows], np.float32)
        padded = np.zeros((2048, raws.shape[1]), np.float32)  # one shape
        for lo in range(0, len(rows), 2048):
            chunk = raws[lo:lo + 2048]
            padded[:len(chunk)] = chunk
            got = np.asarray(apply(pipelines[gen][name], jnp.asarray(padded)))
            out[rows[lo:lo + len(chunk)]] = got[:len(chunk)]
    return out


def float64_scores(responses, pipelines: dict) -> np.ndarray:
    """Eq. 2 in float64 numpy (for the report; not the enforced bound)."""
    out = np.empty(len(responses))
    for i, r in enumerate(responses):
        pipe = pipelines[r.bank_generation][r.predictor]
        y = np.asarray(r.raw_scores, np.float64)
        beta = np.asarray(pipe.betas, np.float64)
        w = np.asarray(pipe.weights, np.float64)
        agg = float(np.sum(beta * y / (1 - (1 - beta) * y) * w / w.sum()))
        out[i] = np.interp(agg, np.asarray(pipe.src_quantiles, np.float64),
                           np.asarray(pipe.ref_quantiles, np.float64))
    return out


def snapshot_pipelines(server) -> dict:
    return {n: p.pipeline for n, p in server.predictors.items()}


def banked_dispatch_is_mosaic(k: int, t: int) -> bool:
    """Lower the banked dispatch at a served shape: a compiled Pallas
    kernel appears as a ``tpu_custom_call``; interpret mode would not."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    f32 = jnp.float32
    text = ops.score_pipeline_banked.lower(
        jax.ShapeDtypeStruct((WINDOW, k), f32),
        jax.ShapeDtypeStruct((WINDOW,), jnp.int32),
        jax.ShapeDtypeStruct((t, k), f32), jax.ShapeDtypeStruct((t, k), f32),
        jax.ShapeDtypeStruct((t, N_Q), f32),
        jax.ShapeDtypeStruct((t, N_Q), f32)).as_text()
    return "tpu_custom_call" in text


# ------------------------------------------------------------- one chip
def run_one_chip(expert_cfg, seed: int, *, require_mosaic: bool = True
                 ) -> list[str]:
    """Default phase; returns the problems found (empty = pass)."""
    import jax

    from repro.serving.calibration import CalibrationController, RefreshPolicy
    from repro.serving.engine import AsyncDispatchEngine
    from repro.serving.warmup import count_compiles, warm_up

    problems: list[str] = []
    t0 = time.perf_counter()
    big, params = transformer_expert(expert_cfg, seed)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    n_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(params))
    factories = {"big": lambda: big, "lin": lambda: linear_expert(seed + 2)}
    server, ref_q = build_server(factories, seed)
    report(phase="build", expert=expert_cfg.name, layers=expert_cfg.n_layers,
           d_model=expert_cfg.d_model, vocab=expert_cfg.vocab_size,
           params=n_params, param_bytes=n_bytes,
           predictors=len(server.predictors),
           seconds=round(time.perf_counter() - t0, 3))

    t0 = time.perf_counter()
    timings = warm_up(server, DIM, batch_sizes=(WINDOW, 1024))
    report(phase="warmup", seconds=round(time.perf_counter() - t0, 3),
           model_seconds=round(sum(timings.values()), 3))

    rng = np.random.default_rng(seed + 3)
    # window-aligned rounds: every engine window holds WINDOW events, and
    # the score_batch groups pad to the 1024 and WINDOW buckets
    round1 = traffic(rng, 2 * WINDOW, WINDOW)
    round2 = traffic(rng, 2 * WINDOW, WINDOW)
    batch3 = traffic(rng, 3 * WINDOW, WINDOW)
    tenant_of = {r.request_id: r.intent.tenant
                 for r in round1 + round2 + batch3}
    pipelines = {server.bank_generation: snapshot_pipelines(server)}
    controller = CalibrationController(server, ref_q, RefreshPolicy(
        alert_rate=ALERT_RATE, rel_error=REL_ERROR, n_levels=N_Q))

    engine = AsyncDispatchEngine(server, max_batch=WINDOW)
    t0 = time.perf_counter()
    with count_compiles() as compiles:
        engine.submit_many(round1)
        served = engine.drain(timeout=900)
    round1_seconds = time.perf_counter() - t0
    refresh = engine.schedule_refresh(controller)   # lands mid-round-2
    engine.submit_many(round2)
    served += engine.drain(timeout=900)
    result = refresh.result(timeout=900)
    engine.close()
    problems += check_engine(engine)
    pipelines[result.generation] = snapshot_pipelines(server)
    report(phase="engine", events=len(served),
           round1_seconds=round(round1_seconds, 3),
           compiles_in_round1=len(compiles),
           refreshed=len(result.refreshed), not_ready=len(result.not_ready),
           rejected=len(result.rejected), generation=result.generation,
           windows=len(engine.window_log), tick_errors=engine.tick_errors,
           track_errors=engine.track_errors,
           prefetch_errors=engine.prefetch_errors)
    if not result.refreshed:
        problems.append("the mid-run refresh published nothing")

    t0 = time.perf_counter()
    served += server.score_batch(batch3)
    report(phase="score_batch", events=len(batch3),
           seconds=round(time.perf_counter() - t0, 3))

    expected = len(round1) + len(round2) + len(batch3)
    if len(served) != expected or \
            len({r.request_id for r in served}) != expected:
        problems.append(f"served {len(served)} responses for {expected}")
    gens = sorted({r.bank_generation for r in served})
    if not set(gens) <= set(pipelines):
        problems.append(f"unknown bank generations {gens}")
        return problems
    scores = np.asarray([r.score for r in served])
    raws = [np.asarray(r.raw_scores) for r in served]
    if not all(np.isfinite(x).all() and (x >= 0).all() and (x <= 1).all()
               for x in raws):
        problems.append("raw expert scores not finite in [0, 1]")
    big_raw = np.asarray([x[0] for x, r in zip(raws, served)
                          if r.predictor.endswith("-ens")])
    want = oracle_scores(served, pipelines)
    err = np.abs(scores - want)
    err64 = np.abs(scores - float64_scores(served, pipelines))
    live_ens = {tenant_of[r.request_id] for r in served
                if r.predictor.endswith("-ens")}
    report(phase="check", responses=len(served), generations=gens,
           ensemble_tenants_served=len(live_ens),
           big_expert_raw_std=float(big_raw.std()),
           max_abs_err_vs_oracle=float(err.max()),
           bitwise_equal_share=float(np.mean(scores == want)),
           max_abs_err_vs_float64=float(err64.max()),
           tolerance=SCORE_TOL,
           kernel_dispatches=server.metrics["kernel_dispatches"],
           track_staged_windows=server.metrics["track_staged_windows"],
           skip_blocks_uniform=server.metrics["skip_blocks_uniform"],
           skip_blocks_total=server.metrics["skip_blocks_total"])
    if not np.isfinite(scores).all() or not (err <= SCORE_TOL).all():
        problems.append(f"scores off the oracle by up to {err.max()!r}")
    if big_raw.std() <= 0:
        problems.append("the big expert's raw scores do not vary")
    if server.metrics["track_staged_windows"] == 0:
        problems.append("the device tracker staged no window")
    if require_mosaic and not banked_dispatch_is_mosaic(
            2, N_ENSEMBLE_TENANTS):
        problems.append("the banked dispatch is not a compiled Mosaic call")
    return problems


# ----------------------------------------------------------- four chips
def shard_placement(server) -> list[str]:
    """Each shard's bank rows (dense-sharded bank or tier view) must sit
    on its own device."""
    problems = []
    for names, entry in server.plane.banks.items():
        if entry.sharded is not None:
            shards = entry.sharded.betas.addressable_shards
            by_row = {s.index[0].start: s.device for s in shards}
            if len(set(by_row.values())) != entry.sharded.num_shards:
                problems.append(f"sharded bank {names[0]}..: rows on "
                                f"{sorted(map(str, set(by_row.values())))}")
    for names, store in server.tiered_stores().items():
        devs = [frozenset(st.devices) for st in store.shards]
        if any(len(d) != 1 for d in devs) or len(set(devs)) != len(devs):
            problems.append(f"tier views of {names[0]}..: {devs}")
    return problems


def run_four_chips(seed: int, shards: int = 4) -> list[str]:
    """Dense vs tenant-sharded vs tenant-sharded tiered serving of the same
    traffic, with one calibration publish between the rounds."""
    from repro.serving.calibration import CalibrationController, RefreshPolicy
    from repro.serving.engine import AsyncDispatchEngine
    from repro.serving.tiering import TieringConfig
    from repro.serving.warmup import warm_up

    problems: list[str] = []
    factories = {"big": lambda: linear_expert(seed + 5),
                 "lin": lambda: linear_expert(seed + 2)}
    configs = {
        "dense": {},
        "sharded": {"tenant_shards": shards},
        "sharded_tiered": {"tenant_shards": shards, "tiering": TieringConfig(
            hot_capacity=8, victim_capacity=8)},
    }
    rng = np.random.default_rng(seed + 3)
    rounds = [traffic(rng, 2 * WINDOW, WINDOW) for _ in range(2)]
    batch3 = traffic(rng, 3 * WINDOW, WINDOW)
    scores: dict[str, dict[int, tuple[float, int]]] = {}
    for label, config in configs.items():
        t0 = time.perf_counter()
        server, ref_q = build_server(factories, seed, **config)
        warm_up(server, DIM, batch_sizes=(WINDOW, 1024))
        controller = CalibrationController(server, ref_q, RefreshPolicy(
            alert_rate=ALERT_RATE, rel_error=REL_ERROR, n_levels=N_Q))
        engine = AsyncDispatchEngine(server, max_batch=WINDOW)
        engine.submit_many(rounds[0])
        served = engine.drain(timeout=900)
        # between rounds, so every topology publishes the same maps
        result = engine.schedule_refresh(controller).result(timeout=900)
        engine.submit_many(rounds[1])
        served += engine.drain(timeout=900)
        engine.close()
        served += server.score_batch(batch3)
        problems += [f"{label}: {p}" for p in check_engine(engine)]
        problems += [f"{label}: {p}" for p in shard_placement(server)]
        if not result.refreshed:
            problems.append(f"{label}: the refresh published nothing")
        scores[label] = {r.request_id: (r.score, r.bank_generation)
                         for r in served}
        report(phase=label, events=len(served),
               refreshed=len(result.refreshed),
               shard_dispatches=server.metrics["shard_dispatches"],
               tier_dispatches=server.metrics["tier_dispatches"],
               seconds=round(time.perf_counter() - t0, 3))
    ids = sorted(scores["dense"])
    dense = np.asarray([scores["dense"][i][0] for i in ids])
    gens = np.asarray([scores["dense"][i][1] for i in ids])
    before = gens == gens.min()     # scored before the publish
    for label in ("sharded", "sharded_tiered"):
        if sorted(scores[label]) != ids:
            problems.append(f"{label}: served a different request set")
            continue
        got = np.asarray([scores[label][i][0] for i in ids])
        err = np.abs(got - dense)
        gens_match = all(scores[label][i][1] == scores["dense"][i][1]
                         for i in ids)
        # after the publish, maps are refit on each topology's own tracked
        # aggregates (the tiered store computes them in numpy), so only
        # the pre-publish scores can be expected bitwise
        report(phase="compare", topology=label, responses=len(ids),
               max_abs_diff_vs_dense=float(err.max()),
               bitwise_share_before_publish=float(
                   np.mean(got[before] == dense[before])),
               bitwise_share_after_publish=float(
                   np.mean(got[~before] == dense[~before])),
               generations_match=gens_match)
        if not gens_match:
            problems.append(f"{label}: bank generations differ from dense")
        if not ((err[before] <= SCORE_TOL).all()
                and (err[~before] <= REFIT_TOL).all()):
            problems.append(f"{label}: scores off dense by {err.max()!r}")
    return problems


# ------------------------------------------------------------------ main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dense-vs-sharded comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache

    report(phase="start", jax=jax.__version__, cache=enable_compile_cache())
    t0 = time.perf_counter()
    if args.chips == 4:
        problems = run_four_chips(args.seed)
    else:
        from repro.configs import get_config
        problems = run_one_chip(get_config("internlm2-1.8b"), args.seed)
    report(phase="done", seconds=round(time.perf_counter() - t0, 3),
           problems=len(problems))
    for p in problems:
        print(f"chip_smoke: FAIL {p}", file=sys.stderr)
    if problems:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
