"""One run of one cell: set up, drive the traffic for the window, check
what was served, and reduce what was measured.

Set-up (timed as ``setup_s``, from process start to the window's opening):
draw the weights, deploy, serve the cell's own warm traffic through the
engine, one event for every (tenant, predictor) stream first, so that the
device tracker has grown to every stream; then warm every shape bucket the
cell's windows can reach (``repro.serving.warmup.warm_up``, one bucket at
a time).  A mix with refreshes also compiles the publish path for every
number of refreshed rows and lands one pass.

The window drives ``AsyncDispatchEngine.submit`` with the one general
traffic generator (``bench/common/traffic.py``), whose parameters are the
traffic mix's file.  Refresh passes go through ``engine.schedule_refresh``
on a ``CalibrationController``.  After the window, with the program's
state freed, ``bench/common/check.py`` compares what was served with the
plain references.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from bench.common import check, deploy, registry, stats, trace, traffic

LATE_WAIT_S = 60.0      # how long after the close the run waits for answers


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def buckets(cap: int) -> list[int]:
    """Every power-of-two window bucket up to the engine's cap."""
    return [1 << i for i in range(int(cap).bit_length())]


class WindowLog:
    """Reads the engine's ``window_log`` incrementally; the engine trims
    the list as it grows, so entries are followed by identity."""

    def __init__(self, engine) -> None:
        self.engine = engine
        self.last = None
        self.entries: list[dict] = []

    def take(self) -> list[dict]:
        log_ = list(self.engine.window_log)
        start = 0
        if self.last is not None:
            for i in range(len(log_) - 1, -1, -1):
                if log_[i] is self.last:
                    start = i + 1
                    break
        new = log_[start:]
        if new:
            self.last = new[-1]
        return new


@dataclasses.dataclass
class Log:
    """Every submission of the run, in submission order."""

    requests: list = dataclasses.field(default_factory=list)
    due: list = dataclasses.field(default_factory=list)       # perf_counter
    submitted: list = dataclasses.field(default_factory=list)
    done: list = dataclasses.field(default_factory=list)      # None = open
    futures: list = dataclasses.field(default_factory=list)

    def submit(self, engine, req, due: float, on_done=None) -> None:
        i = len(self.requests)
        self.requests.append(req)
        self.due.append(due)
        self.done.append(None)
        fut = engine.submit(req)
        self.submitted.append(time.perf_counter())
        self.futures.append(fut)

        def stamp(_, i=i):
            self.done[i] = time.perf_counter()
            if on_done is not None:
                on_done()
        fut.add_done_callback(stamp)


def schedule_refresh(engine, controller, server, passes: list) -> None:
    """A ``refresh_fleet`` pass on the engine, recorded in ``passes``."""
    p = check.Pass(scheduled=time.perf_counter())
    passes.append(p)
    p.future = engine.schedule_refresh(controller)

    def stamp(f):
        # runs on the track thread right after the pass, before any other
        # control operation: the plane is the one the pass published.  The
        # tables are kept as the device arrays they are (read after the
        # window)
        p.done = time.perf_counter()
        if f.exception() is None:
            p.result = f.result()
            preds = server.plane.predictors
            p.published = {
                r.predictor: (preds[r.predictor].pipeline.src_quantiles,
                              preds[r.predictor].pipeline.ref_quantiles)
                for r in p.result.refreshed if r.predictor in preds}
    p.future.add_done_callback(stamp)


class GcPauses:
    """The interpreter's garbage-collection pauses, while installed."""

    def __init__(self) -> None:
        self.pauses: list[tuple[int, float]] = []
        self._start = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._start))
            self._start = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)

    def summary(self) -> dict:
        ms = [d * 1e3 for _, d in self.pauses]
        return {"gc_pauses": len(ms),
                "gc_full_collections": sum(1 for g, _ in self.pauses
                                           if g == 2),
                "gc_pause_ms_max": max(ms) if ms else 0.0,
                "gc_pause_ms_sum": sum(ms)}


def warm_publish(server) -> None:
    """Compile what a calibration publish runs for every number of
    refreshed rows a bank can take: ``TransformBank.with_rows`` stacks and
    scatters k tables, one program per k.  Functional: the banks built
    here are dropped."""
    import jax

    plane = server.plane
    for names in {server.bank_names([n], plane) for n in plane.predictors}:
        bank = server._bank_for(names, plane).bank
        tables = {i: (bank.src_quantiles[i], bank.ref_quantiles[i])
                  for i in range(bank.num_rows)}
        for k in range(1, bank.num_rows + 1):
            jax.block_until_ready(bank.with_rows(
                {i: tables[i] for i in range(k)}).src_quantiles)


# -------------------------------------------------------------------- spans
SPANS = {"run_models": "bench.run_models",
         "apply_transforms": "bench.apply_transforms",
         "track": "bench.track"}


def install_spans(server, controller, calls: list) -> None:
    """TraceAnnotation wrappers around the stage methods, on these
    instances only (traced runs).  ``apply_transforms`` also records each
    window's rows and distinct bank rows for the kernel's work count."""
    import jax

    def wrap(obj, method, span, record=None):
        orig = getattr(obj, method)

        def wrapped(*args, **kwargs):
            if record is not None:
                record(time.perf_counter(), *args)
            with jax.profiler.TraceAnnotation(span):
                return orig(*args, **kwargs)
        setattr(obj, method, wrapped)

    def record(t, raws, pred_names, *_):
        calls.append((t, int(raws.shape[0]), len(set(pred_names)),
                      int(raws.shape[1])))

    for method, span in SPANS.items():
        wrap(server, method, span,
             record if method == "apply_transforms" else None)
    wrap(controller, "refresh_fleet", "bench.refresh_fleet")


# --------------------------------------------------------------------- run
@dataclasses.dataclass
class Run:
    """What a per-layer reader sees (``bench/metrics/<name>.py``)."""

    cell: registry.Cell
    dep: deploy.Deployment
    seconds: float
    peaks: dict
    windows: list            # engine window_log entries inside the window
    latency_ms: np.ndarray   # arrivals in the window; inf if never answered
    queue_ms: np.ndarray     # latency minus the response's model-to-answer
    completed: int           # answers completed inside the window
    flops: float             # what the completed answers needed
    window: tuple = (0.0, 0.0)         # (open, close), perf_counter
    spans: dict | None = None          # traced runs: name -> (start, dur)
    device_ops: dict | None = None     # traced runs: plane -> [DeviceOp]
    trace_window: tuple | None = None  # (lo_ns, hi_ns) on the trace's clock
    transform_calls: list | None = None  # (t, rows, distinct rows, K)


def _device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def event_flops(dep) -> dict[str, float]:
    """FLOPs each predictor's answer needs: its experts, plus Eq. 2."""
    out = {}
    n_q = dep.config["quantile_knots"]
    for pred, experts in dep.predictors.items():
        out[pred] = sum(registry.expert_kind(dep.experts[e]["kind"])
                        .flops_per_event(dep.experts[e]) for e in experts) \
            + transform_flops(len(experts), n_q)
    return out


def transform_flops(k: int, n_q: int) -> float:
    """Eq. 2 for one row: T^C (4 per expert), the weighted sum (2 per
    expert), a binary search over the knots and the interpolation (5)."""
    return float(6 * k + math.ceil(math.log2(n_q)) + 5)


def transform_bytes(rows: int, distinct: int, k: int, n_q: int) -> float:
    """Bytes any implementation of the banked transform must move: the
    raw scores and tenant ids in, the scores out, and the bank rows the
    window references."""
    return float(rows * (k + 1) * 4 + rows * 4 + distinct * (2 * k + 2 * n_q)
                 * 4)


def run_cell(cell: registry.Cell, seed: int, seconds: float, traced: bool,
             *, t_process: float, require_tpu: bool = True,
             rate: float | None = None, tamper=None, control: bool = False,
             peaks: dict | None = None) -> dict:
    """One run; returns the result line's object (``checks`` last).

    ``tamper(dep, engine, controller)`` breaks the timed path (tests);
    ``control`` adds ``control_checks``: the same numbers read off the
    references one precision below the configuration's, put in the
    program's place."""
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.serving.calibration import CalibrationController, RefreshPolicy
    from repro.serving.engine import AsyncDispatchEngine
    from repro.serving.warmup import count_compiles, warm_up

    from bench.common.peaks import peaks_for

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{devices[0].platform}")
    if len(devices) < cell.chips:
        raise SystemExit(f"bench: {cell.name} needs {cell.chips} chips, "
                         f"JAX found {len(devices)}")
    peaks = peaks or peaks_for(devices[0].device_kind)
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg, spec = cell.config, cell.traffic
    setup: dict[str, float] = {"imports_s": time.perf_counter() - t_process}

    # ---- set-up: weights, deploys, warm-up
    weights, t = deploy.draw_weights(cfg, seed)
    setup.update(t)
    dep = deploy.build(cfg, seed, weights)
    setup.update(dep.timings)
    server = dep.server
    eng = cfg["engine"]
    cap = eng["adaptive_batch_cap"]
    policy = cfg["refresh"]
    controller = CalibrationController(server, dep.ref_quantiles,
                                       RefreshPolicy(
                                           alert_rate=policy["alert_rate"],
                                           rel_error=policy["rel_error"],
                                           z=policy["z"],
                                           n_levels=policy["n_levels"]))
    engine = AsyncDispatchEngine(
        server, max_batch=eng["max_batch"], max_wait_ms=eng["max_wait_ms"],
        adaptive_batch_cap=cap).start()
    rng = np.random.default_rng([seed, 2])
    gen0 = server.bank_generation
    passes: list[check.Pass] = []
    log_ = Log()
    t0 = time.perf_counter()
    for req in traffic.draw_events(dep, spec, rng, spec["warm_events"],
                                   cover_all=True):
        log_.submit(engine, req, time.perf_counter())
    for fut in log_.futures:
        fut.result(timeout=600)
    setup["warm_traffic_s"] = time.perf_counter() - t0
    # every stream exists now, so the tracker's staging has its final size
    for b in buckets(cap):
        t0 = time.perf_counter()
        warm_up(server, cfg["features"], batch_sizes=(b,))
        setup[f"warm_bucket_{b}_s"] = time.perf_counter() - t0
    window_plan = traffic.plan(dep, spec, rng, seconds, rate)
    if window_plan.refresh_at:
        t0 = time.perf_counter()
        warm_publish(server)
        setup["warm_publish_s"] = time.perf_counter() - t0
        schedule_refresh(engine, controller, server, passes)
        passes[0].future.result(timeout=600)
    if tamper is not None:
        tamper(dep, engine, controller)

    # ---- the window's traffic was drawn before it opens
    windows = WindowLog(engine)
    windows.take()
    calls: list = []
    tmp = None
    if traced:
        install_spans(server, controller, calls)
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
    first_window = len(log_.requests)
    first_pass = len(passes)
    gc.collect()

    # ---- the window
    with count_compiles() as compiles, GcPauses() as gc_pauses:
        if traced:
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 1     # the benchmark's spans
            options.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=options)
            span = jax.profiler.TraceAnnotation("bench.window")
            span.__enter__()
        t_open = time.perf_counter()
        setup_s = t_open - t_process
        t_close = traffic.drive(
            window_plan,
            lambda req, due, on_done: log_.submit(engine, req, due, on_done),
            lambda: schedule_refresh(engine, controller, server, passes),
            t_open, seconds,
            on_progress=lambda: windows.entries.extend(windows.take()))
        windows.entries += windows.take()
        in_window_windows = list(windows.entries)
        if traced:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        in_flight = sum(1 for d in log_.done[first_window:] if d is None)
        deadline = time.perf_counter() + LATE_WAIT_S
        for fut in log_.futures[first_window:] + [p.future for p in passes]:
            try:
                fut.result(timeout=max(0.0, deadline - time.perf_counter()))
            except Exception:       # noqa: BLE001 — counted as failed below
                pass
        window_compiles = len(compiles)
    engine.close(timeout=LATE_WAIT_S)
    stage_errors = len(engine.errors) + engine.track_errors \
        + engine.tick_errors
    mem = devices[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))

    # ---- what the window measured
    closed = window_plan.offsets is None
    due = np.asarray(log_.due[first_window:])
    done = np.asarray([np.nan if d is None else d
                       for d in log_.done[first_window:]])
    futs = log_.futures[first_window:]
    ok = np.asarray([f.done() and f.exception() is None for f in futs])
    stage = np.asarray([f.result().latency_ms if o else np.nan
                        for f, o in zip(futs, ok)])
    latency = np.where(ok, (done - due) * 1e3, np.inf)
    completed_in = ok & (done <= t_close)
    flops_of = event_flops(dep)
    flops = sum(flops_of[f.result().predictor]
                for f, c in zip(futs, completed_in) if c)
    if closed:
        attempted = int(np.sum(~np.isnan(done) & (done <= t_close)))
        failed = int(np.sum(~ok & ~np.isnan(done) & (done <= t_close)))
    else:
        attempted, failed = len(due), int(np.sum(~ok))
    window_passes = passes[first_pass:]
    refresh_ms = [(p.done - p.scheduled) * 1e3 if p.done is not None
                  else math.inf for p in window_passes]
    late = (np.asarray(log_.submitted[first_window:]) - due) * 1e3
    info = {"window_compiles": window_compiles, "in_flight_at_close":
            in_flight, "stage_errors": stage_errors,
            "windows_in_window": len(in_window_windows),
            "refresh_passes": len(refresh_ms), "compile_cache": cache,
            **gc_pauses.summary()}
    if refresh_ms:
        info["refresh_ms_p50"] = stats.percentile(refresh_ms, 50)
        info["refresh_tables_published"] = sum(
            len(p.published) for p in window_passes)
    if not closed:
        quarter = max(1, len(latency) // 4)
        info.update({"offered_per_s": len(due) / seconds,
                     "latency_p50_first_quarter_ms":
                     stats.percentile(latency[:quarter], 50),
                     "latency_p50_last_quarter_ms":
                     stats.percentile(latency[-quarter:], 50),
                     "latency_p99_ms": stats.percentile(latency, 99),
                     "generator_late_ms_p50": stats.percentile(late, 50),
                     "generator_late_ms_p99": stats.percentile(late, 99),
                     "generator_late_ms_max": float(np.max(late))
                     if len(late) else 0.0})

    run = Run(cell=cell, dep=dep, seconds=seconds, peaks=peaks,
              windows=in_window_windows,
              latency_ms=latency if not closed else np.asarray([]),
              queue_ms=(latency - stage)[ok] if not closed else np.asarray([]),
              completed=int(np.sum(completed_in)), flops=float(flops),
              window=(t_open, t_close),
              transform_calls=calls if traced else None)
    device = _device_info(devices)
    device["memory_peak_bytes"] = memory_peak
    breakdown = None
    if traced:
        tr = trace.load(trace.find_xplane(tmp))
        shutil.rmtree(tmp, ignore_errors=True)
        lo, dur = tr.spans["bench.window"][0]
        run.spans, run.device_ops = tr.spans, tr.devices
        run.trace_window = (lo, lo + dur)
        planes = list(tr.devices.values())[:cell.chips]
        busy = [trace.busy_ns(ops, lo, lo + dur) for ops in planes]
        device["busy_s"] = float(np.mean(busy)) * 1e-9 if busy else 0.0
        device["window_s"] = dur * 1e-9
        breakdown = _breakdown(tr, planes, lo, lo + dur)

    # ---- served answers against the references (program state freed)
    t_check = time.perf_counter()
    served = _served(log_)
    for p in passes:
        p.published = {k: tuple(np.asarray(a, np.float64) for a in v)
                       for k, v in p.published.items()}
    del engine, controller
    server_metrics = dict(server.metrics)
    streams = server.estimator_streams()
    dep.server = server = None
    gc.collect()
    sample = _expert_sample(served, first_window, seed,
                            cfg["check"]["expert_sample"])
    limits = cfg["check"]["limits"]

    def compare(control: bool) -> dict:
        numbers = check.expert_gaps(dep, served, sample, control=control)
        numbers["refit_gap"] = check.refit_gap(dep, served, passes, gen0,
                                               control=control)
        numbers["score_gap"] = check.score_gap(dep, served, passes, gen0,
                                               control=control)
        numbers.update(check.track_gaps(dep, served, streams,
                                        control=control))
        return {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items()}
    checks = compare(False)
    info["check_s"] = time.perf_counter() - t_check
    correct = bool(all(v["value"] <= v["limit"] for v in checks.values())
                   and len(sample) and stage_errors == 0)

    # ---- the result
    metrics = {}
    if traced:
        for m in cell.per_layer:
            value = registry.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = _end_to_end(run, closed, setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    info.update({k: v for k, v in server_metrics.items()
                 if k in ("kernel_dispatches", "model_group_calls",
                          "skip_blocks_uniform", "skip_blocks_total",
                          "track_staged_windows", "bank_generation")})
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["setup"] = setup
    result["info"] = info
    if control:
        result["control_checks"] = compare(True)
    result["checks"] = checks
    return result


def _end_to_end(run: Run, closed: bool, setup_s: float) -> dict:
    out = {"setup_s": setup_s}
    if closed:
        out["events_per_s"] = stats.rate(run.completed, run.seconds)
    else:
        out["p50_ms"] = stats.percentile(run.latency_ms, 50)
        out["p99_ms"] = stats.percentile(run.latency_ms, 99)
    return out


def _served(log_: Log) -> dict:
    """Every answered submission of the run as arrays, in submission
    order."""
    answered = [i for i, f in enumerate(log_.futures)
                if f.done() and f.exception() is None]
    rows = [(log_.requests[i], log_.futures[i].result()) for i in answered]
    names = sorted({r.predictor for _, r in rows})
    pid = {n: i for i, n in enumerate(names)}
    k = max(len(r.raw_scores) for _, r in rows)
    raws = np.full((len(rows), k), np.nan)
    for i, (_, r) in enumerate(rows):
        raws[i, :len(r.raw_scores)] = r.raw_scores
    return {
        "features": np.stack([q.features for q, _ in rows]),
        "raws": raws,
        "score": np.asarray([r.score for _, r in rows], np.float64),
        "generation": np.asarray([r.bank_generation for _, r in rows]),
        "predictor": np.asarray([r.predictor for _, r in rows]),
        "predictor_id": np.asarray([pid[r.predictor] for _, r in rows]),
        "predictor_names": names,
        "tenant": [q.intent.tenant for q, _ in rows],
        "due": np.asarray([log_.due[i] for i in answered], np.float64),
        "done": np.asarray([log_.done[i] for i in answered], np.float64),
        "submission": np.asarray(answered),
    }


def _expert_sample(served: dict, first_window: int, seed: int,
                   size: int) -> np.ndarray:
    """Rows answered for window submissions, a sample of ``size`` drawn
    from the seed (every row where fewer)."""
    rows = np.flatnonzero(served["submission"] >= first_window)
    if len(rows) > size:
        rows = np.sort(np.random.default_rng([seed, 3]).choice(
            rows, size, replace=False))
    return rows


def _breakdown(tr, planes, lo: float, hi: float) -> dict:
    ops: dict[str, float] = {}
    for plane_ops in planes:
        for name, s in trace.op_seconds(plane_ops, lo, hi).items():
            ops[name] = ops.get(name, 0.0) + s
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    idle = []
    if planes:
        for s, e in trace.gaps(planes[0], lo, hi)[:10]:
            idle.append([trace.host_activity(
                {k: v for k, v in tr.spans.items() if k != "bench.window"},
                s, e), (e - s) * 1e-9])
    return {"device_ops": [[n, s] for n, s in top], "idle_gaps": idle}
