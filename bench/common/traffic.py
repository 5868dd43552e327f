"""The one general traffic generator.  A traffic mix is a file of its
parameters, ``bench/traffic/<mix>.json``, and nothing else:

- ``"loop": "open"``: a fixed number of arrivals over the window, each
  submitted when it is due; latency runs from the due time.  The rate is
  ``"rate"`` events/s, or ``"rate_share_of_knee"`` times the
  configuration's ``knee_events_per_s``.  ``"profile"`` shapes it:
  ``{"period_s": P, "segments": [[start_s, end_s, multiplier], ...]}``
  multiplies the rate inside ``[start_s, end_s)`` of every period of P
  seconds (1 elsewhere), so on/off bursts are data.  The count is the
  integral of the rate over the window, and the times are drawn from its
  density (a Poisson process given its count): every seed does the same
  amount of work.
- ``"loop": "closed"``: ``"in_flight"`` events outstanding, the next
  submitted as one completes, from a pool of ``"pool"`` drawn events.
- ``"refresh_every_s"``: a ``refresh_fleet`` pass at the middle of every
  such period of the window (0: none).
- ``"warm_events"``: events served in set-up, after one for every stream.
- ``"groups"``: per tenant group (by its ``prefix`` in the configuration),
  overrides of the group's ``share`` of events and ``zipf_s`` skew.
- ``"run_length"``: consecutive events from one tenant (1: interleaved).
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np


@dataclasses.dataclass
class Plan:
    """A window's traffic, drawn in set-up."""

    requests: list
    offsets: np.ndarray | None       # open loop: due times from the opening
    in_flight: int = 0               # closed loop
    refresh_at: tuple = ()           # seconds from the opening


def groups(dep, spec: dict) -> list[dict]:
    """The configuration's tenant groups with the mix's overrides."""
    over = spec.get("groups", {})
    return [dict(g, **over.get(g["prefix"], {})) for g in dep.groups]


def draw_events(dep, spec: dict, rng, n: int, cover_all: bool = False):
    """``n`` events of the mix's tenant draw, with their features:
    each group takes its ``share`` of events (the first group the rest),
    its tenants Zipf-skewed by ``zipf_s`` (0: uniform; the first tenant
    hottest), in runs of ``run_length`` events of one tenant, the runs in
    random order.  ``cover_all`` puts one event of every tenant first."""
    from repro.core.routing import Intent
    from repro.serving.types import ScoringRequest

    run = int(spec.get("run_length", 1))
    n_runs = -(-n // run)
    gs = groups(dep, spec)
    counts = [int(round(n_runs * g["share"])) for g in gs[1:]]
    counts.insert(0, n_runs - sum(counts))
    tenants: list[str] = []
    for g, k in zip(gs, counts):
        names = g["names"]
        p = 1.0 / np.arange(1, len(names) + 1) ** g["zipf_s"]
        tenants += list(rng.choice(names, k, p=p / p.sum())) if k else []
    tenants = [tenants[i] for i in rng.permutation(len(tenants))]
    tenants = [t for t in tenants for _ in range(run)][:n]
    if cover_all:
        tenants = [t for g in dep.groups for t in g["names"]] + tenants
    feats = rng.normal(0, 1, (len(tenants), dep.config["features"])).astype(
        np.float32)
    return [ScoringRequest(intent=Intent(tenant=str(t)), features=feats[i])
            for i, t in enumerate(tenants)]


def arrival_offsets(spec: dict, rate: float, seconds: float, rng
                    ) -> np.ndarray:
    """Sorted due times in ``[0, seconds)``: as many as the profiled rate
    integrates to, drawn from its piecewise-constant density."""
    segments = [(0.0, seconds, 1.0)]
    profile = spec.get("profile")
    if profile:
        period = float(profile["period_s"])
        cuts = sorted({0.0, period, *(float(x) for s in profile["segments"]
                                      for x in s[:2])})
        mult = []
        for a, b in zip(cuts, cuts[1:]):
            m = [s[2] for s in profile["segments"] if s[0] <= a and b <= s[1]]
            mult.append((a, b, float(m[0]) if m else 1.0))
        segments = []
        for k in range(int(np.ceil(seconds / period))):
            for a, b, m in mult:
                lo, hi = k * period + a, min(k * period + b, seconds)
                if hi > lo:
                    segments.append((lo, hi, m))
    lo, hi, m = (np.asarray(x, np.float64) for x in zip(*segments))
    mass = (hi - lo) * m
    n = int(round(rate * mass.sum()))
    pick = rng.choice(len(mass), n, p=mass / mass.sum())
    return np.sort(lo[pick] + rng.uniform(0.0, 1.0, n) * (hi - lo)[pick])


def plan(dep, spec: dict, rng, seconds: float, rate: float | None = None
         ) -> Plan:
    """The window's traffic; ``rate`` overrides the mix's (a sweep)."""
    every = spec.get("refresh_every_s") or 0
    refresh_at = tuple((k + 0.5) * every
                       for k in range(int(seconds // every))) if every else ()
    if spec["loop"] == "closed":
        return Plan(draw_events(dep, spec, rng, spec["pool"]), None,
                    in_flight=spec["in_flight"], refresh_at=refresh_at)
    if rate is None:
        rate = spec.get("rate") or (spec["rate_share_of_knee"]
                                    * dep.config["knee_events_per_s"])
    offsets = arrival_offsets(spec, rate, seconds, rng)
    return Plan(draw_events(dep, spec, rng, len(offsets)), offsets,
                refresh_at=refresh_at)


def drive(plan_: Plan, submit, fire_refresh, t_open: float, seconds: float,
          on_progress=None) -> float:
    """Run the window: ``submit(request, due, on_done)`` each event on its
    schedule (open loop) or as one completes (closed loop), and
    ``fire_refresh()`` at each refresh time.  Returns the close."""
    t_close = t_open + seconds
    refresh = [t_open + a for a in plan_.refresh_at]
    if plan_.offsets is None:
        return _closed(plan_, submit, fire_refresh, refresh, t_close,
                       on_progress)
    due = t_open + plan_.offsets
    i, n = 0, len(due)
    while i < n or refresh:
        now = time.perf_counter()
        if refresh and now >= refresh[0]:
            fire_refresh()
            refresh.pop(0)
        while i < n and due[i] <= now:
            submit(plan_.requests[i], float(due[i]), None)
            i += 1
        wake = ([due[i]] if i < n else []) + refresh[:1]
        if wake:
            delay = min(wake) - time.perf_counter()
            if delay > 0:
                time.sleep(min(delay, 0.002))
    while time.perf_counter() < t_close:
        time.sleep(min(0.002, max(0.0, t_close - time.perf_counter())))
    return t_close


def _closed(plan_: Plan, submit, fire_refresh, refresh: list, t_close: float,
            on_progress) -> float:
    free = threading.Semaphore(plan_.in_flight)
    pool, k = plan_.requests, 0
    while True:
        now = time.perf_counter()
        if refresh and now >= refresh[0]:
            fire_refresh()
            refresh.pop(0)
        if now >= t_close:
            break
        wait = min([t_close] + refresh[:1]) - now
        if not free.acquire(timeout=max(0.0, wait)):
            continue
        if time.perf_counter() >= t_close:
            break
        submit(pool[k % len(pool)], time.perf_counter(), free.release)
        k += 1
        if on_progress is not None and k % 4096 == 0:
            on_progress()
    return t_close
