"""Shared pieces of the benchmark's CPU tests: the checkout on the import
path, and a cell cut to smoke widths and a few tenants."""
from __future__ import annotations

import copy
import dataclasses
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

SMOKE_INTERNLM2 = dict(num_hidden_layers=2, hidden_size=256,
                       num_attention_heads=4, num_key_value_heads=2,
                       head_dim=64, intermediate_size=512, vocab_size=512)
CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def smoke_cell(name: str, config: str, traffic: str, *, tenants: int = 4,
               cap: int = 8, knee: float = 200.0,
               refresh_every_s: float | None = None):
    """``config`` under ``traffic``, with the metrics of the cell ``name``,
    at smoke widths, ``tenants`` tenants in the first group (2 in any
    other) and windows of at most ``cap`` events; ``refresh_every_s``
    sets the mix's refresh period, with enough warm traffic that streams
    pass the Eq. 5 gate."""
    from bench.common import registry

    cell = registry.assemble(name, f"bench/configs/{config}.json", traffic)
    cfg = copy.deepcopy(cell.config)
    for expert in cfg["experts"]:
        if expert["kind"] == "internlm2":
            expert.update(SMOKE_INTERNLM2)
    cfg["groups"] = [dict(g, tenants=min(g["tenants"],
                                         tenants if i == 0 else 2))
                     for i, g in enumerate(cfg["groups"])]
    cfg["engine"] = dict(cfg["engine"], max_batch=min(4, cap),
                         adaptive_batch_cap=cap)
    cfg["knee_events_per_s"] = knee
    cfg["check"] = dict(cfg["check"], expert_sample=16)
    traffic = dict(cell.traffic, warm_events=16)
    if refresh_every_s is not None:
        traffic.update(refresh_every_s=refresh_every_s, warm_events=400)
    if traffic["loop"] == "closed":
        traffic.update(pool=64, in_flight=16)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def run_smoke(cell, seconds: float = 1.0, **kwargs) -> dict:
    from bench.common import harness

    return harness.run_cell(cell, 2**31 + 11, seconds, False,
                            t_process=time.perf_counter(),
                            require_tpu=False, peaks=CPU_PEAKS, **kwargs)
