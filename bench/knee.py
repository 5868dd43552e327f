"""Sweep of open-loop rates for a steady cell, to find its knee.

    python3 bench/knee.py --workload <steady cell> --rates 800,1000,1200 \\
        --seconds 20 --seeds 7,8

Runs the cell once per seed and rate in one process and prints one JSON
line per run: p50 and p99, the median latency of the first and last
quarter of arrivals and their ratio (``growth``).  A rate holds where
``growth`` stays within ``HOLD`` on every seed (the queue does not grow
across the window); the knee is the highest rate that holds, and a steady
cell offers 0.8 of it (``knee_events_per_s`` in the configuration).  The
last line names the knee.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HOLD = 1.10   # last-quarter median latency over the first quarter's

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="7")
    args = ap.parse_args(argv)

    from bench.common import harness, registry

    cell = registry.cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    held = {r: True for r in rates}
    for seed in (int(s) for s in args.seeds.split(",")):
        for rate in rates:
            result = harness.run_cell(cell, seed, args.seconds, False,
                                      t_process=time.perf_counter(),
                                      rate=rate)
            info = result["info"]
            growth = (info["latency_p50_last_quarter_ms"]
                      / info["latency_p50_first_quarter_ms"])
            held[rate] &= growth <= HOLD and result["failed"] == 0
            print(json.dumps({
                "seed": seed, "rate": rate, "correct": result["correct"],
                "failed": result["failed"], "growth": growth,
                "metrics": {k: v["value"]
                            for k, v in result["metrics"].items()},
                "checks": {k: v["value"]
                           for k, v in result["checks"].items()},
                "info": info}), flush=True)
            del result
            gc.collect()
    knee = max((r for r in rates if held[r]), default=None)
    print(json.dumps({"held": held, "knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
