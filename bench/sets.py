"""Two sets of runs of one cell, to read the spread its bounds rest on.

    python3 bench/sets.py --workload <cell> --seeds 1,2,3,4,5,6 \\
        --seconds 20 [--traced 7,8,9] [--out .bench_runs]

Runs ``bench/run.py`` once per seed, twice over (set A, then set B with
the same seeds), each run a process of its own, then once per ``--traced``
seed with ``--trace 1``.  Every run's output goes to ``--out``; one JSON
line per run and, at the end, each end-to-end metric's spread per set
(quartile distance over the median, ``statistics.quantiles``) and its
median.  The parent never touches JAX, so each child has the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.common import stats  # noqa: E402


def run(workload: str, seed: int, seconds: float, trace: int,
        out: str, label: str) -> dict | None:
    tag = f"{workload}.{seed}.{label}"
    cmd = [sys.executable, os.path.join(ROOT, "bench", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    with open(os.path.join(out, tag + ".out"), "w") as fo, \
            open(os.path.join(out, tag + ".err"), "w") as fe:
        rc = subprocess.call(cmd, stdout=fo, stderr=fe, cwd=ROOT)
    lines = open(os.path.join(out, tag + ".out")).read().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, ValueError):
        result = None
    print(json.dumps({"run": tag, "rc": rc, "result": result}), flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traced", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--out", default=os.path.join(ROOT, ".bench_runs"))
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for i in range(args.sets):
        sets.append([run(args.workload, s, args.seconds, 0, args.out,
                         f"set{i}") for s in seeds])
    for s in (int(s) for s in args.traced.split(",") if s):
        run(args.workload, s, args.seconds, 1, args.out, "trace")
    for i, results in enumerate(sets):
        ok = [r for r in results if r is not None]
        for name in sorted({m for r in ok for m in r["metrics"]}):
            values = [r["metrics"][name]["value"] for r in ok
                      if name in r["metrics"]]
            if len(values) >= 2:
                print(json.dumps({
                    "set": i, "metric": name, "values": values,
                    "median": statistics.median(values),
                    "spread": stats.spread(values)}), flush=True)
        print(json.dumps({"set": i, "correct": [
            r["correct"] if r else None for r in results]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
