"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5

Runs the cell once per seed in one process (set-up is paid per seed, the
compiled programs once) and prints one JSON line per seed: the program's
compared numbers (``checks``) and the control's (``control_checks``: each
reference one precision below the configuration's, in the program's
place).  A limit lies above the largest program reading over a dozen seeds
or more and below the smallest control reading.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench.common import harness, registry

    cell = registry.cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(cell, seed, args.seconds, False,
                                  t_process=time.perf_counter(),
                                  control=True)
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "attempted": result["attempted"],
                          "info": result["info"],
                          "control_checks": result["control_checks"],
                          "checks": result["checks"]}), flush=True)
        del result
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
