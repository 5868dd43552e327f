"""MUSE benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's deployment from ``--seed``, warms it, drives its traffic
through ``AsyncDispatchEngine.submit`` for ``--seconds``, checks what was
served against plain references, and prints one JSON object as the last
line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the per-layer
ones read from a profiler trace of the window), ``device``, and, last,
``checks``: each compared number beside its limit.  The same numbers are
the last lines of standard error.  Needs a TPU: on any other backend, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.common import harness, registry

    cell = registry.cell(args.workload)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS)
    print("bench: setup", json.dumps(result.pop("setup")), flush=True)
    print("bench: info", json.dumps(result.pop("info")), flush=True)
    for name, c in result["checks"].items():
        harness.log(f"check {name} {c['value']!r} limit {c['limit']!r} "
                    f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
