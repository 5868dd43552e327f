"""Find the parts of a cell by the names ``BENCHMARK.json`` gives them.

A cell names a configuration and a traffic mix; a per-layer metric names a
reader.  Each part is a file of its own, so a later change adds a
configuration, a mix or a metric by adding files and entries, never by
editing a file that is already here:

- ``bench/configs/<config>.json``: the deployment's sizes, as it is run;
  each expert in it names a kind, ``bench/experts/<kind>.py``, which draws
  the expert's weights, builds the program's scorer and holds the plain
  reference;
- ``bench/traffic/<mix>.json``: the parameters of the one general traffic
  generator (``bench/common/harness.py``);
- ``bench/metrics/<metric>.py``: a reader with ``read(run)``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Any

ROOT = pathlib.Path(__file__).resolve().parents[2]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]    # the cell's end-to-end metrics
    per_layer: tuple[dict, ...]     # the cell's per-layer metrics


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    entries = {w["name"]: w for w in benchmark(root)["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(entries)})")
    entry = entries[name]
    files = {c["name"]: c["file"] for c in benchmark(root)["configs"]}
    return assemble(name, files[entry["config"]], entry["traffic"],
                    int(entry["chips"]), root)


def assemble(name: str, config_file: str, traffic: str, chips: int = 1,
             root: pathlib.Path = ROOT) -> Cell:
    """A configuration file under a traffic mix, with the metrics
    ``BENCHMARK.json`` gives a cell called ``name``."""
    bench = benchmark(root)
    return Cell(
        name=name, chips=chips,
        config=json.loads((root / config_file).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{traffic}.json").read_text()),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))


def _load(path: pathlib.Path, module_name: str) -> Any:
    if module_name in sys.modules:
        return sys.modules[module_name]
    if not path.is_file():
        raise KeyError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module
    spec.loader.exec_module(module)
    return module


def expert_kind(kind: str, root: pathlib.Path = ROOT) -> Any:
    """The module for one kind of expert (weights, scorer, reference)."""
    return _load(root / "bench" / "experts" / f"{kind}.py",
                 f"bench_expert_{kind}")


def metric_reader(name: str, root: pathlib.Path = ROOT) -> Any:
    """The ``read(run)`` function of one per-layer metric."""
    return _load(root / "bench" / "metrics" / f"{name}.py",
                 f"bench_metric_{name.replace('.', '_')}").read
