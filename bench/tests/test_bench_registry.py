"""Configurations, traffic mixes and metric readers are found by name, so
a later change adds one with new files and entries only."""
import json
import shutil

import numpy as np
import pytest
from conftest import run_smoke

from bench.common import registry, traffic


def test_every_cell_resolves_with_its_metrics():
    bench = registry.benchmark()
    for w in bench["workloads"]:
        cell = registry.cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(registry.metric_reader(m["name"]))
            assert m["moves"] in names
        for e in cell.config["experts"]:
            kind = registry.expert_kind(e["kind"])
            for fn in ("init", "program_score_fn", "reference",
                       "flops_per_event"):
                assert callable(getattr(kind, fn))


def test_a_new_mix_config_and_metric_are_files_and_entries(tmp_path):
    """A mix with on/off bursts, a deployment of another layout and a
    metric reader, added as files and entries only, resolve and run."""
    root = tmp_path
    shutil.copytree(registry.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = registry.benchmark()
    (root / "bench" / "traffic" / "bursty.json").write_text(json.dumps(
        {"loop": "open", "rate": 400.0, "refresh_every_s": 0.25,
         "warm_events": 16,
         "profile": {"period_s": 2.0, "segments": [[0.0, 0.2, 3.0]]}}))
    (root / "bench" / "metrics" / "late_share.py").write_text(
        "def read(run):\n    return 42.0\n")
    cfg = json.loads((registry.ROOT / "bench" / "configs"
                      / "tabular512-baf.json").read_text())
    cfg.update(name="split64-baf", groups=[
        {"prefix": "a", "tenants": 6, "experts": ["lin0", "lin1", "lin2"],
         "predictor": "own", "share": 0.5, "zipf_s": 1.1},
        {"prefix": "b", "tenants": 3, "experts": ["lin0"],
         "predictor": "shared-b", "share": 0.5, "zipf_s": 0.0}])
    cfg["engine"] = dict(cfg["engine"], max_batch=4, adaptive_batch_cap=8)
    cfg["check"] = dict(cfg["check"], expert_sample=16)
    (root / "bench" / "configs" / "split64-baf.json").write_text(
        json.dumps(cfg))
    bench["configs"].append({
        "name": "split64-baf", "source": "https://example.org",
        "file": "bench/configs/split64-baf.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({"name": "split64.bursty",
                               "config": "split64-baf",
                               "traffic": "bursty", "chips": 1,
                               "why": "a test"})
    bench["end_to_end"].append({
        "name": "p50_ms", "unit": "ms", "better": "lower", "bound": 0.1,
        "source": "host_clock", "workloads": ["split64.bursty"]})
    bench["per_layer"].append({
        "name": "late_share", "unit": "%", "better": "lower",
        "source": "host_clock", "layer": "engine", "moves": "p50_ms",
        "workloads": ["split64.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = registry.cell("split64.bursty", root)
    assert [g["prefix"] for g in cell.config["groups"]] == ["a", "b"]
    assert [m["name"] for m in cell.per_layer] == ["late_share"]
    assert registry.metric_reader("late_share", root)(None) == 42.0
    with pytest.raises(KeyError):
        registry.cell("no-such-cell", root)

    # the bursts are in the arrivals: 3x the rate for 0.2 s of every 2 s
    offsets = traffic.arrival_offsets(cell.traffic, 1000.0, 20.0,
                                      np.random.default_rng(0))
    assert len(offsets) == 24_000      # 10 periods of 0.2 * 3 + 1.8 s
    in_burst = np.mean(np.mod(offsets, 2.0) < 0.2)
    assert in_burst == pytest.approx(0.6 / 2.4, abs=0.02)

    # and the cell runs end to end, its layout deployed from the data
    result = run_smoke(cell, seconds=0.6)
    assert result["correct"], result["checks"]
    assert set(result["info"]) >= {"refresh_passes", "window_compiles"}
    assert result["info"]["window_compiles"] == 0
