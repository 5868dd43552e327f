"""FLOP and byte counters against values worked out by hand."""
import json
import pathlib

import pytest

from bench.common import harness, registry

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _expert(config: str, name: str) -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    return {e["name"]: e for e in cfg["experts"]}[name]


def test_internlm2_flops_per_event_at_published_widths():
    spec = _expert("fleet48-internlm2-1.8b", "big")
    # per token and layer: q 2048*2048, k and v 2048*1024 each, o
    # 2048*2048, SwiGLU 3*2048*8192 = 62,914,560 matmul weights
    per_layer_weights = 4_194_304 * 2 + 2 * 2_097_152 + 50_331_648
    assert per_layer_weights == 62_914_560
    # 24 layers * 1.51e9 weights * 2 FLOPs * 32 tokens, plus causal
    # attention 4 * 16 heads * 128 * (32*33/2) per layer, plus the head
    want = 24 * (2 * per_layer_weights * 32 + 4 * 16 * 128 * 528) + 2 * 2048
    assert want == 96_740_577_280
    kind = registry.expert_kind("internlm2")
    assert kind.flops_per_event(spec) == want


def test_logistic_and_transform_counts():
    kind = registry.expert_kind("logistic")
    assert kind.flops_per_event(_expert("tabular512-baf", "lin0")) == 60
    # K=3, 128 knots: 6*3 + 7 (binary search) + 5
    assert harness.transform_flops(3, 128) == 30
    # 1024 rows, 3 experts, 250 distinct bank rows of 2*3 + 2*128 floats
    assert harness.transform_bytes(1024, 250, 3, 128) == \
        1024 * 4 * 4 + 1024 * 4 + 250 * 262 * 4


def test_shares_never_use_a_guessed_peak():
    from bench.common import peaks

    assert peaks.peaks_for("TPU v5 lite") == {"flops_per_s": 197e12,
                                              "hbm_bytes_per_s": 819e9}
    with pytest.raises(LookupError):
        peaks.peaks_for("cpu")
