"""The Jamba cell: its configuration, a CPU rehearsal at smoke widths, the
expert's counts at the published widths, and the two selective-scan
readers on hand-made device traces."""
import json
import types

import pytest
from conftest import CPU_PEAKS, run_smoke, smoke_cell

from bench.common import registry
from bench.common.trace import DeviceOp

CELL = "fleet48-jamba2.steady"
CONFIG = "fleet48-jamba2-3b"
SMOKE_JAMBA = dict(num_hidden_layers=4, hidden_size=128,
                   num_attention_heads=4, num_key_value_heads=1,
                   intermediate_size=256, vocab_size=512,
                   attn_layer_period=4, attn_layer_offset=2,
                   mamba_dt_rank=8, tokens_per_event=16)


def _published():
    cfg = json.loads((registry.ROOT / "bench" / "configs"
                      / f"{CONFIG}.json").read_text())
    return cfg, next(e for e in cfg["experts"] if e["kind"] == "jamba")


def test_the_expert_is_the_published_model():
    """The expert runs the sizes the configuration copies from the model's
    config, all 28 layers at full width, with attention at 7 and 21."""
    cfg, spec = _published()
    for key, value in spec.items():
        if key in cfg and key != "name":
            assert cfg[key] == value, key
    assert cfg["num_experts"] == 1 and cfg["tie_word_embeddings"]
    kind = registry.expert_kind("jamba")
    model_cfg = kind.model_config(spec)
    mixers = [b.mixer for b in model_cfg.layer_pattern] * model_cfg.n_groups
    assert [i for i, m in enumerate(mixers) if m == "attn"] == [7, 21]
    assert model_cfg.head_dim == 128 and model_cfg.mamba.dt_rank == 160
    assert not model_cfg.rope and model_cfg.mamba.inner_norms
    assert 3.0e9 < model_cfg.param_count() < 3.1e9


def test_a_rehearsal_is_correct_and_stamps_the_model_bucket():
    cell = smoke_cell(CELL, CONFIG, "steady")
    for expert in cell.config["experts"]:
        if expert["kind"] == "jamba":
            expert.update(SMOKE_JAMBA)
    engines = []
    result = run_smoke(cell, tamper=lambda dep, eng, ctl: engines.append(eng))
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["info"]["window_compiles"] == 0
    assert result["info"]["stage_errors"] == 0
    assert {m["name"] for m in cell.per_layer} >= {"ssm_scan_roofline",
                                                   "ssm_scan_share"}
    windows = list(engines[0].window_log)
    assert windows
    for w in windows:
        bucket = w["model_bucket"]
        assert bucket >= w["size"] and bucket & (bucket - 1) == 0
        assert bucket < 2 * w["size"]


def test_counts_at_the_published_widths():
    _, spec = _published()
    kind = registry.expert_kind("jamba")
    t = 128
    d, d_in, n, rank, ff = 2560, 5120, 16, 160, 8192
    mamba = (2 * d * 2 * d_in + 2 * d_in * (rank + 2 * n) + 2 * rank * d_in
             + 2 * d_in * d + 2 * 3 * d * ff)
    attn = 2 * d * 2560 + 2 * 2 * d * 128 + 2 * 2560 * d + 2 * 3 * d * ff
    scores = 2 * 2 * 20 * 128 * (t * (t + 1) // 2)
    scan = 26 * t * d_in * (7 * n + 3)
    want = 26 * mamba * t + 2 * (attn * t + scores) + scan + 2 * d
    assert kind.flops_per_event(spec) == want
    assert kind.scan_flops(spec, t) == scan
    assert kind.scan_bytes(spec, t) == 26 * t * (3 * d_in + 2 * n) * 4
    # ~0.73 TFLOP per event; ~7.9 MB per Mamba layer and event
    assert 0.73e12 < want < 0.74e12
    assert kind.scan_bytes(spec, t) / 26 == pytest.approx(7.88e6, rel=1e-3)


def _run(ops, windows):
    _, spec = _published()
    return types.SimpleNamespace(
        device_ops={"/device:TPU:0": ops}, trace_window=(0.0, 1e9),
        cell=types.SimpleNamespace(chips=1), windows=windows,
        dep=types.SimpleNamespace(experts={
            "big": spec, "lin": {"kind": "logistic"}}),
        peaks=dict(CPU_PEAKS, flops_per_s=197e12, hbm_bytes_per_s=819e9))


def _op(name, start, dur, module="jit_jamba_score", kernel=False):
    return DeviceOp(name, module, float(start), float(dur), kernel)


WINDOWS = [{"key": "big+lin", "size": 3}, {"key": "lin", "size": 5},
           {"key": "big+lin", "size": 1}]


def test_scan_readers_on_a_device_trace():
    roofline = registry.metric_reader("ssm_scan_roofline")
    share = registry.metric_reader("ssm_scan_share")
    _, spec = _published()
    kind = registry.expert_kind("jamba")
    ops = [_op("fusion.1", 0, 6e6), _op("selective_scan.3", 6e6, 2e6,
                                        kernel=True),
           _op("selective_scan.3", 9e6, 2e6, kernel=True),
           _op("fusion.7", 20e6, 5e6, module="jit_score_pipeline_banked"),
           _op("score.1", 30e6, 1e6, module="jit_score_pipeline_banked",
               kernel=True)]
    run = _run(ops, WINDOWS)
    # 4 Jamba events of 128 tokens; bytes bound the least time
    least = kind.scan_bytes(spec, 4 * 128) / 819e9
    assert least > kind.scan_flops(spec, 4 * 128) / 197e12
    assert roofline(run) == pytest.approx(100 * least / 4e-3)
    assert share(run) == pytest.approx(100 * 4e6 / 10e6)


@pytest.mark.parametrize("missing", ["kernel", "windows", "trace"])
def test_scan_readers_read_nothing_without_their_inputs(missing):
    roofline = registry.metric_reader("ssm_scan_roofline")
    share = registry.metric_reader("ssm_scan_share")
    ops = [_op("fusion.1", 0, 6e6),
           _op("selective_scan.3", 6e6, 2e6, kernel=True)]
    windows = WINDOWS
    if missing == "kernel":
        ops = ops[:1]
    elif missing == "windows":
        windows = [{"key": "lin", "size": 5}]
    run = _run(ops, windows)
    if missing == "trace":
        run.device_ops = None
    assert roofline(run) is None
    if missing != "windows":
        assert share(run) is None
