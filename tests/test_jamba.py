"""The Jamba expert at a small size on the CPU: the program's forward
against the plain reference of ``bench/experts/jamba.py``, the
selective-scan kernel (interpret mode) against a sequential scan, its
gradient, and attention without positional encoding."""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.selective_scan import selective_scan
from repro.models import attention, mamba as mamba_mod
from repro.models.config import ModelConfig
from repro.models.model import Model

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.common import registry  # noqa: E402

T = 24
SPEC = dict(kind="jamba", num_hidden_layers=8, hidden_size=128,
            num_attention_heads=4, num_key_value_heads=1,
            intermediate_size=256, vocab_size=512, attn_layer_period=4,
            attn_layer_offset=2, mamba_d_state=16, mamba_d_conv=4,
            mamba_expand=2, mamba_dt_rank=8, rms_norm_eps=1e-6,
            tokens_per_event=T, dtype="float32")
# Both sides run float32; they differ in summation order (the program's
# chunked attention and fused matmuls, the kernel's per-step sums), which
# moves a score by ~1e-6 and a logit by ~3e-6 at this size.  The bfloat16
# control reads ~1.5e-2 on the scores, so these tolerances are four orders
# of magnitude tighter than one precision down.
SCORE_ATOL = 2e-5
LOGIT_ATOL = 5e-5


@pytest.fixture(scope="module")
def drawn():
    kind = registry.expert_kind("jamba")
    params = jax.jit(lambda k: kind.init(k, SPEC))(jax.random.key(5))
    feats = np.random.default_rng(5).normal(0, 1, (12, T)).astype(np.float32)
    want = kind.reference(SPEC, params, feats, block=8)
    return kind, params, feats, want


def _program(kind, params, feats, cfg=None):
    model = Model(cfg or kind.model_config(SPEC))
    out = model.forward(params, tokens=jnp.asarray(kind.tokens(feats, SPEC)),
                        compute_dtype=jnp.float32, logits_mode="last")
    return np.asarray(out.risk_score), np.asarray(out.logits[:, 0])


def test_weights_have_the_programs_layout(drawn):
    kind, params, _, _ = drawn
    model = Model(kind.model_config(SPEC))
    want = jax.eval_shape(lambda k: model.init(k, jnp.float32),
                          jax.random.key(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert [a.shape for a in jax.tree.leaves(params)] == \
        [a.shape for a in jax.tree.leaves(want)]
    pattern = [b.mixer for b in model.cfg.layer_pattern]
    assert pattern == ["mamba", "mamba", "attn", "mamba"]


def test_forward_matches_reference(drawn):
    kind, params, feats, want = drawn
    score, logits = _program(kind, params, feats)
    assert want.std() > 0.05
    np.testing.assert_allclose(score, want, rtol=0, atol=SCORE_ATOL)
    want_logits = kind.reference_logits(SPEC, params, feats, block=8)
    np.testing.assert_allclose(logits, want_logits, rtol=0, atol=LOGIT_ATOL)


def test_the_bfloat16_control_reads_far_off(drawn):
    kind, params, feats, want = drawn
    for precision in ("bfloat16", "float8_e4m3fn"):
        got = kind.reference(SPEC, params, feats, precision, block=8)
        assert np.max(np.abs(got - want)) > 100 * SCORE_ATOL


@pytest.mark.parametrize("mutation", ["inner_norms", "d_skip", "gate"])
def test_a_program_missing_part_of_the_mixer_fails(drawn, mutation,
                                                   monkeypatch):
    kind, params, feats, want = drawn
    cfg = kind.model_config(SPEC)
    if mutation == "inner_norms":
        cfg = dataclasses.replace(cfg, mamba=dataclasses.replace(
            cfg.mamba, inner_norms=False))
    elif mutation == "d_skip":
        params = jax.tree_util.tree_map_with_path(
            lambda p, a: jnp.zeros_like(a)
            if jax.tree_util.keystr(p).endswith("['D']") else a, params)
    else:
        monkeypatch.setattr(mamba_mod, "_gated", lambda y, z: y)
    score, _ = _program(kind, params, feats, cfg)
    assert np.max(np.abs(score - want)) > 100 * SCORE_ATOL


# ------------------------------------------------------------------ kernel
def _scan_inputs(bsz, t, c, n=16, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    return (jax.nn.softplus(jax.random.normal(k[0], (bsz, t, c)) - 2),
            jax.random.normal(k[1], (bsz, t, c)),
            jax.random.normal(k[2], (bsz, t, n)),
            jax.random.normal(k[3], (bsz, t, n)),
            -jnp.exp(0.5 * jax.random.normal(k[4], (c, n)) + 1.0),
            jax.random.normal(k[5], (c,)),
            jax.random.normal(k[6], (bsz, c, n)))


@pytest.mark.parametrize("bsz,t,c", [
    (2, 24, 256),      # one time block of 24 steps, one channel block
    (1, 21, 128),      # T padded to the unrolled steps
    (2, 200, 256),     # two 128-step time blocks, the second partial
    (1, 16, 3072),     # three channel blocks of 1,024
    (1, 9, 200),       # channels padded to the 128 lanes
])
def test_selective_scan_matches_a_sequential_scan(bsz, t, c):
    args = _scan_inputs(bsz, t, c)
    y, h = selective_scan(*args, True)
    y_ref, h_ref = ref.selective_scan(*args)
    assert y.shape == (bsz, t, c) and h.shape == (bsz, c, 16)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(h, h_ref, rtol=1e-5, atol=1e-5)


def test_selective_scan_of_a_batch_padded_to_a_bucket():
    """Rows padded with zeros (a window below its bucket) leave the real
    rows' outputs as they are, and their own outputs and state stay at
    zero."""
    dt, x, b, c, a, d, h0 = _scan_inputs(3, T, 256)
    padded = [jnp.concatenate([v, jnp.zeros((1,) + v.shape[1:], v.dtype)])
              for v in (dt, x, b, c)]
    h0_pad = jnp.concatenate([h0, jnp.zeros((1,) + h0.shape[1:])])
    y, h = selective_scan(*padded, a, d, h0_pad, True)
    y_ref, h_ref = ref.selective_scan(dt, x, b, c, a, d, h0)
    np.testing.assert_allclose(y[:3], y_ref, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(h[:3], h_ref, rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(y[3])) and not np.any(np.asarray(h[3]))


def test_gradient_through_mamba_forward_is_the_plain_scans(monkeypatch):
    kind = registry.expert_kind("jamba")
    cfg = kind.model_config(SPEC)
    params = mamba_mod.init_mamba(jax.random.key(3), SPEC["hidden_size"],
                                  cfg.mamba)
    x = jax.random.normal(jax.random.key(4), (2, T, SPEC["hidden_size"]))

    def loss(p, xx):
        out, _ = mamba_mod.mamba_forward(p, xx, cfg.mamba,
                                         eps=cfg.norm_eps)
        return jnp.sum(out * jnp.cos(out))

    got = jax.grad(loss, argnums=(0, 1))(params, x)
    monkeypatch.setattr(mamba_mod, "selective_scan",
                        lambda *a: ref.selective_scan(*a))
    want = jax.grad(loss, argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------- attention
@pytest.mark.parametrize("rope", [False, True])
def test_attention_without_positions_ignores_the_order_of_earlier_tokens(
        rope):
    """The last query of a causal layer with no positional encoding reads
    its keys as a set: rotating the earlier tokens leaves its output as it
    is.  With RoPE the same rotation moves every relative position."""
    cfg = ModelConfig(name="t", arch_type="hybrid", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=1, d_ff=128, vocab_size=64,
                      rope=rope)
    params = attention.init_attention(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (1, 12, 64))
    rotated = jnp.concatenate([jnp.roll(x[:, :-1], 3, axis=1), x[:, -1:]], 1)
    angles = Model(cfg)._angles(1, 12, 0, None)
    assert (angles is None) == (not rope)
    last = [attention.attention_forward(params, v, cfg,
                                        angles=angles)[0][:, -1]
            for v in (x, rotated)]
    gap = float(jnp.max(jnp.abs(last[0] - last[1])))
    if rope:
        assert gap > 1e-3
    else:
        assert gap < 1e-5
