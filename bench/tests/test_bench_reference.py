"""The plain internlm2 reference against the program's forward, on the
CPU at smoke widths, and the control one precision below."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.common import registry
from conftest import SMOKE_INTERNLM2

SPEC = dict(kind="internlm2", rope_theta=1_000_000.0, rms_norm_eps=1e-5,
            tokens_per_event=32, dtype="float32", **SMOKE_INTERNLM2)


@pytest.fixture(scope="module")
def drawn():
    kind = registry.expert_kind("internlm2")
    params = jax.jit(lambda k: kind.init(k, SPEC))(jax.random.key(5))
    feats = np.random.default_rng(5).normal(0, 1, (24, 32)).astype(np.float32)
    return kind, params, feats


def test_weights_have_the_programs_layout(drawn):
    from repro.models.model import Model

    kind, params, _ = drawn
    model = Model(kind.model_config(SPEC))
    want = jax.eval_shape(lambda k: model.init(k, jnp.float32),
                          jax.random.key(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert [a.shape for a in jax.tree.leaves(params)] == \
        [a.shape for a in jax.tree.leaves(want)]


def test_reference_matches_model_forward(drawn):
    from repro.models.model import Model

    kind, params, feats = drawn
    model = Model(kind.model_config(SPEC))
    got = np.asarray(model.forward(
        params, tokens=jnp.asarray(kind.tokens(feats, SPEC)),
        compute_dtype=jnp.float32, logits_mode="last").risk_score)
    want = kind.reference(SPEC, params, feats, block=16)
    assert got.std() > 0.01
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_control_reads_further_off(drawn):
    kind, params, feats = drawn
    want = kind.reference(SPEC, params, feats)
    for precision in ("bfloat16", "float8_e4m3fn"):
        got = kind.reference(SPEC, params, feats, precision)
        assert np.max(np.abs(got - want)) > 1e-4
