"""Correctness of the shard_map all-to-all MoE vs the einsum oracle.

Runs in a subprocess so XLA can be forced to 4 host devices (the main test
process keeps the default 1-device config).
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.models.config import MoEConfig
from repro.models import moe as moe_mod
from repro.models.moe_a2a import moe_forward_a2a

mo = MoEConfig(num_experts=4, top_k=2, d_ff_expert=32,
               capacity_factor=2.0)  # E/top_k: drop-free
d = 16
params = moe_mod.init_moe(jax.random.key(0), d, mo)
x = jax.random.normal(jax.random.key(1), (2, 8, d), jnp.float32)

ref, aux_ref = moe_mod.moe_forward(params, x, mo)

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with jax.set_mesh(mesh):
    got, aux = jax.jit(
        lambda p, xx: moe_forward_a2a(p, xx, mo)
    )(params, x)

np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
# aux definitions match (same f, p statistics)
np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)

# gradient path through shard_map (train viability)
def loss(p):
    out, aux2 = moe_forward_a2a(p, x, mo)
    return jnp.sum(out**2) + 0.01 * aux2
with jax.set_mesh(mesh):
    g = jax.jit(jax.grad(loss))(params)
gn = sum(float(jnp.sum(jnp.abs(t))) for t in jax.tree.leaves(g))
assert np.isfinite(gn) and gn > 0
print("A2A_MOE_OK")
"""


@pytest.mark.parametrize("dummy", [0])
def test_a2a_matches_einsum_oracle(dummy):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "A2A_MOE_OK" in res.stdout, (
        f"stdout:\n{res.stdout}\nstderr:\n{res.stderr[-3000:]}"
    )
