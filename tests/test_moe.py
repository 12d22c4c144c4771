"""MoE dispatch: oracle equivalence, capacity dropping, gradients,
and the multi-device shard_map path (subprocess with 8 host devices)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe

# Whole-module integration tests: excluded from tier-1 (run nightly / -m slow).
pytestmark = pytest.mark.slow


def _setup(E=8, k=2, d=16, ff=32, B=2, S=16, seed=0):
    params = moe.init_moe(jax.random.PRNGKey(seed), d, ff, E)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, d),
                          jnp.float32)
    return params, x


def test_local_matches_dense_oracle_no_drops():
    params, x = _setup()
    out1 = moe.moe_ffn(params, x, k=2, num_experts=8, capacity_factor=8.0)
    out2 = moe.moe_ffn_dense(params, x, k=2, num_experts=8)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=2e-3, atol=2e-3)


def test_capacity_drops_tokens():
    params, x = _setup(B=1, S=64)
    full = moe.moe_ffn(params, x, k=2, num_experts=8, capacity_factor=8.0)
    tight = moe.moe_ffn(params, x, k=2, num_experts=8,
                        capacity_factor=0.25)
    # Dropping changes outputs but keeps them finite.
    assert np.isfinite(np.asarray(tight)).all()
    assert not np.allclose(np.asarray(full), np.asarray(tight))


def test_router_normalizes_topk():
    params, x = _setup()
    w, ids = moe._router(params["router"], x.reshape(-1, 16), 2)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    assert int(ids.max()) < 8


def test_gradients_flow_to_all_weight_kinds():
    params, x = _setup()

    def loss(p):
        return jnp.sum(moe.moe_ffn(p, x, k=2, num_experts=8,
                                   capacity_factor=8.0) ** 2)

    g = jax.grad(loss)(params)
    for key in ("router", "w_gate", "w_up", "w_down"):
        leaf_sum = jax.tree_util.tree_reduce(
            lambda a, b: a + float(jnp.sum(jnp.abs(b))), g[key], 0.0)
        assert leaf_sum > 0, key


_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.models import moe
from repro.models.sharding_ctx import ShardingCtx

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
params = moe.init_moe(jax.random.PRNGKey(0), 16, 32, 8)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16), jnp.float32)
ctx = ShardingCtx({}, mesh)
out_sharded = moe.moe_ffn(params, x, k=2, num_experts=8,
                          capacity_factor=8.0, ctx=ctx)
out_local = moe.moe_ffn(params, x, k=2, num_experts=8, capacity_factor=8.0)
np.testing.assert_allclose(np.asarray(out_sharded), np.asarray(out_local),
                           rtol=2e-3, atol=2e-3)
# gradient parity through shard_map
def loss_sharded(p):
    return jnp.sum(moe.moe_ffn(p, x, k=2, num_experts=8,
                               capacity_factor=8.0, ctx=ctx) ** 2)
def loss_local(p):
    return jnp.sum(moe.moe_ffn(p, x, k=2, num_experts=8,
                               capacity_factor=8.0) ** 2)
gs = jax.grad(loss_sharded)(params)
gl = jax.grad(loss_local)(params)
for k2 in ("w_gate", "w_down"):
    np.testing.assert_allclose(np.asarray(gs[k2]), np.asarray(gl[k2]),
                               rtol=5e-3, atol=5e-3)
print("SHARDED-MOE-OK")
"""


def test_shard_map_moe_multi_device():
    r = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                       capture_output=True, text=True, timeout=500,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "SHARDED-MOE-OK" in r.stdout, r.stderr[-2000:]
