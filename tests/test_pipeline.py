"""Pipeline parallelism: multi-device equivalence vs sequential stack."""
import subprocess
import sys

import pytest

# Whole-module integration tests: excluded from tier-1 (run nightly / -m slow).
pytestmark = pytest.mark.slow

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.train.pipeline import pipeline_apply, split_stages

S, L, D, N_MICRO, MB = 4, 8, 16, 6, 4
mesh = jax.make_mesh((S,), ("stage",),
                     axis_types=(jax.sharding.AxisType.Auto,))
key = jax.random.PRNGKey(0)
Ws = jax.random.normal(key, (L, D, D)) * (1.0 / np.sqrt(D))

def block_fn(params, x):
    # params: [L/S, D, D]; apply the stage's layers sequentially.
    def body(h, w):
        return jnp.tanh(h @ w), None
    out, _ = jax.lax.scan(body, x, params)
    return out

x = jax.random.normal(jax.random.PRNGKey(1), (N_MICRO, MB, D))
stage_params = split_stages(Ws, S)
got = pipeline_apply(block_fn, stage_params, x, mesh=mesh)

# Sequential reference: all L layers over each microbatch.
def seq(x1):
    h = x1
    for i in range(L):
        h = jnp.tanh(h @ Ws[i])
    return h
ref = jax.vmap(seq)(x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)

# Gradients flow through the pipeline schedule (backward pipeline).
def loss(sp):
    return jnp.sum(pipeline_apply(block_fn, sp, x, mesh=mesh) ** 2)
g = jax.grad(loss)(stage_params)
def loss_ref(w):
    h = x
    def seq2(x1):
        h = x1
        for i in range(L):
            h = jnp.tanh(h @ w[i])
        return h
    return jnp.sum(jax.vmap(seq2)(x) ** 2)
g_ref = split_stages(jax.grad(loss_ref)(Ws), S)
np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                           rtol=1e-4, atol=1e-4)
print("PIPELINE-OK")
"""


def test_pipeline_multi_device_equivalence():
    r = subprocess.run([sys.executable, "-c", _SCRIPT],
                       capture_output=True, text=True, timeout=500,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "PIPELINE-OK" in r.stdout, (r.stdout[-1000:], r.stderr[-2000:])
