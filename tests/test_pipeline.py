"""Pipeline parallelism (GPipe over a 'stage' axis) — subprocess with 8
virtual devices; forward AND gradient must match the sequential oracle."""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.sharding.pipeline import pipeline_apply, sequential_reference

mesh = make_mesh((4,), ("stage",))
S, M, MB, D = 4, 6, 2, 16
rng = np.random.default_rng(0)
params = {"w": jnp.asarray(rng.normal(size=(S, D, D)).astype(np.float32) * 0.3),
          "b": jnp.asarray(rng.normal(size=(S, D)).astype(np.float32) * 0.1)}
x = jnp.asarray(rng.normal(size=(M, MB, D)).astype(np.float32))

def stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])

with mesh:
    y = pipeline_apply(mesh, stage_fn, params, x)
y_ref = sequential_reference(stage_fn, params, x)
err = float(jnp.abs(y - y_ref).max())
assert err < 1e-5, err
print("PIPELINE_FWD_OK", err)

def loss_pipe(p):
    with mesh:
        return jnp.sum(pipeline_apply(mesh, stage_fn, p, x) ** 2)

def loss_ref(p):
    return jnp.sum(sequential_reference(stage_fn, p, x) ** 2)

g1 = jax.grad(loss_pipe)(params)
g2 = jax.grad(loss_ref)(params)
gerr = max(float(jnp.abs(a - b).max()) for a, b in
           zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
assert gerr < 1e-4, gerr
print("PIPELINE_BWD_OK", gerr)
"""


@pytest.mark.slow
def test_pipeline_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PIPELINE_FWD_OK" in r.stdout and "PIPELINE_BWD_OK" in r.stdout
