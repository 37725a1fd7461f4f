"""Multi-device integration tests — run in a subprocess with 8 virtual
devices via the shared tests/_multidev.py runner (XLA device count locks at
first jax import, so these cannot share the main pytest process)."""

import pytest

from _multidev import run_multidev

SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
assert jax.device_count() == 8
from repro.configs.base import get_config, reduced
from repro.launch.mesh import make_mesh
from repro.models.lm.model import build_lm
from repro.sharding.specs import mesh_context
from repro.train import lm_step

mesh_mp = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = reduced(get_config("qwen3-0.6b"))
lm = build_lm(cfg, tp=2)
batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
         "targets": jnp.ones((8, 32), jnp.int32)}

with mesh_context(mesh_mp), mesh_mp:
    state = lm_step.init_train_state(lm, jax.random.PRNGKey(0))
    plain = jax.jit(lm_step.make_train_step(lm, total_steps=10))
    s1, m1 = plain(state, batch)
    comp = jax.jit(lm_step.make_train_step(lm, total_steps=10,
                                           compress_pod_grads=True))
    s2, m2 = comp(state, batch)

l1, l2 = float(m1["loss"]), float(m2["loss"])
assert abs(l1 - l2) < 1e-3, (l1, l2)
# int8-compressed grads: params close but not identical to exact path
d = max(float(jnp.abs(a - b).max())
        for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)))
assert d < 5e-3, d
print("COMPRESSED_OK", l1, l2, d)

# sequence-sharded decode (shard_map flash-decode) vs single-device oracle
from repro.models.lm import serve
params = lm.init(jax.random.PRNGKey(0))
tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (2, 16)).astype(np.int32))
with mesh_context(None):
    cache0, logits0 = serve.prefill(lm, params, tokens, None)
    nc0, d0 = serve.decode_step(lm, params, cache0, tokens[:, -1:],
                                jnp.asarray(15, jnp.int32))
with mesh_context(mesh_mp), mesh_mp:
    cache1, logits1 = serve.prefill(lm, params, tokens, None)
    nc1, d1 = serve.decode_step(lm, params, cache1, tokens[:, -1:],
                                jnp.asarray(15, jnp.int32))
err = float(jnp.abs(d0 - d1).max())
assert err < 2e-3, err
print("SHARDED_DECODE_OK", err)

# elastic restore across mesh shapes
import tempfile
from repro.checkpoint import save_checkpoint, restore_checkpoint
tmp = tempfile.mkdtemp()
with mesh_context(mesh_mp), mesh_mp:
    save_checkpoint(tmp, 0, state)
mesh_small = make_mesh((4, 2), ("data", "model"))
lm2 = build_lm(cfg, tp=2)
with mesh_context(mesh_small), mesh_small:
    shardings = lm_step.train_state_shardings(lm2, mesh_small)
    restored = restore_checkpoint(tmp, 0, state, shardings=shardings)
ok = all(np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
         for a, b in zip(jax.tree.leaves(state.params),
                         jax.tree.leaves(restored.params)))
assert ok
print("ELASTIC_OK")
"""


@pytest.mark.slow
def test_multidevice_subprocess():
    run_multidev(SCRIPT, n_devices=8,
                 expect=("COMPRESSED_OK", "SHARDED_DECODE_OK", "ELASTIC_OK"))
