"""Infra: optimizer, schedules, compression, checkpoint, fault policy,
data pipeline, sharding specs."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # bare container: seeded fallback
    from _hyp_fallback import given, settings, strategies as st

from repro.checkpoint import (CheckpointManager, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.fault import ElasticController, StepMonitor
from repro.optim import adamw_init, adamw_update, constant, cosine, wsd
from repro.optim.compression import quantize_int8

settings.register_profile("fast", max_examples=25, deadline=None)
settings.load_profile("fast")


# --------------------------- optimizer --------------------------------

def test_adamw_first_step_is_lr_sized():
    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.full((4,), 0.5)}
    st_ = adamw_init(params)
    new, st2 = adamw_update(params, grads, st_, jnp.asarray(0.1))
    # bias-corrected first step ≈ lr * sign(g)
    np.testing.assert_allclose(np.asarray(new["w"]), 1.0 - 0.1, rtol=1e-3)
    assert int(st2.step) == 1


def test_adamw_weight_decay_shrinks():
    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.zeros((4,))}
    st_ = adamw_init(params)
    new, _ = adamw_update(params, grads, st_, jnp.asarray(0.1),
                          weight_decay=0.5)
    assert float(new["w"][0]) < 1.0


def test_grad_clip():
    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.full((4,), 100.0)}
    st_ = adamw_init(params)
    _, st2 = adamw_update(params, grads, st_, jnp.asarray(0.1),
                          grad_clip=1.0)
    # clipped grads: m = (1-b1)*g_clipped, |g_clipped| = 1/2 per element
    assert float(jnp.abs(st2.m["w"]).max()) < 0.06


def test_wsd_schedule_shape():
    f = wsd(1.0, 1000)
    assert float(f(jnp.asarray(0))) < 0.2            # warmup
    assert float(f(jnp.asarray(500))) == 1.0         # stable
    assert float(f(jnp.asarray(999))) < 0.2          # decay
    c = cosine(1.0, 1000, warmup=10)
    assert float(c(jnp.asarray(1000))) <= 0.11


# --------------------------- compression ------------------------------

@given(st.integers(0, 10_000))
def test_int8_quantization_error_bound(seed):
    x = np.random.default_rng(seed).normal(size=(64,)).astype(np.float32)
    q, scale = quantize_int8(jnp.asarray(x))
    deq = np.asarray(q, np.float32) * float(scale)
    assert np.abs(deq - x).max() <= float(scale) * 0.5 + 1e-7


def test_int8_sum_exactness():
    """int32 accumulation of quantized values is exact."""
    x = np.array([1.0, -2.0, 3.0], np.float32)
    q, s = quantize_int8(jnp.asarray(x))
    total = np.asarray(q, np.int32) * 4                 # 4 participants
    deq = total.astype(np.float32) * float(s)
    np.testing.assert_allclose(deq / 4, np.asarray(q, np.float32) * float(s))


# --------------------------- checkpoint -------------------------------

def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
             "nested": {"b": jnp.ones((4,), jnp.bfloat16)},
             "t": (jnp.zeros(()), jnp.ones((2,)))}
    save_checkpoint(str(tmp_path), 7, state)
    assert latest_step(str(tmp_path)) == 7
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        state)
    restored = restore_checkpoint(str(tmp_path), 7, like)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_manager_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), every=1, keep=2)
    state = {"w": jnp.ones((2,))}
    for step in range(5):
        mgr.maybe_save(step, state)
    mgr.finalize()
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path))
    assert steps == [3, 4]


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"w": jnp.ones((2,))})
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


# --------------------------- fault policy -----------------------------

def test_straggler_detection_escalates():
    mon = StepMonitor(n_hosts=1, patience=2)
    for s in range(20):
        mon.record(s, 0, 1.0)
    ev1 = mon.record(20, 0, 3.0)
    assert ev1 and ev1.action == "slack"
    ev2 = mon.record(21, 0, 3.0)
    assert ev2 and ev2.action == "rebalance"
    ev3 = mon.record(22, 0, 100.0)
    assert ev3 and ev3.action == "restart"


def test_healthy_steps_no_events():
    mon = StepMonitor()
    for s in range(50):
        assert mon.record(s, 0, 1.0 + 0.01 * (s % 3)) is None


def test_elastic_shrink():
    ec = ElasticController(data=16, model=16, pods=2)
    assert ec.shrink(0) == (2, 16, 16)
    pods, data, model = ec.shrink(16)     # lose a pod's worth
    assert model == 16 and pods * data * model <= 2 * 16 * 16 - 0
    pods, data, model = ec.shrink(3)      # partial loss -> shrink data
    assert data in (8, 16) and model == 16


def test_shard_remap_covers_dead():
    ec = ElasticController(data=8, model=1)
    remap = ec.shard_remap(8, dead=[2, 5])
    assert set(remap) == {2, 5}
    assert all(t not in (2, 5) for t in remap.values())


# --------------------------- data pipeline ----------------------------

def test_pipeline_determinism_and_shard_disjointness():
    cfg = DataConfig(vocab=1000, seq_len=32, global_batch=8, n_shards=4)
    p1, p2 = TokenPipeline(cfg), TokenPipeline(cfg)
    b1 = p1.shard_batch(5, 2)
    b2 = p2.shard_batch(5, 2)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # different shards/steps differ
    assert not np.array_equal(b1["tokens"], p1.shard_batch(5, 3)["tokens"])
    assert not np.array_equal(b1["tokens"], p1.shard_batch(6, 2)["tokens"])


def test_pipeline_targets_shifted():
    cfg = DataConfig(vocab=50, seq_len=16, global_batch=2)
    b = TokenPipeline(cfg).global_batch(0)
    assert b["tokens"].shape == (2, 16)
    # targets are next-token: overlap check
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])


def test_pipeline_tokens_in_range():
    cfg = DataConfig(vocab=128, seq_len=64, global_batch=4)
    b = TokenPipeline(cfg).global_batch(3)
    assert b["tokens"].min() >= 0 and b["tokens"].max() < 128


# --------------------------- sharding specs ---------------------------

def test_make_pspec_divisibility_fallback():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.sharding.specs import make_pspec
    mesh = make_mesh((1,), ("model",))
    # size-1 axis: everything shards trivially
    assert make_pspec((16, 7), ("mlp", "vocab"), mesh) == P("model", "model")
    mesh1 = make_mesh((1,), ("data",))
    spec = make_pspec((16, 7), ("mlp", None), mesh1)
    assert spec == P(None, None)     # 'model' absent from mesh => replicated
