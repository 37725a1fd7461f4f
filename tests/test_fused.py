"""Parity + packing tests for the fused single-dispatch DR-SpMM executors.

Both executors of the fused arena ("pallas_fused", "xla_fused") must match
the dense oracle (kernels/ref.py) — forward and gradient, on the arena tier
and on the dense tier — across the degree distributions that stress the
packing: empty buckets, single evil rows, all-rows-one-bucket, non-divisible
row counts, and the empty matrix.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # bare container: seeded fallback
    from _hyp_fallback import given, settings, strategies as st

from repro.core.cbsr import cbsr_from_dense
from repro.core.drelu import drelu
from _dispatch import dispatch_count
from repro.graphs.ell import (DENSE_TIER_NNZ, fuse_bucketed, pack_ell,
                              pack_ell_pair, pack_fused, ROW_BLOCK)
from repro.kernels import ops, ref

settings.register_profile("fast", max_examples=25, deadline=None)
settings.load_profile("fast")

BACKENDS = ("pallas_fused", "xla_fused")
# the op's dense-tier crossover, and -1: every relation on the arena tier
TIERS = {"dense": DENSE_TIER_NNZ, "arena": -1}


def _assert_close(actual, ref, msg):
    """≤1e-5 agreement at the reference's scale (f32 accumulation-order
    noise grows with |ref| — a raw atol would fail even xla-vs-dense)."""
    atol = 1e-5 * max(1.0, float(np.abs(ref).max()) if ref.size else 1.0)
    np.testing.assert_allclose(actual, ref, atol=atol, rtol=1e-5,
                               err_msg=msg)


def _coo(name, rng):
    """Named degree distributions that stress the bucketing."""
    if name == "empty":
        return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros(0, np.float32), 11, 9)
    if name == "evil_row":
        # one row holding most of the nnz (lands alone in a wide bucket,
        # leaving intermediate buckets empty), plus a sparse bulk
        n_dst, n_src = 24, 40
        dst = np.concatenate([np.zeros(37, np.int64),
                              rng.integers(0, n_dst, 30)])
        src = np.concatenate([np.arange(37) % n_src,
                              rng.integers(0, n_src, 30)])
    elif name == "one_bucket":
        # every row has degree 3 → a single bucket, row count not a
        # multiple of ROW_BLOCK
        n_dst, n_src = ROW_BLOCK * 2 + 3, 7
        dst = np.repeat(np.arange(n_dst), 3)
        src = rng.integers(0, n_src, dst.size)
    elif name == "mixed":
        n_dst, n_src = 61, 53
        deg = rng.integers(1, 70, n_dst)
        dst = np.repeat(np.arange(n_dst), deg)
        src = rng.integers(0, n_src, dst.size)
    else:
        raise ValueError(name)
    pairs = np.unique(np.stack([dst, src], 1), axis=0)
    w = rng.normal(size=pairs.shape[0]).astype(np.float32)
    return pairs[:, 0], pairs[:, 1], w, n_dst, n_src


@pytest.mark.parametrize("dist", ["empty", "evil_row", "one_bucket", "mixed"])
@pytest.mark.parametrize("dim", [64, 256])
def test_drspmm_backend_parity(dist, dim, monkeypatch):
    rng = np.random.default_rng(hash(dist) % 2 ** 31)
    dst, src, w, n_dst, n_src = _coo(dist, rng)
    adj, adj_t = pack_ell_pair(dst, src, w, n_dst, n_src)
    k = 8
    x = rng.normal(size=(n_src, dim)).astype(np.float32)
    c = cbsr_from_dense(drelu(jnp.asarray(x), k), k)

    y_ref = np.asarray(ref.drspmm_fwd_ref(adj, c.values, c.idx, dim))
    g_ref = np.asarray(jax.grad(lambda v: jnp.sum(ref.drspmm_fwd_ref(
        adj, v, c.idx, dim) ** 2))(c.values))
    for tier, thr in TIERS.items():
        monkeypatch.setattr(ops, "DENSE_TIER_NNZ", thr)
        for be in BACKENDS:
            y = np.asarray(ops.drspmm(adj, adj_t, c.values, c.idx, dim,
                                      backend=be))
            _assert_close(y, y_ref, f"fwd {be}/{tier}/{dist}/d{dim}")
            g = np.asarray(jax.grad(lambda v: jnp.sum(ops.drspmm(
                adj, adj_t, v, c.idx, dim, backend=be) ** 2))(c.values))
            _assert_close(g, g_ref, f"grad {be}/{tier}/{dist}/d{dim}")


@pytest.mark.parametrize("dist", ["empty", "evil_row", "one_bucket", "mixed"])
def test_spmm_backend_parity(dist):
    rng = np.random.default_rng(hash(dist) % 2 ** 31)
    dst, src, w, n_dst, n_src = _coo(dist, rng)
    adj, adj_t = pack_ell_pair(dst, src, w, n_dst, n_src)
    x = jnp.asarray(rng.normal(size=(n_src, 64)).astype(np.float32))
    y_ref = np.asarray(ref.spmm_dense_ref(adj, x))
    g_ref = np.asarray(jax.grad(lambda v: jnp.sum(ref.spmm_dense_ref(
        adj, v) ** 2))(x))
    for be in BACKENDS:
        y = np.asarray(ops.spmm(adj, adj_t, x, backend=be))
        _assert_close(y, y_ref, f"{be}/{dist}")
        g = np.asarray(jax.grad(lambda v: jnp.sum(ops.spmm(
            adj, adj_t, v, backend=be) ** 2))(x))
        _assert_close(g, g_ref, f"grad {be}/{dist}")


def test_fused_is_one_dispatch_per_direction(monkeypatch):
    """The forward traces to exactly ONE pallas_call and forward+backward
    to two, though the packing spans several degree buckets — on the arena
    tier and on the dense tier alike."""
    rng = np.random.default_rng(3)
    dst, src, w, n_dst, n_src = _coo("mixed", rng)
    adj, adj_t = pack_ell_pair(dst, src, w, n_dst, n_src)
    assert len(adj.buckets) >= 2
    k, dim = 8, 64
    x = rng.normal(size=(n_src, dim)).astype(np.float32)
    c = cbsr_from_dense(drelu(jnp.asarray(x), k), k)

    def fwd(v):
        return ops.drspmm(adj, adj_t, v, c.idx, dim, backend="pallas_fused")

    for thr in TIERS.values():
        monkeypatch.setattr(ops, "DENSE_TIER_NNZ", thr)
        assert dispatch_count(fwd, c.values) == 1
        assert dispatch_count(jax.grad(lambda v: jnp.sum(fwd(v) ** 2)),
                              c.values) == 2


# ------------------------ packing round-trip ---------------------------

rt_graphs = st.integers(0, 2 ** 31 - 1).flatmap(lambda seed: st.tuples(
    st.just(seed), st.integers(1, 50), st.integers(1, 50),
    st.integers(0, 250)))


@given(rt_graphs)
def test_pack_fused_roundtrip(args):
    """pack_fused reconstructs exactly the matrix pack_ell reconstructs."""
    seed, n_dst, n_src, nnz = args
    rng = np.random.default_rng(seed)
    if nnz:
        dst = rng.integers(0, n_dst, nnz)
        src = rng.integers(0, n_src, nnz)
        pairs = np.unique(np.stack([dst, src], 1), axis=0)
        dst, src = pairs[:, 0], pairs[:, 1]
        w = rng.normal(size=dst.shape[0]).astype(np.float32)
    else:
        dst = src = np.zeros(0, np.int64)
        w = np.zeros(0, np.float32)
    adj = pack_ell(dst, src, w, n_dst, n_src)
    fused = pack_fused(dst, src, w, n_dst, n_src)
    np.testing.assert_allclose(fused.to_dense(), np.asarray(adj.to_dense()),
                               atol=1e-6)
    assert fused.nnz == adj.nnz == int((w != 0).sum())


@pytest.mark.parametrize("op", ["spmm", "drspmm"])
def test_fused_backend_with_traced_graph_falls_back(op):
    """A jitted step that takes the graph as an ARGUMENT (traced pytree)
    cannot host-pack the fused arena: a traced BucketedELL is refused with
    a TypeError that says to fuse host-side, and the pre-fused arenas of
    the same graph run inside the jit."""
    rng = np.random.default_rng(0)
    dst, src, w, n_dst, n_src = _coo("mixed", rng)
    adj, adj_t = pack_ell_pair(dst, src, w, n_dst, n_src)
    x = jnp.asarray(rng.normal(size=(n_src, 32)).astype(np.float32))
    c = cbsr_from_dense(drelu(x, 8), 8)
    if op == "spmm":
        call = lambda a, at: ops.spmm(a, at, x, backend="xla_fused")
        y_ref = ref.spmm_dense_ref(adj, x)
    else:
        call = lambda a, at: ops.drspmm(a, at, c.values, c.idx, 32,
                                        backend="xla_fused")
        y_ref = ref.drspmm_fwd_ref(adj, c.values, c.idx, 32)
    step = jax.jit(call)

    with pytest.raises(TypeError, match="fuse_bucketed"):
        step(adj, adj_t)
    y = np.asarray(step(fuse_bucketed(adj), fuse_bucketed(adj_t)))
    _assert_close(y, np.asarray(y_ref), f"traced fused arenas {op}")


def test_fuse_bucketed_memoized():
    rng = np.random.default_rng(0)
    dst, src, w, n_dst, n_src = _coo("mixed", rng)
    adj = pack_ell(dst, src, w, n_dst, n_src)
    assert fuse_bucketed(adj) is fuse_bucketed(adj)


def test_nnz_is_static_and_cheap():
    rng = np.random.default_rng(0)
    dst, src, w, n_dst, n_src = _coo("mixed", rng)
    adj = pack_ell(dst, src, w, n_dst, n_src)
    assert isinstance(adj.nnz, int)
    assert adj.nnz == int((w != 0).sum())
    # static field ⇒ part of the pytree aux data, not a device array
    leaves, treedef = jax.tree_util.tree_flatten(adj)
    assert all(not isinstance(l, int) for l in leaves)


@pytest.mark.parametrize("name", ["pallas", "xla", "dense"])
def test_unknown_backend_is_refused(name):
    """Only the two executors are backend names; any other is refused at
    the op boundary, not mapped onto one of them."""
    rng = np.random.default_rng(0)
    dst, src, w, n_dst, n_src = _coo("mixed", rng)
    adj, adj_t = pack_ell_pair(dst, src, w, n_dst, n_src)
    x = jnp.asarray(rng.normal(size=(n_src, 16)).astype(np.float32))
    c = cbsr_from_dense(drelu(x, 4), 4)
    with pytest.raises(ValueError, match="unknown backend"):
        ops.spmm(adj, adj_t, x, backend=name)
    with pytest.raises(ValueError, match="unknown backend"):
        ops.drspmm(adj, adj_t, c.values, c.idx, 16, backend=name)
