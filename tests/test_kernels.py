"""Per-kernel validation: the DR-SpMM arena kernels (interpret mode on CPU)
through the ``pallas_fused`` executor, against the pure-jnp oracle
(kernels/ref.py) and the ``xla_fused`` executor of the same arena, swept
over shapes and dtypes (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cbsr import cbsr_from_dense
from repro.graphs.ell import pack_ell_pair
from repro.kernels import ops, ref
from repro.kernels import drspmm as K


def make_graph(rng, n_dst, n_src, nnz):
    dst = rng.integers(0, n_dst, nnz)
    src = rng.integers(0, n_src, nnz)
    pairs = np.unique(np.stack([dst, src], 1), axis=0)
    w = rng.normal(size=pairs.shape[0]).astype(np.float32)
    return pack_ell_pair(pairs[:, 0], pairs[:, 1], w, n_dst, n_src)


SHAPES = [
    # (n_dst, n_src, nnz, D, k)
    (8, 8, 20, 8, 4),
    (37, 53, 400, 32, 8),
    (64, 64, 1000, 64, 16),
    (100, 40, 600, 128, 32),
    (16, 128, 256, 16, 16),       # k == D (no sparsity)
]


# every Pallas parity test below runs on both gather paths of the arena
# kernels (conftest.py ``gather_path``)
PATHS = pytest.mark.parametrize("gather_path", ["vmem", "dma"],
                                indirect=True)


@pytest.fixture(autouse=True)
def arena_tier(monkeypatch):
    """Pin every relation to the arena tier: these graphs sit below the
    dense-tier crossover (DESIGN.md §14), and the tests are of the arena
    kernels."""
    monkeypatch.setattr(ops, "DENSE_TIER_NNZ", -1)


@PATHS
@pytest.mark.parametrize("n_dst,n_src,nnz,d,k", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_drspmm_fwd_vs_oracle(n_dst, n_src, nnz, d, k, dtype, gather_path):
    rng = np.random.default_rng(n_dst + d)
    adj, adj_t = make_graph(rng, n_dst, n_src, nnz)
    x = rng.normal(size=(n_src, d)).astype(np.float32)
    c = cbsr_from_dense(jnp.asarray(x, dtype), k)
    y_ref = ref.drspmm_fwd_ref(adj, c.values.astype(jnp.float32),
                               c.idx, d)
    y = ops.drspmm(adj, adj_t, c.values, c.idx, d, backend="pallas_fused")
    tol = 1e-5 if dtype == jnp.float32 else 8e-2
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref),
                               rtol=tol, atol=tol)


@PATHS
@pytest.mark.parametrize("n_dst,n_src,nnz,d,k", SHAPES[:4])
def test_drspmm_bwd_vs_oracle(n_dst, n_src, nnz, d, k, gather_path):
    rng = np.random.default_rng(7 * n_dst + d)
    adj, adj_t = make_graph(rng, n_dst, n_src, nnz)
    x = rng.normal(size=(n_src, d)).astype(np.float32)
    c = cbsr_from_dense(jnp.asarray(x), k)

    def loss(xv, backend):
        y = ops.drspmm(adj, adj_t, xv, c.idx, d, backend=backend)
        return jnp.sum(jnp.sin(y))

    g_ref = jax.grad(lambda xv: jnp.sum(jnp.sin(
        ref.drspmm_fwd_ref(adj, xv, c.idx, d))))(c.values)
    g = jax.grad(loss)(c.values, "pallas_fused")
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_dst,n_src,nnz,d,k", SHAPES[:3])
def test_xla_backend_matches_pallas(n_dst, n_src, nnz, d, k):
    """The two executors of one arena agree, forward and gradient."""
    rng = np.random.default_rng(n_dst * 13)
    adj, adj_t = make_graph(rng, n_dst, n_src, nnz)
    x = rng.normal(size=(n_src, d)).astype(np.float32)
    c = cbsr_from_dense(jnp.asarray(x), k)

    def run(backend):
        return jax.value_and_grad(lambda v: jnp.sum(jnp.sin(ops.drspmm(
            adj, adj_t, v, c.idx, d, backend=backend))))(c.values)

    (l_p, g_p), (l_x, g_x) = run("pallas_fused"), run("xla_fused")
    np.testing.assert_allclose(float(l_p), float(l_x), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_x),
                               rtol=1e-5, atol=1e-5)


@PATHS
@pytest.mark.parametrize("n,d", [(32, 16), (64, 64), (40, 128)])
def test_dense_spmm_kernel(n, d, gather_path):
    rng = np.random.default_rng(n + d)
    adj, adj_t = make_graph(rng, n, n, n * 6)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y_ref = ref.spmm_dense_ref(adj, jnp.asarray(x))
    y = ops.spmm(adj, adj_t, jnp.asarray(x), backend="pallas_fused")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_empty_rows_are_zero():
    """Rows with no in-edges must produce exactly zero output rows."""
    dst = np.array([0, 0, 2])
    src = np.array([1, 2, 0])
    adj, adj_t = pack_ell_pair(dst, src, None, 5, 3)
    x = np.ones((3, 8), np.float32)
    c = cbsr_from_dense(jnp.asarray(x), 4)
    y = ops.drspmm(adj, adj_t, c.values, c.idx, 8, backend="pallas_fused")
    assert np.allclose(np.asarray(y)[[1, 3, 4]], 0.0)
    assert not np.allclose(np.asarray(y)[0], 0.0)


def test_gradient_zero_outside_cbsr_support():
    """SSpMM: gradients must vanish at positions D-ReLU zeroed (Alg. 2)."""
    rng = np.random.default_rng(3)
    adj, adj_t = make_graph(rng, 20, 20, 100)
    x = jnp.asarray(rng.normal(size=(20, 16)).astype(np.float32))
    from repro.core.drelu import drelu

    def loss(xd):
        xs = drelu(xd, 4)
        c = cbsr_from_dense(xs, 4)
        y = ops.drspmm(adj, adj_t, c.values, c.idx, 16, backend="xla_fused")
        return jnp.sum(y ** 2)

    g = jax.grad(loss)(x)
    xs = drelu(x, 4)
    mask = np.asarray(xs != 0)
    assert np.all(np.asarray(g)[~mask] == 0.0)


def test_gather_path_boundary(monkeypatch):
    """The path follows the slab's bytes: a slab exactly at the budget is
    VMEM-resident, one row over it takes per-row DMAs, and both agree."""
    from _gather import path_of
    from repro.graphs.ell import fuse_bucketed
    rng = np.random.default_rng(11)
    n = 40                                 # whole 8-row tiles
    adj, _ = make_graph(rng, 24, n, 150)
    fused = fuse_bucketed(adj)
    x = jnp.asarray(rng.normal(size=(n + 1, 16)).astype(np.float32))
    monkeypatch.setattr(K, "_slab_budget",
                        lambda other_bytes: n * K.LANES * 4)
    y_at, path_at = path_of(K.spmm_dense_fused, fused, x[:n])
    y_over, path_over = path_of(K.spmm_dense_fused, fused, x)
    assert (path_at, path_over) == ("vmem", "dma")
    np.testing.assert_array_equal(np.asarray(y_at), np.asarray(y_over))
    y_ref = ref.spmm_dense_ref(adj, x[:n])
    np.testing.assert_allclose(
        np.take(np.asarray(y_at), np.asarray(fused.gather), 0),
        np.asarray(y_ref),
        rtol=1e-5, atol=1e-5)


def test_slab_budget_follows_the_device(monkeypatch):
    """Half the core's VMEM less the call's other buffers; with no TPU
    described the conservative capacity stands in."""
    assert K._vmem_capacity() == K.VMEM_FALLBACK_BYTES   # CPU host
    monkeypatch.setattr(K, "_vmem_capacity", lambda: 128 << 20)
    assert K._slab_budget(1 << 20) == (64 << 20) - (1 << 20)


def test_undescribed_tpu_is_refused_not_assumed(monkeypatch):
    """On a TPU host a chip ``get_tpu_info`` cannot describe raises: the
    16 MiB stand-in would quietly put the Table-1 slabs on the DMA path."""
    def undescribed():
        raise ValueError("unknown device kind")

    monkeypatch.setattr(K.pltpu, "get_tpu_info", undescribed)
    assert K._vmem_capacity() == K.VMEM_FALLBACK_BYTES   # CPU host
    monkeypatch.setattr(K.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="unknown device kind"):
        K._vmem_capacity()
