"""Learnable edge weights through DR-SpMM vs dense oracle (fwd + both grads).

Covers the fused single-dispatch path (DESIGN.md §8): 5-backend parity,
padded eid-slot (−1) inertness, fused eid packing round-trip, executor
cache hits, and collated (member-offset) eid arenas.
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
from repro.graphs.ell import (fuse_bucketed, pack_eid_slabs,
                              pack_fused_eid_pair)
from repro.kernels import ops
from repro.kernels.learnable import drspmm_learnable

settings.register_profile("fast_learnable", max_examples=25, deadline=None)
settings.load_profile("fast_learnable")

BACKENDS = ("pallas_fused", "xla_fused", "pallas", "xla", "dense")


def setup(seed=0, n_dst=23, n_src=31, nnz_target=200, d=16, k=4):
    rng = np.random.default_rng(seed)
    dst = rng.integers(0, n_dst, nnz_target)
    src = rng.integers(0, n_src, nnz_target)
    pairs = np.unique(np.stack([dst, src], 1), axis=0)
    dst, src = pairs[:, 0], pairs[:, 1]
    fwd, bwd, order, nnz = pack_eid_slabs(dst, src, n_dst, n_src)
    w = jnp.asarray(rng.normal(size=nnz).astype(np.float32))
    x = rng.normal(size=(n_src, d)).astype(np.float32)
    c = cbsr_from_dense(jnp.asarray(x), k)
    # dense oracle: A(w) with w in CANONICAL (dst-stable-sorted) order
    canon = np.argsort(dst, kind="stable")
    a_rows, a_cols = dst[canon], src[canon]

    def dense_y(wv, xv):
        a = jnp.zeros((n_dst, n_src)).at[a_rows, a_cols].add(wv)
        xd = jnp.zeros((n_src, d)).at[
            jnp.arange(n_src)[:, None], c.idx].add(xv)
        return a @ xd

    return fwd, bwd, nnz, w, c, d, dense_y


def test_forward_matches_dense():
    fwd, bwd, nnz, w, c, d, dense_y = setup()
    y = drspmm_learnable(fwd, bwd, nnz, w, c.values, c.idx, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense_y(w, c.values)),
                               rtol=1e-5, atol=1e-5)


def test_gradients_match_dense():
    fwd, bwd, nnz, w, c, d, dense_y = setup(seed=3)

    def loss(wv, xv):
        return jnp.sum(jnp.sin(
            drspmm_learnable(fwd, bwd, nnz, wv, xv, c.idx, d)))

    def loss_ref(wv, xv):
        return jnp.sum(jnp.sin(dense_y(wv, xv)))

    gw, gx = jax.grad(loss, argnums=(0, 1))(w, c.values)
    gw_r, gx_r = jax.grad(loss_ref, argnums=(0, 1))(w, c.values)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r),
                               rtol=1e-4, atol=1e-5)


def test_weights_actually_learn():
    """One GD step on w reduces a target-matching loss."""
    fwd, bwd, nnz, w, c, d, dense_y = setup(seed=5)
    target = dense_y(w * 0.3, c.values)

    def loss(wv):
        y = drspmm_learnable(fwd, bwd, nnz, wv, c.values, c.idx, d)
        return jnp.mean((y - target) ** 2)

    l0 = float(loss(w))
    g = jax.grad(loss)(w)
    l1 = float(loss(w - 0.5 * g))
    assert l1 < l0


# ------------------- fused path: 5-backend parity ----------------------

def setup_mixed(seed=7, n_dst=41, n_src=37, d=16, k=4):
    """Heavy-tailed degrees (evil row + sparse bulk) so the packing spans
    several buckets and the arenas carry real −1 padding."""
    rng = np.random.default_rng(seed)
    deg = rng.integers(1, 30, n_dst)
    deg[0] = n_src - 1                    # evil row
    dst = np.repeat(np.arange(n_dst), deg)
    src = rng.integers(0, n_src, dst.size)
    pairs = np.unique(np.stack([dst, src], 1), axis=0)
    dst, src = pairs[:, 0], pairs[:, 1]
    fwd, bwd, order, nnz = pack_eid_slabs(dst, src, n_dst, n_src)
    w = jnp.asarray(rng.normal(size=nnz).astype(np.float32))
    x = rng.normal(size=(n_src, d)).astype(np.float32)
    c = cbsr_from_dense(jnp.asarray(x), k)
    canon = np.argsort(dst, kind="stable")
    a_rows, a_cols = dst[canon], src[canon]

    def dense_y(wv, xv):
        a = jnp.zeros((n_dst, n_src)).at[a_rows, a_cols].add(wv)
        xd = jnp.zeros((n_src, d)).at[
            jnp.arange(n_src)[:, None], c.idx].add(xv)
        return a @ xd

    return fwd, bwd, nnz, w, c, d, dense_y


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_parity_fwd_and_grads(backend):
    """Every backend matches the dense oracle: forward, dw, and dx."""
    fwd, bwd, nnz, w, c, d, dense_y = setup_mixed()

    def loss(wv, xv):
        return jnp.sum(jnp.sin(ops.drspmm_learnable(
            fwd, bwd, nnz, wv, xv, c.idx, d, backend=backend)))

    def loss_ref(wv, xv):
        return jnp.sum(jnp.sin(dense_y(wv, xv)))

    y = ops.drspmm_learnable(fwd, bwd, nnz, w, c.values, c.idx, d,
                             backend=backend)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(dense_y(w, c.values)),
                               rtol=1e-4, atol=1e-4,
                               err_msg=f"fwd {backend}")
    gw, gx = jax.grad(loss, argnums=(0, 1))(w, c.values)
    gw_r, gx_r = jax.grad(loss_ref, argnums=(0, 1))(w, c.values)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r),
                               rtol=1e-4, atol=1e-4,
                               err_msg=f"dw {backend}")
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r),
                               rtol=1e-4, atol=1e-4,
                               err_msg=f"dx {backend}")


def test_prefused_arenas_upgrade_bucket_backends():
    """Pre-fused eid arenas (the collated-batch form) run under every
    backend name via the family-upgrade rules."""
    fwd, bwd, nnz, w, c, d, dense_y = setup_mixed(seed=11)
    # rebuild the fused pair straight from the slabs
    ff, fb = fuse_bucketed(fwd, eids=True), fuse_bucketed(bwd, eids=True)
    y_ref = np.asarray(dense_y(w, c.values))
    for be in ("xla", "pallas", "xla_fused", "pallas_fused", "dense"):
        y = ops.drspmm_learnable(ff, fb, nnz, w, c.values, c.idx, d,
                                 backend=be)
        np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4,
                                   atol=1e-4, err_msg=f"prefused {be}")


# ------------------- padded eid-slot inertness -------------------------

def test_fused_eid_padding_slots_are_inert():
    """Padding slots decode to −1 exactly where the mask is 0, every real
    edge id appears exactly once, and scribbling on the weights of padded
    slots' gather target (the appended zero slot) cannot change the output
    — i.e. padding gathers weight 0 by construction."""
    fwd, bwd, nnz, w, c, d, dense_y = setup_mixed(seed=13)
    f = fuse_bucketed(fwd, eids=True)
    eid = np.asarray(f.eid)
    mask = np.asarray(f.w)
    assert ((eid < 0) == (mask == 0)).all()
    real = eid[eid >= 0]
    assert sorted(real.tolist()) == list(range(nnz))   # bijective coverage
    # numerics: fused output with half the weights zeroed matches dense —
    # zero CANONICAL weights are real edges (not padding) and must still
    # land; padding must contribute nothing.
    w_half = w.at[::2].set(0.0)
    y = ops.drspmm_learnable(fwd, bwd, nnz, w_half, c.values, c.idx, d,
                             backend="xla_fused")
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(dense_y(w_half, c.values)),
                               rtol=1e-4, atol=1e-4)


# ------------------- fused eid packing round-trip ----------------------

rt_graphs = st.integers(0, 2 ** 31 - 1).flatmap(lambda seed: st.tuples(
    st.just(seed), st.integers(1, 40), st.integers(1, 40),
    st.integers(0, 200)))


@given(rt_graphs)
def test_fused_eid_packing_roundtrip(args):
    """Scattering w[eid] over the fused arena reconstructs exactly the
    dense A(w) the canonical COO builds."""
    seed, n_dst, n_src, nnz_t = args
    rng = np.random.default_rng(seed)
    if nnz_t:
        dst = rng.integers(0, n_dst, nnz_t)
        src = rng.integers(0, n_src, nnz_t)
        pairs = np.unique(np.stack([dst, src], 1), axis=0)
        dst, src = pairs[:, 0], pairs[:, 1]
    else:
        dst = src = np.zeros(0, np.int64)
    ff, fb, order, nnz = pack_fused_eid_pair(dst, src, n_dst, n_src)
    w = rng.normal(size=nnz).astype(np.float32)
    canon = np.argsort(dst, kind="stable")
    a_ref = np.zeros((n_dst, n_src), np.float32)
    np.add.at(a_ref, (dst[canon], src[canon]), w)
    for f, transposed in ((ff, False), (fb, True)):
        a = np.zeros(a_ref.T.shape if transposed else a_ref.shape,
                     np.float32)
        eid = np.asarray(f.eid)
        rows = np.asarray(f.rows)
        blk = np.asarray(f.block_of)
        br = f.row_block
        for ci in range(f.n_chunks):
            for b in range(br):
                rid = rows[blk[ci] * br + b]
                sl = eid[ci, b]
                m = sl >= 0
                np.add.at(a[rid], np.asarray(f.nbr)[ci, b][m], w[sl[m]])
        # (chosen by direction, not shape: a square A has equal shapes)
        ref = a_ref.T if transposed else a_ref
        np.testing.assert_allclose(a, ref, atol=1e-6)
    assert ff.nnz == fb.nnz == nnz == dst.shape[0]


# ------------------- executor cache regression -------------------------

def test_no_retrace_on_second_call():
    """The custom-vjp executor must be built (and traced) once per
    (packing, nnz, dim, backend) — the seed rebuilt it per call, defeating
    jit caching (same class of bug tests/test_parallel_cache.py guards in
    core/parallel.py)."""
    fwd, bwd, nnz, w, c, d, dense_y = setup_mixed(seed=17)
    for be in ("xla", "xla_fused"):
        ops.drspmm_learnable(fwd, bwd, nnz, w, c.values, c.idx, d,
                             backend=be)                   # warm (trace 1)
        n0 = len(ops._LEARNABLE_TRACES)
        a = ops.drspmm_learnable(fwd, bwd, nnz, w, c.values, c.idx, d,
                                 backend=be)
        b = ops.drspmm_learnable(fwd, bwd, nnz, 2 * w, c.values, c.idx, d,
                                 backend=be)
        assert len(ops._LEARNABLE_TRACES) == n0, \
            f"repeated {be} call retraced the learnable executor"
        assert jnp.allclose(2 * a, b, atol=1e-5)


def test_executable_identity_is_cached():
    fwd, bwd, nnz, w, c, d, _ = setup_mixed(seed=19)
    e1 = ops._learnable_executable(fwd, bwd, nnz, d, "xla")
    e2 = ops._learnable_executable(fwd, bwd, nnz, d, "xla")
    assert e1 is e2
    assert ops._learnable_executable(fwd, bwd, nnz, d, "xla_fused") is not e1


# ------------------- collated (member-offset) eid arenas ---------------

def test_collated_eids_match_per_member():
    """Block-diagonal collation with_eids: the batched learnable op over
    the merged arena equals each member's own learnable op, forward and
    w-gradient (member weights concatenated at the recorded offsets)."""
    from repro.graphs.collate import collate_graphs
    from repro.graphs.ell import ell_to_coo
    from repro.graphs.generator import generate_design

    gs = generate_design(4, "small", scale=0.03)[:2]
    batch = collate_graphs(gs, with_eids=True)
    et = "near"
    es = batch.graph.edges[et]
    nnz = batch.edge_nnz[et]
    offs = batch.edge_eid_offsets[et]
    assert es.adj.eid is not None and es.adj_t.eid is not None

    rng = np.random.default_rng(0)
    d, k = 16, 4
    packs, ws, xvs, xis = [], [], [], []
    for g in gs:
        dst, src, _w = ell_to_coo(g.edges[et].adj)
        order = np.argsort(dst, kind="stable")
        packs.append(pack_eid_slabs(dst[order], src[order],
                                    g.n_cell, g.n_cell))
        ws.append(rng.normal(size=packs[-1][3]).astype(np.float32))
        xvs.append(rng.normal(size=(g.n_cell, k)).astype(np.float32))
        xis.append(rng.integers(0, d, size=(g.n_cell, k)).astype(np.int32))

    xv_b = np.zeros((batch.graph.n_cell, k), np.float32)
    xi_b = np.zeros((batch.graph.n_cell, k), np.int32)
    for m, xv, xi in zip(batch.members, xvs, xis):
        xv_b[m.cell_off:m.cell_off + m.n_cell] = xv
        xi_b[m.cell_off:m.cell_off + m.n_cell] = xi
    w_b = batch.concat_edge_weights(et, ws)

    def batched(wv):
        return ops.drspmm_learnable(es.adj, es.adj_t, nnz, wv,
                                    jnp.asarray(xv_b), jnp.asarray(xi_b),
                                    d, backend="xla_fused")

    y_b = batched(w_b)
    gw_b = jax.grad(lambda wv: jnp.sum(jnp.sin(batched(wv))))(w_b)
    for (fwd, bwd, _o, m_nnz), wv, xv, xi, m, off in zip(
            packs, ws, xvs, xis, batch.members, offs):
        def member(w0):
            return ops.drspmm_learnable(fwd, bwd, m_nnz, w0,
                                        jnp.asarray(xv), jnp.asarray(xi),
                                        d, backend="xla")
        y_m = member(jnp.asarray(wv))
        np.testing.assert_allclose(
            np.asarray(y_b[m.cell_off:m.cell_off + m.n_cell]),
            np.asarray(y_m), rtol=1e-4, atol=1e-5)
        gw_m = jax.grad(lambda w0: jnp.sum(jnp.sin(member(w0))))(
            jnp.asarray(wv))
        np.testing.assert_allclose(np.asarray(gw_b[off:off + m_nnz]),
                                   np.asarray(gw_m), rtol=1e-4, atol=1e-5)
