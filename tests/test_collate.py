"""Block-diagonal collation (graphs/collate.py).

A collated batch must be numerically indistinguishable from its members run
one at a time: forward and gradients match per-graph results under both
executors and across the batch packings (all-arena plan, mixed dense-tier
plan, quantized with a filler member), quantization padding contributes
exactly zero to member outputs, and the member offsets tile the merged node
spaces without overlap.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # bare container: seeded fallback
    from _hyp_fallback import given, settings, strategies as st

from repro.core.hetero_mp import HeteroMPConfig
from repro.graphs.collate import (BucketLayout, collate_graphs,
                                  quantize_up)
from repro.graphs.ell import ell_to_coo, pack_ell, pick_chunk
from repro.graphs.generator import generate_partition, pack_graph_parallel
from repro.models.hgnn import (batched_loss_fn, drcircuitgnn_forward,
                               init_drcircuitgnn, loss_fn)

settings.register_profile("fast", max_examples=20, deadline=None)
settings.load_profile("fast")

BACKENDS = ("pallas_fused", "xla_fused")
# batch packings: exact-size collation with every plan relation on the
# arena tier; exact size with ``near`` on the arena tier and ``pin`` /
# ``pinned`` on the dense tier; quantized with a filler replica of the last
# member (tiers as the batch's sizes route them)
PACKINGS = ("arena", "mixed", "filler")
_TIERS = {"arena": {"near": "arena", "pin": "arena", "pinned": "arena"},
          "mixed": {"near": "arena", "pin": "dense", "pinned": "dense"}}


def _graph(n_cell, n_net, seed):
    coo, xc, xn, y = generate_partition(np.random.default_rng(seed),
                                        n_cell, n_net)
    return pack_graph_parallel(coo, n_cell, n_net, xc, xn, y)


@pytest.fixture(scope="module")
def members():
    return [_graph(60, 30, 0), _graph(101, 55, 1), _graph(37, 20, 2)]


@pytest.fixture(scope="module")
def params():
    return init_drcircuitgnn(jax.random.PRNGKey(0), 16, 16, 32)


def _cfg(backend):
    return HeteroMPConfig(hidden=32, k_cell=8, k_net=8, backend=backend)


def _collated(members, packing):
    if packing == "filler":
        return collate_graphs(members + [members[-1]], n_real=len(members))
    batch = collate_graphs(
        members, quantize=False,
        layout=BucketLayout(plan_tier=dict(_TIERS[packing])))
    assert {s.etype: s.tier for s in batch.plan.segments} == _TIERS[packing]
    return batch


def _assert_close(actual, ref, msg):
    atol = 1e-5 * max(1.0, float(np.abs(ref).max()) if ref.size else 1.0)
    np.testing.assert_allclose(actual, ref, atol=atol, rtol=1e-5,
                               err_msg=msg)


@pytest.mark.parametrize("packing", PACKINGS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_forward_matches_member_loop(members, params, backend,
                                             packing):
    """The collated block-diagonal graph reproduces the per-member
    forwards under both executors, whatever the batch's packing."""
    cfg = _cfg(backend)
    batch = _collated(members, packing)
    parts = batch.split_cell(drcircuitgnn_forward(params, batch.graph, cfg))
    assert len(parts) == len(members)
    for i, (g, p) in enumerate(zip(members, parts)):
        ref = np.asarray(drcircuitgnn_forward(params, g, cfg))
        _assert_close(np.asarray(p), ref,
                      f"member {i} fwd {backend}/{packing}")


@pytest.mark.parametrize("packing", PACKINGS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_gradients_match_member_loop(members, params, backend,
                                             packing):
    """∇ of the weighted batched loss == ∇ of the mean of per-graph mean-MSE
    losses — the property that makes train_epoch(batch_size=B) a drop-in."""
    cfg = _cfg(backend)
    batch = _collated(members, packing)
    g_b = jax.grad(batched_loss_fn)(params, batch.graph, batch.cell_weight,
                                    cfg)
    g_ref = None
    for g in members:
        gi = jax.grad(loss_fn)(params, g, cfg)
        g_ref = gi if g_ref is None else jax.tree_util.tree_map(
            jnp.add, g_ref, gi)
    g_ref = jax.tree_util.tree_map(lambda x: x / len(members), g_ref)
    for (pa, a), (_, r) in zip(
            jax.tree_util.tree_leaves_with_path(g_b),
            jax.tree_util.tree_leaves_with_path(g_ref)):
        _assert_close(np.asarray(a), np.asarray(r),
                      f"grad {jax.tree_util.keystr(pa)} {backend}/{packing}")


@pytest.mark.parametrize("backend", ["xla_fused", "pallas_fused"])
def test_quantization_padding_is_invariant(members, params, backend):
    """Padded node rows / arena chunks contribute zero: the quantized fused
    collation reproduces the exact collation on every member slice.  Runs
    both fused executors — the Pallas kernel must tolerate the padding
    chunks extending the sentinel block's run."""
    cfg = _cfg(backend)
    exact = collate_graphs(members, quantize=False)
    quant = collate_graphs(members, quantize=True)
    assert quant.graph.n_cell >= exact.graph.n_cell
    p_exact = exact.split_cell(drcircuitgnn_forward(params, exact.graph, cfg))
    p_quant = quant.split_cell(drcircuitgnn_forward(params, quant.graph, cfg))
    for i, (a, b) in enumerate(zip(p_exact, p_quant)):
        _assert_close(np.asarray(b), np.asarray(a), f"member {i} padding")
    # gradients flow identically through the padded arenas
    g_e = jax.grad(batched_loss_fn)(params, exact.graph, exact.cell_weight,
                                    cfg)
    g_q = jax.grad(batched_loss_fn)(params, quant.graph, quant.cell_weight,
                                    cfg)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_e),
            jax.tree_util.tree_leaves_with_path(g_q)):
        _assert_close(np.asarray(b), np.asarray(a),
                      f"grad {jax.tree_util.keystr(pa)} padding")


def test_fused_collation_runs_fused_inside_jit(members, params):
    """The whole point of pre-fused arenas: the batched forward runs even
    when the graph is a traced jit argument (no recompile for an
    equal-signature batch)."""
    cfg = _cfg("xla_fused")
    batch = collate_graphs(members, quantize=True)

    fwd = jax.jit(lambda p, g: drcircuitgnn_forward(p, g, cfg))
    y1 = fwd(params, batch.graph)
    ref = np.asarray(drcircuitgnn_forward(params, batch.graph, cfg))
    _assert_close(np.asarray(y1), ref, "jitted batched fwd")
    if hasattr(fwd, "_cache_size"):
        # same-signature batch (different member sizes, same buckets) must
        # hit the compiled executable
        other = collate_graphs([_graph(62, 31, 7), _graph(99, 56, 8),
                                _graph(36, 20, 9)],
                               quantize=True)
        if other.signature == batch.signature:
            fwd(params, other.graph)
            assert fwd._cache_size() == 1


def test_cell_weight_normalization(members):
    batch = collate_graphs(members)
    w = np.asarray(batch.cell_weight)
    assert abs(w.sum() - 1.0) < 1e-5
    # weight is zero exactly off the member slices
    mask = np.zeros(batch.graph.n_cell, bool)
    for m in batch.members:
        mask[m.cell_off:m.cell_off + m.n_cell] = True
    assert (w[~mask] == 0).all() and (w[mask] > 0).all()


def test_filler_members_have_zero_weight(members):
    batch = collate_graphs(members + [members[-1]], n_real=len(members))
    assert batch.n_real == len(members)
    assert len(batch.split_cell(jnp.zeros(batch.graph.n_cell))) == len(members)
    w = np.asarray(batch.cell_weight)
    filler = batch.members[-1]
    assert (w[filler.cell_off:filler.cell_off + filler.n_cell] == 0).all()
    assert abs(w.sum() - 1.0) < 1e-5


def test_quantize_up_grid():
    assert quantize_up(17, 2) == 20
    assert quantize_up(16, 2) == 16
    assert quantize_up(1000, 2) == 1024
    assert quantize_up(5, 2, minimum=8) == 8
    # monotone, idempotent on grid points, bounded padding
    for bits in (1, 2, 3):
        for n in range(8, 4000, 37):
            q = quantize_up(n, bits)
            assert q >= n
            assert quantize_up(q, bits) == q
            assert q <= n * (1 + 2.0 ** -bits) + 1


def test_pick_chunk_follows_degree_histogram():
    """ROADMAP item: narrow pin/pinned fan-outs should get a narrow chunk,
    heavy-tailed rows a wide one — slot-minimizing per packing."""
    rng = np.random.default_rng(0)
    # fan-outs 2–4 (pin-like)
    dst = np.repeat(np.arange(64), 3)
    adj_narrow = pack_ell(dst, rng.integers(0, 64, dst.size), None, 64, 64)
    assert pick_chunk(adj_narrow) == 4
    # uniformly heavy rows (near-like evil bulk)
    dst = np.repeat(np.arange(32), 64)
    adj_wide = pack_ell(dst, rng.integers(0, 256, dst.size), None, 32, 256)
    assert pick_chunk(adj_wide) == 16


# --------------------- offset round-trip property ----------------------

member_lists = st.integers(0, 2 ** 31 - 1).flatmap(lambda seed: st.tuples(
    st.just(seed), st.integers(1, 4), st.booleans()))


@given(member_lists)
def test_collate_offsets_roundtrip(args):
    """The collated adjacency is exactly the block-diagonal direct sum: each
    member's dense matrix reappears at its offsets, and nothing appears
    outside the member blocks."""
    seed, n_members, quantize = args
    rng = np.random.default_rng(seed)
    members = [_graph(int(rng.integers(12, 48)), int(rng.integers(6, 24)),
                      int(rng.integers(0, 2 ** 31))) for _ in range(n_members)]
    batch = collate_graphs(members, quantize=quantize)
    g = batch.graph
    off = {"cell": [m.cell_off for m in batch.members],
           "net": [m.net_off for m in batch.members]}
    n_of = {"cell": [m.n_cell for m in batch.members],
            "net": [m.n_net for m in batch.members]}
    from repro.graphs.circuit import EDGE_SCHEMA
    for et, es in g.edges.items():
        s_t, d_t = EDGE_SCHEMA[et]
        dense = np.asarray(es.adj.to_dense())
        covered = np.zeros_like(dense, bool)
        for i, m in enumerate(members):
            ds, de = off[d_t][i], off[d_t][i] + n_of[d_t][i]
            ss, se = off[s_t][i], off[s_t][i] + n_of[s_t][i]
            block = dense[ds:de, ss:se]
            np.testing.assert_allclose(
                block, np.asarray(m.edges[et].adj.to_dense()), atol=1e-6,
                err_msg=f"{et} member {i}")
            covered[ds:de, ss:se] = True
        assert dense[~covered].sum() == 0, f"{et}: mass outside blocks"
        # transposed packing is consistent
        np.testing.assert_allclose(np.asarray(es.adj_t.to_dense()).T, dense,
                                   atol=1e-6, err_msg=f"{et} adj_t")


def test_ell_to_coo_roundtrip():
    rng = np.random.default_rng(4)
    dst = rng.integers(0, 40, 200)
    src = rng.integers(0, 30, 200)
    pairs = np.unique(np.stack([dst, src], 1), axis=0)
    w = rng.normal(size=pairs.shape[0]).astype(np.float32)
    w[w == 0] = 1.0
    adj = pack_ell(pairs[:, 0], pairs[:, 1], w, 40, 30)
    d2, s2, w2 = ell_to_coo(adj)
    a = np.zeros((40, 30), np.float32)
    np.add.at(a, (d2, s2), w2)
    np.testing.assert_allclose(a, np.asarray(adj.to_dense()), atol=1e-6)


def test_signature_stability_within_bucket():
    """Graphs jittered within one size class collate to the same padded
    shape signature when a shared BucketLayout pins the arena layout — the
    property the serve engine's compile cache rests on (engine-level
    assertion lives in test_circuit_engine.py)."""
    layout = BucketLayout()
    b1 = collate_graphs([_graph(60, 30, 0), _graph(58, 29, 1)],
                        node_bits=1, layout=layout)
    b2 = collate_graphs([_graph(63, 31, 2), _graph(59, 28, 3)],
                        node_bits=1, layout=layout)
    assert b1.signature == b2.signature
