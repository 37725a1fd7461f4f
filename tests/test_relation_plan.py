"""Relation-fused mega-dispatch (RelationPlan, DESIGN.md §9).

The plan path — one super-arena dispatch per direction-group covering every
edge-type direction of a hetero layer — must be numerically interchangeable
with the serial per-direction reference loop and the dense oracle under both
executors, forward and gradient, whatever tiers the plan routes its
relations to; its relation segments must round-trip exactly onto
the member relations' matrices; collation padding and fillers must stay
inert through the plan; and the cached custom-vjp executor must never
retrace on repeat calls.
"""

import dataclasses

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
from repro.core.hetero_mp import HeteroMPConfig, hetero_conv, \
    init_hetero_layer
from repro.graphs.circuit import EDGE_SCHEMA, relation_plan_of, with_plan
from repro.graphs.collate import BucketLayout, collate_graphs
from repro.graphs.ell import build_relation_plan, pack_ell_pair
from repro.graphs.generator import generate_partition, pack_graph_parallel
from _dispatch import dispatch_count
from repro.kernels import ops, ref
from repro.models.hgnn import drcircuitgnn_forward, init_drcircuitgnn

settings.register_profile("fast", max_examples=15, deadline=None)
settings.load_profile("fast")

BACKENDS = ("pallas_fused", "xla_fused")
# plan crossovers over op_setup's relations (near ≈ 4·57 edges, pin/pinned
# ≈ 2·57): every relation on the arena tier; near on the arena tier and
# pin/pinned on the dense tier; the default crossover, which puts these
# small relations all on the dense tier
THRESHOLDS = {"arena": -1, "mixed": 150, "default": None}


def _assert_close(actual, ref, msg):
    atol = 1e-5 * max(1.0, float(np.abs(ref).max()) if ref.size else 1.0)
    np.testing.assert_allclose(actual, ref, atol=atol, rtol=1e-5,
                               err_msg=msg)


def _graph(n_cell, n_net, seed):
    coo, xc, xn, y = generate_partition(np.random.default_rng(seed),
                                        n_cell, n_net)
    return pack_graph_parallel(coo, n_cell, n_net, xc, xn, y)


def _mixed_relations(rng, n_cell, n_net):
    """Three mixed-degree relations over the circuit schema."""

    def mk(n_dst, n_src, nnz):
        d = rng.integers(0, n_dst, nnz)
        s = rng.integers(0, n_src, nnz)
        pairs = np.unique(np.stack([d, s], 1), axis=0)
        w = rng.normal(size=pairs.shape[0]).astype(np.float32)
        w[w == 0] = 1.0
        return pairs[:, 0], pairs[:, 1], w

    sizes = {"cell": n_cell, "net": n_net}
    out = []
    for et, nnz in (("near", 4 * n_cell), ("pin", 2 * n_cell),
                    ("pinned", 2 * n_cell)):
        s_t, d_t = EDGE_SCHEMA[et]
        out.append((et, s_t, d_t, *mk(sizes[d_t], sizes[s_t], max(nnz, 1))))
    return out


# ------------------------- op-level parity -----------------------------

@pytest.fixture(scope="module")
def op_setup():
    rng = np.random.default_rng(3)
    n_cell, n_net, dim = 57, 29, 64
    rels = _mixed_relations(rng, n_cell, n_net)
    plans = {tier: build_relation_plan(rels, {"cell": n_cell, "net": n_net},
                                       dense_threshold=thr)
             for tier, thr in THRESHOLDS.items()}
    assert not plans["arena"].has_dense
    assert plans["mixed"].has_arena and plans["mixed"].has_dense
    assert not plans["default"].has_arena
    k_cell, k_net = 8, 6
    cc = cbsr_from_dense(drelu(jnp.asarray(
        rng.normal(size=(n_cell, dim)).astype(np.float32)), k_cell), k_cell)
    cn = cbsr_from_dense(drelu(jnp.asarray(
        rng.normal(size=(n_net, dim)).astype(np.float32)), k_net), k_net)
    packs = {r[0]: pack_ell_pair(r[3], r[4], r[5],
                                 {"cell": n_cell, "net": n_net}[r[2]],
                                 {"cell": n_cell, "net": n_net}[r[1]])
             for r in rels}
    src_of = {r[0]: r[1] for r in rels}
    return plans, rels, packs, src_of, cc, cn, dim


def _serial_ref(packs, src_of, cc, cn, dim, vc, vn):
    out = {}
    for et, (adj, adj_t) in packs.items():
        c = cc if src_of[et] == "cell" else cn
        v = vc if src_of[et] == "cell" else vn
        out[et] = ref.drspmm_fwd_ref(adj, v, c.idx, dim)
    return out


@pytest.mark.parametrize("tiers", list(THRESHOLDS))
@pytest.mark.parametrize("backend", BACKENDS)
def test_multi_matches_serial_per_relation(op_setup, backend, tiers):
    """drspmm_multi == the dense oracle of each relation, fwd + grads in
    both source types, under both executors and whatever tiers the plan
    routes the relations to."""
    plans, rels, packs, src_of, cc, cn, dim = op_setup
    plan = plans[tiers]
    refs = _serial_ref(packs, src_of, cc, cn, dim, cc.values, cn.values)
    ys = ops.drspmm_multi(plan, {"cell": (cc.values, cc.idx),
                                 "net": (cn.values, cn.idx)}, dim,
                          backend=backend)
    for et in packs:
        _assert_close(np.asarray(ys[et]), np.asarray(refs[et]),
                      f"fwd {backend}/{tiers}/{et}")

    def loss_multi(vc, vn):
        ys = ops.drspmm_multi(plan, {"cell": (vc, cc.idx),
                                     "net": (vn, cn.idx)}, dim,
                              backend=backend)
        return sum(jnp.sum(y ** 2) for y in ys.values())

    def loss_serial(vc, vn):
        refs = _serial_ref(packs, src_of, cc, cn, dim, vc, vn)
        return sum(jnp.sum(y ** 2) for y in refs.values())

    g = jax.grad(loss_multi, argnums=(0, 1))(cc.values, cn.values)
    g_ref = jax.grad(loss_serial, argnums=(0, 1))(cc.values, cn.values)
    for a, r, nm in zip(g, g_ref, ("cell", "net")):
        _assert_close(np.asarray(a), np.asarray(r),
                      f"grad {backend}/{tiers}/{nm}")


def test_no_retrace_on_second_multi_call(op_setup):
    """The plan executor is built (and traced) once per (plan, dim,
    backend) — mirrors test_no_retrace_on_second_call for the learnable
    op."""
    plans, rels, packs, src_of, cc, cn, dim = op_setup
    plan = plans["default"]
    cbsr = {"cell": (cc.values, cc.idx), "net": (cn.values, cn.idx)}
    for be in ("xla_fused", "pallas_fused"):
        ops.drspmm_multi(plan, cbsr, dim, backend=be)   # warm (trace 1)
        n0 = len(ops._MULTI_TRACES)
        a = ops.drspmm_multi(plan, cbsr, dim, backend=be)["near"]
        b = ops.drspmm_multi(plan, {"cell": (2 * cc.values, cc.idx),
                                    "net": (cn.values, cn.idx)},
                             dim, backend=be)["near"]
        assert len(ops._MULTI_TRACES) == n0, \
            f"repeated {be} drspmm_multi call retraced the executor"
        _assert_close(np.asarray(b), 2 * np.asarray(a), f"linearity {be}")


# ------------------------ layer-level parity ---------------------------

@pytest.fixture(scope="module")
def layer_setup():
    g = _graph(72, 36, 11)
    lp = init_hetero_layer(jax.random.PRNGKey(0), 32)
    rng = np.random.default_rng(5)
    x_cell = jnp.asarray(rng.normal(size=(72, 32)).astype(np.float32))
    x_net = jnp.asarray(rng.normal(size=(36, 32)).astype(np.float32))
    return g, lp, x_cell, x_net


@pytest.mark.parametrize("backend", ["pallas_fused", "xla_fused"])
def test_hetero_conv_plan_matches_serial(layer_setup, backend):
    """Plan-fused hetero_conv == the serial per-direction loop, forward
    (both node types) and gradients (inputs + layer params)."""
    g, lp, x_cell, x_net = layer_setup
    cfg_p = HeteroMPConfig(hidden=32, k_cell=8, k_net=8, backend=backend,
                           use_plan=True)
    cfg_s = dataclasses.replace(cfg_p, use_plan=False)

    y_p = hetero_conv(lp, g, x_cell, x_net, cfg_p)
    y_s = hetero_conv(lp, g, x_cell, x_net, cfg_s)
    for a, r, nm in zip(y_p, y_s, ("cell", "net")):
        _assert_close(np.asarray(a), np.asarray(r), f"fwd {backend}/{nm}")

    def loss(cfg):
        def f(p, xc, xn):
            yc, yn = hetero_conv(p, g, xc, xn, cfg)
            return jnp.sum(yc ** 2) + jnp.sum(jnp.sin(yn))
        return f

    g_p = jax.grad(loss(cfg_p), argnums=(0, 1, 2))(lp, x_cell, x_net)
    g_s = jax.grad(loss(cfg_s), argnums=(0, 1, 2))(lp, x_cell, x_net)
    for (pa, a), (_, r) in zip(jax.tree_util.tree_leaves_with_path(g_p),
                               jax.tree_util.tree_leaves_with_path(g_s)):
        _assert_close(np.asarray(a), np.asarray(r),
                      f"grad {jax.tree_util.keystr(pa)} {backend}")


def test_one_dispatch_per_direction_group():
    """The acceptance property: a hetero layer's message passing is ONE
    pallas_call forward and ONE backward on the plan path — vs one per edge
    type (×2 for grad) on the serial path.  The xla family asserts the same
    via the trace-time dispatch log.  Uses its own graph (→ fresh plan →
    fresh executor) so every trace actually runs and gets recorded."""
    g = _graph(48, 24, 23)
    lp = init_hetero_layer(jax.random.PRNGKey(1), 32)
    rng = np.random.default_rng(9)
    x_cell = jnp.asarray(rng.normal(size=(48, 32)).astype(np.float32))
    x_net = jnp.asarray(rng.normal(size=(24, 32)).astype(np.float32))
    cfg_p = HeteroMPConfig(hidden=32, k_cell=8, k_net=8,
                           backend="pallas_fused", use_plan=True)
    cfg_s = dataclasses.replace(cfg_p, use_plan=False)

    def fwd(cfg):
        return lambda xc: hetero_conv(lp, g, xc, x_net, cfg)[0]

    def grad_both(cfg):
        # sum over BOTH outputs, differentiate wrt BOTH inputs, so no
        # direction's forward or backward is dead-code-eliminated
        return lambda xc, xn: jax.grad(lambda qc, qn: sum(
            jnp.sum(y ** 2) for y in hetero_conv(lp, g, qc, qn, cfg)),
            argnums=(0, 1))(xc, xn)

    assert dispatch_count(fwd(cfg_p), x_cell) == 1
    assert dispatch_count(fwd(cfg_s), x_cell) == 3
    assert dispatch_count(grad_both(cfg_p), x_cell, x_net) == 2
    assert dispatch_count(grad_both(cfg_s), x_cell, x_net) == 6

    # xla family: executor issues recorded while tracing.  Only the
    # direction-group executors may appear — a serial per-relation tag
    # ("xla:fwd"/"xla:bwd") would mean the plan path leaked back to the
    # loop.  This tiny graph's relations all sit below the dense-tier
    # crossover, so the group runs as the batched dense dispatch
    # (DESIGN.md §14).  (custom_vjp traces the forward body twice under
    # grad — primal + f_fwd — so the fwd tag may legitimately repeat.)
    plan = relation_plan_of(g)
    assert not plan.has_arena and plan.has_dense
    cfg_px = dataclasses.replace(cfg_p, backend="xla_fused")
    n0 = len(ops.FUSED_DISPATCH_LOG)
    jax.make_jaxpr(grad_both(cfg_px))(x_cell, x_net)
    tags = list(ops.FUSED_DISPATCH_LOG)[n0:]
    assert set(tags) == {"xla:multi_dense_fwd", "xla:multi_dense_bwd"}, tags
    assert tags.count("xla:multi_dense_bwd") == 1, tags


def test_relation_plan_memoized(layer_setup):
    g, lp, x_cell, x_net = layer_setup
    assert relation_plan_of(g) is relation_plan_of(g)
    pg = with_plan(g)
    assert pg.plan is relation_plan_of(g)
    assert with_plan(pg) is pg


# ------------------ plan from the graph's own packings ------------------

def _coo_built_plan(g):
    """The plan as built from COO read back out of the forward packings
    (``build_relation_plan`` packing both directions itself)."""
    from repro.graphs.ell import ell_to_coo
    rels = [(et,) + EDGE_SCHEMA[et] + ell_to_coo(g.edges[et].adj)
            for et in ("near", "pin", "pinned")]
    return build_relation_plan(rels, {"cell": g.n_cell, "net": g.n_net})


@pytest.fixture(scope="module")
def packed_vs_coo():
    # near is past the dense-tier crossover, pin / pinned below it
    g = _graph(300, 150, 17)
    return g, relation_plan_of(g), _coo_built_plan(g)


def test_relation_plan_of_matches_coo_built_plan(packed_vs_coo):
    """Forward leaves bit-identical; the backward arena differs only in
    the neighbour order inside a row (the graph's own ``adj_t``), so its
    matrix, segments and every shape are equal."""
    g, plan, ref = packed_vs_coo
    assert plan.has_arena and plan.has_dense
    assert plan.segments == ref.segments
    assert (plan.src_types, plan.src_off, plan.src_sizes) == \
        (ref.src_types, ref.src_off, ref.src_sizes)
    assert jax.tree.structure(plan) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(plan), jax.tree.leaves(ref)):
        assert np.shape(a) == np.shape(b)
        assert np.asarray(a).dtype == np.asarray(b).dtype
    for f in dataclasses.fields(plan.fwd):
        a, b = getattr(plan.fwd, f.name), getattr(ref.fwd, f.name)
        if f.metadata.get("static") or a is None:
            assert a == b, f.name
        else:
            assert np.array_equal(np.asarray(a), np.asarray(b)), f.name
    for name in ("block_of", "start", "rows", "gather", "rel"):
        assert np.array_equal(getattr(plan.bwd, name),
                              getattr(ref.bwd, name)), name
    assert np.array_equal(plan.bwd.to_dense(), ref.bwd.to_dense())
    assert np.array_equal(plan.bwd_src_rows, ref.bwd_src_rows)
    assert np.array_equal(plan.dense_fwd, ref.dense_fwd)
    assert np.array_equal(plan.dense_bwd, ref.dense_bwd)
    # each backward row holds the graph's own adj_t row, in its order
    near = plan.segment("near")
    lo, hi = near.bwd_chunks
    assert not np.array_equal(plan.bwd.nbr[lo:hi], ref.bwd.nbr[lo:hi])


def test_relation_plan_of_packs_nothing(monkeypatch):
    """The graph's packings feed the plan: no ``pack_ell`` on this path."""
    import repro.graphs.ell as ell
    g = _graph(120, 60, 29)
    calls = []
    real = ell.pack_ell
    monkeypatch.setattr(ell, "pack_ell",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    relation_plan_of(g)
    assert calls == []
    _coo_built_plan(g)
    assert len(calls) == 6        # the COO path packs both directions


def test_relation_plan_of_pins_no_fused_arena():
    """The plan is memoized whole; the per-relation fused arenas it was
    built from leave no ``_FUSE_CACHE`` entry behind."""
    from repro.graphs.ell import _FUSE_CACHE
    g = _graph(120, 60, 37)
    before = set(_FUSE_CACHE)
    relation_plan_of(g)
    assert set(_FUSE_CACHE) <= before


def test_plan_builds_counter():
    """``graph.plan_builds{source="packed"}`` counts one per new graph and
    none on a memo hit; only a plan packed from COO counts ``coo``."""
    from repro.obs.metrics import DEFAULT_REGISTRY as reg

    def counts():
        return (reg.value("graph.plan_builds", source="packed"),
                reg.value("graph.plan_builds", source="coo"))

    p0, c0 = counts()
    g1, g2 = _graph(40, 20, 31), _graph(44, 22, 32)
    relation_plan_of(g1)
    assert counts() == (p0 + 1, c0)
    relation_plan_of(g1)                      # memo hit
    assert counts() == (p0 + 1, c0)
    relation_plan_of(g2)
    assert counts() == (p0 + 2, c0)
    _coo_built_plan(g1)
    assert counts() == (p0 + 2, c0 + 1)


def test_trainer_step_with_packed_plan_matches_coo_plan(packed_vs_coo):
    """One training step on the graph's plan and on the COO-built plan:
    same loss, and the same weights after the update, to 1e-6."""
    from repro.train.circuit_trainer import CircuitTrainConfig, \
        CircuitTrainer
    g, plan, ref = packed_vs_coo
    cfg = CircuitTrainConfig(hidden=32, k_cell=8, k_net=8, seed=3)
    out = []
    for p in (plan, ref):
        tr = CircuitTrainer(cfg, g.x_cell.shape[1], g.x_net.shape[1])
        loss = tr.train_epoch([dataclasses.replace(g, plan=p)])
        out.append((loss, jax.tree.leaves(tr.params)))
    (l_a, p_a), (l_b, p_b) = out
    assert abs(l_a - l_b) <= 1e-6 * max(1.0, abs(l_b))
    for a, b in zip(p_a, p_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


# --------------------- segment round-trip property ---------------------

rt_plans = st.integers(0, 2 ** 31 - 1).flatmap(lambda seed: st.tuples(
    st.just(seed), st.integers(9, 40), st.integers(5, 24)))


def _check_plan_roundtrip(plan, rels):
    """Tier-aware block property: every relation's matrix reappears exactly
    at its segment's block of the full-coordinate plan matrix, nothing
    lands outside the blocks, arena segments tile the rel chunk table /
    transposed super-arena, and dense segments tile the stacked
    ``dense_fwd``/``dense_bwd`` tables."""
    A = plan.to_dense()                   # (n_out_total, n_src_total)
    off = dict(zip(plan.src_types, plan.src_off))
    cov_a = np.zeros_like(A, bool)
    arena_pos = {id(s): i for i, s in enumerate(plan.arena_segments)}
    B = plan.bwd.to_dense() if plan.has_arena else None
    DF = np.asarray(plan.dense_fwd)
    rel_tab = np.asarray(plan.fwd.rel) if plan.has_arena else None
    for seg, r in zip(plan.segments, rels):
        et, s_t, d_t, dst, src, w = r
        dense = np.zeros((seg.n_dst, seg.n_src), np.float32)
        np.add.at(dense, (dst, src), w)
        so = off[seg.src_type]
        np.testing.assert_allclose(
            A[seg.out_off:seg.out_off + seg.n_dst, so:so + seg.n_src],
            dense, atol=1e-6, err_msg=f"fwd {et}")
        cov_a[seg.out_off:seg.out_off + seg.n_dst, so:so + seg.n_src] = True
        if seg.tier == "arena":
            # transposed super-arena addresses the FULL output concat
            np.testing.assert_allclose(
                B[seg.src_out_off:seg.src_out_off + seg.n_src,
                  seg.out_off:seg.out_off + seg.n_dst],
                dense.T, atol=1e-6, err_msg=f"bwd {et}")
            lo, hi = seg.fwd_chunks
            assert (rel_tab[lo:hi] == arena_pos[id(seg)]).all()
            assert seg.dense_off == -1
        else:
            np.testing.assert_allclose(
                DF[seg.dense_off:seg.dense_off + seg.n_dst,
                   so:so + seg.n_src],
                dense, atol=1e-6, err_msg=f"dense fwd {et}")
            assert seg.fwd_chunks == (0, 0) and seg.arena_out_off == -1
    assert A[~cov_a].sum() == 0
    np.testing.assert_allclose(np.asarray(plan.dense_bwd), DF.T, atol=0,
                               err_msg="dense_bwd is dense_fwd transposed")
    if plan.has_arena:
        assert rel_tab.shape[0] == plan.fwd.n_chunks
    assert plan.bwd_src_rows.shape[0] == plan.bwd.n_arena_rows


@given(rt_plans)
def test_relation_segment_roundtrip(args):
    """The block property holds for every tiering of the same relations:
    the default classification (these tiny graphs go all-dense), a
    threshold of −1 (all-arena, the pre-tiering layout), and a forced
    mixed-tier split."""
    seed, n_cell, n_net = args
    rng = np.random.default_rng(seed)
    rels = _mixed_relations(rng, n_cell, n_net)
    sizes = {"cell": n_cell, "net": n_net}
    for plan in (
            build_relation_plan(rels, sizes),
            build_relation_plan(rels, sizes, dense_threshold=-1),
            build_relation_plan(rels, sizes,
                                tiers={"near": "arena", "pin": "dense",
                                       "pinned": "arena"})):
        _check_plan_roundtrip(plan, rels)


# --------------------- collation rides the plan ------------------------

@pytest.fixture(scope="module")
def members():
    return [_graph(60, 30, 0), _graph(101, 55, 1), _graph(37, 20, 2)]


@pytest.fixture(scope="module")
def model_params():
    return init_drcircuitgnn(jax.random.PRNGKey(0), 16, 16, 32)


@pytest.mark.parametrize("backend", ["pallas_fused", "xla_fused"])
def test_collated_plan_padding_is_inert(members, model_params, backend):
    """Quantized collation with an attached plan reproduces the exact
    (serial, unquantized) collation on every member slice — through a jit
    whose graph (plan included) is a TRACED argument, forward and grad."""
    from repro.models.hgnn import batched_loss_fn

    params = model_params
    cfg = HeteroMPConfig(hidden=32, k_cell=8, k_net=8, backend=backend,
                         use_plan=True)
    cfg_ref = dataclasses.replace(cfg, use_plan=False)
    exact = collate_graphs(members, quantize=False, with_plan=False)
    quant = collate_graphs(members, quantize=True)
    assert quant.graph.plan is not None
    assert exact.graph.plan is None

    fwd = jax.jit(lambda p, g: drcircuitgnn_forward(p, g, cfg))
    p_ref = exact.split_cell(
        drcircuitgnn_forward(params, exact.graph, cfg_ref))
    p_plan = quant.split_cell(fwd(params, quant.graph))
    for i, (a, r) in enumerate(zip(p_plan, p_ref)):
        _assert_close(np.asarray(a), np.asarray(r),
                      f"member {i} {backend} padding")

    g_q = jax.grad(batched_loss_fn)(params, quant.graph, quant.cell_weight,
                                    cfg)
    g_e = jax.grad(batched_loss_fn)(params, exact.graph, exact.cell_weight,
                                    cfg_ref)
    for (pa, a), (_, r) in zip(jax.tree_util.tree_leaves_with_path(g_q),
                               jax.tree_util.tree_leaves_with_path(g_e)):
        _assert_close(np.asarray(a), np.asarray(r),
                      f"grad {jax.tree_util.keystr(pa)} {backend}")


def test_collated_plan_filler_members_inert(members, model_params):
    """Filler replicas change nothing for the real members on the plan
    path (the deadline-batcher property)."""
    cfg = HeteroMPConfig(hidden=32, k_cell=8, k_net=8, backend="xla_fused",
                         use_plan=True)
    plain = collate_graphs(members)
    padded = collate_graphs(members + [members[-1]], n_real=len(members))
    a = plain.split_cell(
        drcircuitgnn_forward(model_params, plain.graph, cfg))
    b = padded.split_cell(
        drcircuitgnn_forward(model_params, padded.graph, cfg))
    assert len(a) == len(b) == len(members)
    for i, (x, y) in enumerate(zip(a, b)):
        _assert_close(np.asarray(y), np.asarray(x), f"member {i} filler")


def test_collated_plan_signature_stable_in_bucket():
    """Jittered same-class batches share one padded signature with a shared
    BucketLayout — now including the plan's super-arena dims (plan_chunk
    pinning + plan_min_chunks floors)."""
    layout = BucketLayout()
    b1 = collate_graphs([_graph(60, 30, 0), _graph(58, 29, 1)],
                        node_bits=1, layout=layout)
    b2 = collate_graphs([_graph(63, 31, 2), _graph(59, 28, 3)],
                        node_bits=1, layout=layout)
    assert b1.graph.plan is not None and b2.graph.plan is not None
    assert b1.signature == b2.signature
    assert layout.plan_chunk.keys() == {"fwd", "bwd"}


# --------------------- shape-bucketed learnable nnz --------------------

def test_edge_nnz_quantized_and_padding_inert():
    """collate_graphs(with_eids=True) rounds the traced-weight nnz up the
    arena grid (layout-floored), and the zero-padded tail is inert: the
    learnable op over the padded vector equals the exact-nnz result, with
    zero gradient on the pad slots."""
    layout = BucketLayout()
    b1 = collate_graphs([_graph(60, 30, 0), _graph(58, 29, 1)],
                        node_bits=1, with_eids=True, layout=layout)
    b2 = collate_graphs([_graph(63, 31, 2), _graph(59, 28, 3)],
                        node_bits=1, with_eids=True, layout=layout)
    et = "near"
    assert b1.edge_nnz[et] >= b1.edge_nnz_exact[et]
    # same bucket -> same padded nnz even though exact counts differ
    assert b1.edge_nnz[et] == b2.edge_nnz[et]
    assert b1.edge_nnz_exact[et] != b2.edge_nnz_exact[et]

    rng = np.random.default_rng(0)
    batch = b1
    es = batch.graph.edges[et]
    exact, padded = batch.edge_nnz_exact[et], batch.edge_nnz[et]
    member_ws = [rng.normal(
        size=batch.edge_eid_offsets[et][1] if i == 0
        else exact - batch.edge_eid_offsets[et][1]).astype(np.float32)
        for i in range(2)]
    w_pad = batch.concat_edge_weights(et, member_ws)
    assert w_pad.shape[0] == padded
    d, k = 16, 4
    n = batch.graph.n_cell
    xv = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    xi = jnp.asarray(rng.integers(0, d, size=(n, k)).astype(np.int32))

    def f(wv, nnz):
        return ops.drspmm_learnable(es.adj, es.adj_t, nnz, wv, xv, xi, d,
                                    backend="xla_fused")

    y_pad = f(w_pad, padded)
    y_exact = f(w_pad[:exact], exact)
    _assert_close(np.asarray(y_pad), np.asarray(y_exact), "padded nnz fwd")
    gw = jax.grad(lambda wv: jnp.sum(jnp.sin(f(wv, padded))))(w_pad)
    assert np.all(np.asarray(gw[exact:]) == 0.0), "pad slots got gradient"


# ------------------------- params hot-swap -----------------------------

def test_engine_params_hot_swap(members):
    """update_params() swaps replicas between batches: post-swap requests
    are served by the new weights and stamped with the new version; no
    recompile is paid for the swap."""
    from repro.serve import CircuitServeEngine

    cfg = HeteroMPConfig(hidden=32, k_cell=8, k_net=8, backend="xla_fused")
    p0 = init_drcircuitgnn(jax.random.PRNGKey(0), 16, 16, 32)
    p1 = init_drcircuitgnn(jax.random.PRNGKey(1), 16, 16, 32)
    eng = CircuitServeEngine(p0, cfg, max_batch=len(members))
    g = members[0]

    r0 = eng.submit(g)
    eng.run()
    assert eng.result(r0).params_version == 0
    compiles_before = eng.compiles

    assert eng.update_params(p1) == 1
    assert eng.params_version == 1
    r1 = eng.submit(g)
    eng.run()
    req1 = eng.result(r1)
    assert req1.params_version == 1
    assert eng.compiles == compiles_before, "hot swap must not recompile"
    assert eng.stats()["params_version"] == 1

    ref0 = np.asarray(drcircuitgnn_forward(p0, g, cfg))
    ref1 = np.asarray(drcircuitgnn_forward(p1, g, cfg))
    _assert_close(eng.result(r0).pred, ref0, "pre-swap prediction")
    _assert_close(req1.pred, ref1, "post-swap prediction")
    assert not np.allclose(ref0, ref1), "swap should change predictions"
