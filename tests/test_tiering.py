"""Size-adaptive dense-tier routing (DESIGN.md §14).

Sub-crossover relations leave the super-arena chunk walk for a batched
masked dense matmul; these tests pin the routing rule and the hybrid
executor around the crossover itself:

* fwd+grad parity of the hybrid ``drspmm_multi`` (both executors) against
  the dense per-relation oracle on plans that STRADDLE the threshold
  (mixed arena + dense tiers in one direction-group) and on single-tier
  plans of the same relations, including the default-constant crossover
  on a relation genuinely above it;
* tier routing is a function of (nnz, table area) alone — invariant under
  degree-preserving edge/node permutations (hypothesis property);
* exact threshold boundary: nnz == cutoff lands dense, cutoff + 1 lands
  arena, and both plans stay numerically identical;
* collation filler members stay inert when the batch plan routes through
  the dense tier;
* mesh-sharded parity on a mixed-tier plan (sharding flattens every
  relation back into per-shard local arenas — the documented §14 rule).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # bare container: seeded fallback
    from _hyp_fallback import given, settings, strategies as st

from _multidev import run_multidev
from repro.core.cbsr import cbsr_from_dense
from repro.core.drelu import drelu
from repro.core.hetero_mp import HeteroMPConfig
from repro.graphs.circuit import EDGE_SCHEMA
from repro.graphs.collate import collate_graphs
from repro.graphs.ell import DENSE_TIER_NNZ, build_relation_plan, \
    pack_ell_pair
from repro.graphs.generator import generate_partition, pack_graph_parallel
from repro.kernels import ops, ref
from repro.models.hgnn import drcircuitgnn_forward, init_drcircuitgnn

settings.register_profile("fast", max_examples=15, deadline=None)
settings.load_profile("fast")

BACKENDS = ("pallas_fused", "xla_fused")


def _assert_close(actual, ref, msg):
    atol = 1e-5 * max(1.0, float(np.abs(ref).max()) if ref.size else 1.0)
    np.testing.assert_allclose(actual, ref, atol=atol, rtol=1e-5,
                               err_msg=msg)


def _graph(n_cell, n_net, seed):
    coo, xc, xn, y = generate_partition(np.random.default_rng(seed),
                                        n_cell, n_net)
    return pack_graph_parallel(coo, n_cell, n_net, xc, xn, y)


def _mk(rng, n_dst, n_src, nnz):
    d = rng.integers(0, n_dst, nnz)
    s = rng.integers(0, n_src, nnz)
    pairs = np.unique(np.stack([d, s], 1), axis=0)
    w = rng.normal(size=pairs.shape[0]).astype(np.float32)
    w[w == 0] = 1.0
    return pairs[:, 0], pairs[:, 1], w


def _mixed_relations(rng, n_cell, n_net, near_nnz=None):
    sizes = {"cell": n_cell, "net": n_net}
    out = []
    for et, nnz in (("near", near_nnz or 4 * n_cell), ("pin", 2 * n_cell),
                    ("pinned", 2 * n_cell)):
        s_t, d_t = EDGE_SCHEMA[et]
        out.append((et, s_t, d_t,
                    *_mk(rng, sizes[d_t], sizes[s_t], max(nnz, 1))))
    return out


def _cbsr_pair(rng, n_cell, n_net, dim, k_cell=8, k_net=6):
    cc = cbsr_from_dense(drelu(jnp.asarray(
        rng.normal(size=(n_cell, dim)).astype(np.float32)), k_cell), k_cell)
    cn = cbsr_from_dense(drelu(jnp.asarray(
        rng.normal(size=(n_net, dim)).astype(np.float32)), k_net), k_net)
    return cc, cn


def _serial_refs(rels, sizes, cc, cn, dim, vc, vn):
    out = {}
    for et, s_t, d_t, dst, src, w in rels:
        adj, adj_t = pack_ell_pair(dst, src, w, sizes[d_t], sizes[s_t])
        c = cc if s_t == "cell" else cn
        v = vc if s_t == "cell" else vn
        out[et] = ref.drspmm_fwd_ref(adj, v, c.idx, dim)
    return out


def _check_parity(plan, rels, sizes, cc, cn, dim, backend, tag):
    refs = _serial_refs(rels, sizes, cc, cn, dim, cc.values, cn.values)
    ys = ops.drspmm_multi(plan, {"cell": (cc.values, cc.idx),
                                 "net": (cn.values, cn.idx)}, dim,
                          backend=backend)
    for et in refs:
        _assert_close(np.asarray(ys[et]), np.asarray(refs[et]),
                      f"{tag} fwd {backend}/{et}")

    def loss_multi(vc, vn):
        ys = ops.drspmm_multi(plan, {"cell": (vc, cc.idx),
                                     "net": (vn, cn.idx)}, dim,
                              backend=backend)
        return sum(jnp.sum(y ** 2) for y in ys.values())

    def loss_serial(vc, vn):
        refs = _serial_refs(rels, sizes, cc, cn, dim, vc, vn)
        return sum(jnp.sum(y ** 2) for y in refs.values())

    g = jax.grad(loss_multi, argnums=(0, 1))(cc.values, cn.values)
    g_ref = jax.grad(loss_serial, argnums=(0, 1))(cc.values, cn.values)
    for a, r, nm in zip(g, g_ref, ("cell", "net")):
        _assert_close(np.asarray(a), np.asarray(r),
                      f"{tag} grad {backend}/{nm}")


# ------------------- hybrid parity across the crossover -----------------

# crossovers over the straddle relations (near ≈ 4·57 edges, pin/pinned
# ≈ 2·57): every relation on the arena tier, near on the arena tier and
# pin/pinned on the dense tier, every relation on the dense tier
THRESHOLDS = {"arena": -1, "mixed": 150, "dense": 10_000}


@pytest.fixture(scope="module")
def straddle_setup():
    """Relations that straddle an overridden crossover, and their plan at
    each of ``THRESHOLDS``."""
    rng = np.random.default_rng(3)
    n_cell, n_net, dim = 57, 29, 64
    rels = _mixed_relations(rng, n_cell, n_net)
    sizes = {"cell": n_cell, "net": n_net}
    plans = {tier: build_relation_plan(rels, sizes, dense_threshold=thr)
             for tier, thr in THRESHOLDS.items()}
    assert plans["mixed"].segment("near").tier == "arena"
    assert plans["mixed"].segment("pin").tier == "dense"
    assert plans["mixed"].has_arena and plans["mixed"].has_dense
    assert not plans["arena"].has_dense and not plans["dense"].has_arena
    cc, cn = _cbsr_pair(rng, n_cell, n_net, dim)
    return plans, rels, sizes, cc, cn, dim


@pytest.mark.parametrize("tiers", list(THRESHOLDS))
@pytest.mark.parametrize("backend", BACKENDS)
def test_hybrid_multi_matches_serial(straddle_setup, backend, tiers):
    """drspmm_multi == the dense oracle of each relation, fwd + grads in
    both source types, under both executors, on the mixed-tier plan and
    on the single-tier plans of the same relations."""
    plans, rels, sizes, cc, cn, dim = straddle_setup
    _check_parity(plans[tiers], rels, sizes, cc, cn, dim, backend, tiers)


def test_default_crossover_straddle_parity():
    """Same property at the DEFAULT measured crossover, with a `near`
    genuinely above ``DENSE_TIER_NNZ`` and the cell–net relations below it
    — the real mixed-tier shape medium designs produce."""
    rng = np.random.default_rng(7)
    n_cell, n_net, dim = 300, 150, 32
    rels = _mixed_relations(rng, n_cell, n_net,
                            near_nnz=2 * DENSE_TIER_NNZ)
    sizes = {"cell": n_cell, "net": n_net}
    plan = build_relation_plan(rels, sizes)
    assert plan.segment("near").tier == "arena"
    assert plan.segment("pin").tier == "dense"
    cc, cn = _cbsr_pair(rng, n_cell, n_net, dim)
    _check_parity(plan, rels, sizes, cc, cn, dim, "xla_fused", "default-thr")


# --------------------------- threshold boundary -------------------------

def test_threshold_boundary_exact_nnz():
    """nnz == cutoff routes dense, nnz == cutoff + 1 routes arena (the rule
    is ``nnz <= thr``), and both plans compute identical numbers."""
    rng = np.random.default_rng(11)
    n_cell, n_net, dim = 80, 40, 32
    lin = rng.choice(n_cell * n_cell, size=96, replace=False)
    dst, src = np.divmod(np.sort(lin), n_cell)
    w = rng.normal(size=96).astype(np.float32)
    w[w == 0] = 1.0
    rels = [("near", "cell", "cell", dst, src, w)]
    sizes = {"cell": n_cell, "net": n_net}
    nnz = 96
    cc, cn = _cbsr_pair(rng, n_cell, n_net, dim)
    ys = {}
    for thr, want in ((nnz, "dense"), (nnz - 1, "arena")):
        plan = build_relation_plan(rels, sizes, dense_threshold=thr)
        assert plan.segments[0].tier == want, (thr, want)
        _check_parity(plan, rels, sizes, cc, cn, dim, "xla_fused",
                      f"thr={thr}")
        ys[want] = np.asarray(ops.drspmm_multi(
            plan, {"cell": (cc.values, cc.idx), "net": (cn.values, cn.idx)},
            dim, backend="xla_fused")["near"])
    np.testing.assert_allclose(ys["dense"], ys["arena"], atol=1e-5,
                               rtol=1e-5, err_msg="tier flip changed math")


# ----------------- routing invariance (hypothesis property) -------------

@given(st.integers(0, 2 ** 31 - 1))
def test_tier_routing_invariant_under_permutation(seed):
    """Tier routing is decided by (nnz, table area) alone: shuffling edge
    order and relabeling nodes (a degree-multiset-preserving permutation)
    must route every relation to the same tier."""
    rng = np.random.default_rng(seed)
    n_cell, n_net = 31, 17
    rels = _mixed_relations(rng, n_cell, n_net)
    sizes = {"cell": n_cell, "net": n_net}
    perm = {"cell": rng.permutation(n_cell), "net": rng.permutation(n_net)}
    prels = []
    for et, s_t, d_t, dst, src, w in rels:
        o = rng.permutation(dst.shape[0])
        prels.append((et, s_t, d_t, perm[d_t][dst][o], perm[s_t][src][o],
                      w[o]))
    thr = int(rng.integers(0, 5 * n_cell))
    base = build_relation_plan(rels, sizes, dense_threshold=thr)
    perm_plan = build_relation_plan(prels, sizes, dense_threshold=thr)
    assert [s.tier for s in base.segments] == \
        [s.tier for s in perm_plan.segments]


# ------------------- collated fillers through the dense tier ------------

def test_collated_filler_inert_through_dense_tier():
    """Filler replicas change nothing for the real members when the batch
    plan routes relations through the dense tier (tiny members: the whole
    direction-group is sub-crossover)."""
    members = [_graph(60, 30, 0), _graph(37, 20, 2)]
    params = init_drcircuitgnn(jax.random.PRNGKey(0), 16, 16, 32)
    cfg = HeteroMPConfig(hidden=32, k_cell=8, k_net=8, backend="xla_fused",
                         use_plan=True)
    plain = collate_graphs(members)
    padded = collate_graphs(members + [members[-1]], n_real=len(members))
    assert padded.graph.plan.has_dense, \
        {s.etype: s.tier for s in padded.graph.plan.segments}
    a = plain.split_cell(drcircuitgnn_forward(params, plain.graph, cfg))
    b = padded.split_cell(drcircuitgnn_forward(params, padded.graph, cfg))
    assert len(a) == len(b) == len(members)
    for i, (x, y) in enumerate(zip(a, b)):
        _assert_close(np.asarray(y), np.asarray(x), f"member {i} filler")


# ----------------------- sharded mixed-tier parity ----------------------

SHARDED_SCRIPT = r"""
import sys

import jax
import jax.numpy as jnp
import numpy as np

n = int(sys.argv[1])
assert jax.device_count() == n, (jax.device_count(), n)

from repro.core.cbsr import cbsr_from_dense
from repro.core.drelu import drelu
from repro.graphs.ell import build_relation_plan
from repro.kernels import ops
from repro.sharding.plan_shard import shard_relation_plan

rng = np.random.default_rng(3)
n_cell, n_net, dim, k = 57, 29, 32, 8


def mk(n_dst, n_src, nnz):
    d = rng.integers(0, n_dst, nnz)
    s = rng.integers(0, n_src, nnz)
    pairs = np.unique(np.stack([d, s], 1), axis=0)
    w = rng.normal(size=pairs.shape[0]).astype(np.float32)
    w[w == 0] = 1.0
    return pairs[:, 0], pairs[:, 1], w


rels = [("near", "cell", "cell", *mk(n_cell, n_cell, 4 * n_cell)),
        ("pin", "cell", "net", *mk(n_net, n_cell, 2 * n_cell)),
        ("pinned", "net", "cell", *mk(n_cell, n_net, 2 * n_cell))]
sizes = {"cell": n_cell, "net": n_net}
plan = build_relation_plan(rels, sizes, dense_threshold=150)
assert plan.has_arena and plan.has_dense, \
    {s.etype: s.tier for s in plan.segments}
splan = shard_relation_plan(plan, n)

cc = cbsr_from_dense(drelu(jnp.asarray(
    rng.normal(size=(n_cell, dim)).astype(np.float32)), k), k)
cn = cbsr_from_dense(drelu(jnp.asarray(
    rng.normal(size=(n_net, dim)).astype(np.float32)), k), k)
cbsr = {"cell": (cc.values, cc.idx), "net": (cn.values, cn.idx)}

y_ref = ops.drspmm_multi(plan, cbsr, dim, backend="xla_fused")
y_sh = ops.drspmm_multi_sharded(splan, cbsr, dim, backend="xla_fused")
for et in y_ref:
    r = np.asarray(y_ref[et])
    atol = 2e-5 * max(1.0, float(np.abs(r).max()))
    np.testing.assert_allclose(np.asarray(y_sh[et]), r, atol=atol,
                               rtol=2e-5, err_msg=f"fwd {et}")


def loss(op, p):
    def f(vc, vn):
        ys = op(p, {"cell": (vc, cc.idx), "net": (vn, cn.idx)}, dim,
                backend="xla_fused")
        return sum(jnp.sum(jnp.sin(y)) for y in ys.values())
    return f


g_ref = jax.grad(loss(ops.drspmm_multi, plan),
                 argnums=(0, 1))(cc.values, cn.values)
g_sh = jax.grad(loss(ops.drspmm_multi_sharded, splan),
                argnums=(0, 1))(cc.values, cn.values)
for a, r, t in zip(g_sh, g_ref, ("cell", "net")):
    r = np.asarray(r)
    atol = 2e-5 * max(1.0, float(np.abs(r).max()))
    np.testing.assert_allclose(np.asarray(a), r, atol=atol, rtol=2e-5,
                               err_msg=f"grad {t}")
print("MIXED_TIER_SHARDED_OK")
"""


@pytest.mark.slow
@pytest.mark.parametrize("n", [2, 3])
def test_sharded_mixed_tier_parity(n):
    """The sharded executor reproduces the hybrid single-device path on a
    plan that mixes tiers — sharding flattens every relation (dense tier
    included) back into per-shard local arenas (DESIGN.md §14)."""
    run_multidev(SHARDED_SCRIPT, n_devices=n, argv=[n],
                 expect=("MIXED_TIER_SHARDED_OK",))
