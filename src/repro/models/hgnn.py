"""DR-CircuitGNN model (paper Fig. 1) + homogeneous GNN baselines.

DR-CircuitGNN: per-type input Linear → N × HeteroConv → per-cell Linear head
(congestion regression).  Baselines: GCN / GraphSAGE / GAT stacks on the
homogenized graph (all edges merged, single node space), matching the paper's
Table 2 comparison protocol.

Each HeteroConv layer dispatches its whole message passing through the
graph's :class:`~repro.graphs.ell.RelationPlan` when one is available
(``ops.drspmm_multi`` — one kernel per direction-group, DESIGN.md §9); the
per-direction serial loop remains the reference (core/hetero_mp.py).

Both stacks run through the deep-backbone executor (models/backbone.py,
DESIGN.md §13): every forward takes an optional :class:`BackboneSpec`
selecting wiring (plain/residual/dense) and layer-granular remat; the
entry points here stay thin wrappers with exact init/numeric parity to the
pre-backbone hardcoded loops (the default spec IS the old behavior)."""

from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.drelu import drelu
from repro.core.hetero_mp import (HeteroLayerParams, HeteroMPConfig,
                                  _plan_for, hetero_conv, init_hetero_layer)
from repro.graphs.circuit import CircuitGraph, with_fused_edges
from repro.graphs.ell import BucketedELL, ell_to_coo, pack_fused_eid_pair
from repro.kernels import ops
from repro.models.backbone import (BackboneSpec, apply_stack, init_stack,
                                   spec_for)
from repro.sharding.plan_shard import ShardedRelationPlan


# ---------------------------------------------------------------------------
# DR-CircuitGNN
# ---------------------------------------------------------------------------

class DRCircuitGNNParams(NamedTuple):
    in_cell: jax.Array          # (f_cell, H)
    in_net: jax.Array           # (f_net, H)
    layers: Tuple[HeteroLayerParams, ...]
    head_w: jax.Array           # (H, 1)
    head_b: jax.Array           # (1,)


def init_drcircuitgnn(key, f_cell: int, f_net: int, hidden: int,
                      n_layers: int = 2) -> DRCircuitGNNParams:
    (k_ic, k_in), layers, (k_head,) = init_stack(
        key, n_layers, lambda k, _i: init_hetero_layer(k, hidden),
        n_pre=2, n_post=1)
    s_c, s_n = 1.0 / jnp.sqrt(f_cell), 1.0 / jnp.sqrt(f_net)
    return DRCircuitGNNParams(
        in_cell=jax.random.uniform(k_ic, (f_cell, hidden), jnp.float32, -s_c, s_c),
        in_net=jax.random.uniform(k_in, (f_net, hidden), jnp.float32, -s_n, s_n),
        layers=layers,
        head_w=jax.random.uniform(k_head, (hidden, 1), jnp.float32,
                                  -1.0 / jnp.sqrt(hidden), 1.0 / jnp.sqrt(hidden)),
        head_b=jnp.zeros((1,)))


def _hetero_body(cfg: HeteroMPConfig):
    """One checkpointable backbone layer: hetero_conv + the inter-layer
    activation.  ``const`` threads the layer-invariant (graph, plan) pair
    resolved ONCE per stack application — under remat they are saved input
    residuals, not recomputed (models/backbone.py)."""
    def body(lp, state, const):
        graph, plan = const
        h_cell, h_net = hetero_conv(lp, graph, *state, cfg, plan=plan)
        # inter-layer nonlinearity IS D-ReLU (dense form) — the sparsifier
        # doubles as the activation, per the paper's framing.
        if cfg.use_drelu:
            return drelu(h_cell, cfg.k_cell), drelu(h_net, cfg.k_net)
        return jax.nn.relu(h_cell), jax.nn.relu(h_net)
    return body


def _concrete_bucketed(graph: CircuitGraph) -> bool:
    adj = graph.edges["near"].adj
    return (isinstance(adj, BucketedELL)
            and not isinstance(adj.buckets[0].nbr, jax.core.Tracer))


def drcircuitgnn_forward(params: DRCircuitGNNParams, graph: CircuitGraph,
                         cfg: HeteroMPConfig,
                         spec: Optional[BackboneSpec] = None) -> jax.Array:
    """Per-cell congestion prediction in [0, 1].

    ``spec`` selects the backbone wiring/remat (DESIGN.md §13); the
    default — plain wiring, no remat, depth from ``params`` — reproduces
    the pre-backbone loop bit-for-bit."""
    if spec is None:
        spec = spec_for(params.layers, params.head_w.shape[0])
    h_cell = graph.x_cell @ params.in_cell
    h_net = graph.x_net @ params.in_net
    # layer-invariant hoist: ONE plan resolution per stack application
    plan = _plan_for(graph, cfg, h_cell.shape[-1])
    if plan is None and _concrete_bucketed(graph):
        # The serial path runs inside the stack, where remat's checkpoint
        # traces the graph and the ops take no BucketedELL: fuse host-side
        # here (memoized per packing), as the trainer's no-plan step does.
        graph = with_fused_edges(graph)
    if spec.remat and isinstance(plan, ShardedRelationPlan):
        # The mesh-sharded executor (DESIGN.md §12) needs its plan
        # pre-placed with a NamedSharding, which a checkpoint-traced primal
        # cannot express — so the sharded path draws no checkpoint boundary
        # (remat composes with data-parallel replicas, not with §12 yet).
        spec = dataclasses.replace(spec, remat=False)
    h_cell, h_net = apply_stack(params.layers, (h_cell, h_net),
                                _hetero_body(cfg), spec, (graph, plan))
    pred = jax.nn.sigmoid(h_cell @ params.head_w + params.head_b)
    return pred[:, 0]


def loss_fn(params, graph, cfg,
            spec: Optional[BackboneSpec] = None) -> jax.Array:
    pred = drcircuitgnn_forward(params, graph, cfg, spec)
    with jax.named_scope("loss"):
        return jnp.mean((pred - graph.y_cell) ** 2)


def batched_loss_fn(params, graph, cell_weight, cfg,
                    spec: Optional[BackboneSpec] = None) -> jax.Array:
    """Loss over a block-diagonal collated batch (graphs/collate.py).

    ``cell_weight`` is 1/(n_members·n_cell_i) on member i's cells and 0 on
    padding, so this equals the mean of the members' per-graph ``loss_fn``
    values — batched gradients match the per-graph loop exactly."""
    pred = drcircuitgnn_forward(params, graph, cfg, spec)
    with jax.named_scope("loss"):
        return jnp.sum(cell_weight * (pred - graph.y_cell) ** 2)


# ---------------------------------------------------------------------------
# Homogeneous baselines (GCN / SAGE / GAT) on the homogenized graph
# ---------------------------------------------------------------------------

class HomoParams(NamedTuple):
    w_in: jax.Array
    w_layers: Tuple[Any, ...]
    head_w: jax.Array
    head_b: jax.Array


def homogenize(graph: CircuitGraph):
    """Merge node spaces: [cells; nets], all edges unified, mean-normalized.

    Features are zero-padded into a common width.  Returns (adj, adj_t, x, y,
    n_cell) with adj in BucketedELL over the merged id space."""
    import numpy as np
    from repro.graphs.ell import pack_ell_pair

    n_c, n_n = graph.n_cell, graph.n_net
    n = n_c + n_n
    dsts, srcs = [], []
    for et, es in graph.edges.items():
        a = np.asarray(es.adj.to_dense())
        d, s = np.nonzero(a)
        if et == "near":
            pass                      # cell->cell
        elif et == "pin":
            d = d + n_c               # dst nets offset
        elif et == "pinned":
            s = s + n_c               # src nets offset
        dsts.append(d), srcs.append(s)
    # self-loops (Â = A + I — GCN/GAT need the node's own features)
    loop = np.arange(n)
    dsts.append(loop), srcs.append(loop)
    dst = np.concatenate(dsts)
    src = np.concatenate(srcs)
    deg = np.bincount(dst, minlength=n).astype(np.float32)
    w = 1.0 / np.maximum(deg[dst], 1.0)
    adj, adj_t = pack_ell_pair(dst, src, w, n, n)

    f = max(graph.x_cell.shape[1], graph.x_net.shape[1])
    xc = jnp.pad(graph.x_cell, ((0, 0), (0, f - graph.x_cell.shape[1])))
    xn = jnp.pad(graph.x_net, ((0, 0), (0, f - graph.x_net.shape[1])))
    x = jnp.concatenate([xc, xn], 0)
    return adj, adj_t, x, graph.y_cell, n_c


def init_homo(key, f_in: int, hidden: int, n_layers: int = 3,
              kind: str = "gcn", nnz: int = 0) -> HomoParams:
    """``kind="gat_edge"`` layers carry a free per-edge attention logit
    vector (nnz,) — pass ``nnz`` (e.g. ``adj.nnz`` of the homogenized
    graph).  Zero-initialized logits start at uniform attention, which
    coincides with the mean aggregation the other baselines use."""
    s = 1.0 / jnp.sqrt(hidden)

    def layer_init(k, _i):
        if kind == "sage":
            return (jax.random.uniform(k, (hidden, hidden),
                                       jnp.float32, -s, s),
                    jax.random.uniform(jax.random.fold_in(k, 1),
                                       (hidden, hidden), jnp.float32, -s, s))
        if kind == "gat":
            return (jax.random.uniform(k, (hidden, hidden),
                                       jnp.float32, -s, s),
                    jax.random.uniform(jax.random.fold_in(k, 1),
                                       (2 * hidden,), jnp.float32, -s, s))
        if kind == "gat_edge":
            assert nnz > 0, "gat_edge needs the homogenized edge count (nnz)"
            return (jax.random.uniform(k, (hidden, hidden),
                                       jnp.float32, -s, s),
                    jnp.zeros((nnz,), jnp.float32))
        return jax.random.uniform(k, (hidden, hidden),  # gcn
                                  jnp.float32, -s, s)

    _, layers, (k_in, k_head) = init_stack(key, n_layers, layer_init,
                                           n_pre=0, n_post=2)
    si = 1.0 / jnp.sqrt(f_in)
    return HomoParams(
        w_in=jax.random.uniform(k_in, (f_in, hidden), jnp.float32, -si, si),
        w_layers=layers,
        head_w=jax.random.uniform(k_head, (hidden, 1), jnp.float32, -s, s),
        head_b=jnp.zeros((1,)))


# Memoized per-adjacency edge-ID packing for learnable per-edge attention
# (kind="gat_edge"): host-side one-time preprocessing, id-keyed with weakref
# guards like graphs/ell.py::_FUSE_CACHE.
_EDGE_PACK_CACHE: Dict[int, tuple] = {}


def learnable_edge_packing(adj: BucketedELL):
    """(fwd_arena, bwd_arena, dst_canon, src_canon, w_canon, nnz) for
    ``adj``'s edge set.

    The fused eid arenas feed :func:`repro.kernels.ops.drspmm_learnable`;
    ``dst_canon``/``src_canon`` (nnz,) are the canonical
    (dst-stable-sorted) edge endpoints — segment ids for per-destination
    softmax reductions and gather ids for per-source scores — and
    ``w_canon`` carries ``adj``'s fixed weights in the same order (the
    mean-normalization the "gat" branch folds into its attention).  A
    canonical per-edge parameter vector (nnz,) aligns with all of them.
    """
    key = id(adj)
    hit = _EDGE_PACK_CACHE.get(key)
    if hit is not None and hit[0]() is adj:
        return hit[1]
    dst, src, w = ell_to_coo(adj)
    order = np.argsort(dst, kind="stable")
    dst, src, w = dst[order], src[order], w[order]
    fwd, bwd, _order, nnz = pack_fused_eid_pair(dst, src, adj.n_dst,
                                                adj.n_src)
    pack = (fwd, bwd, dst.astype(np.int32), src.astype(np.int32),
            w.astype(np.float32), nnz)
    _EDGE_PACK_CACHE[key] = (
        weakref.ref(adj, lambda _: _EDGE_PACK_CACHE.pop(key, None)), pack)
    return pack


def _homo_body(kind: str, adj, adj_t, backend: ops.Backend):
    """One homogeneous backbone layer (relu included).  ``adj``/``adj_t``
    are closed over — the homo baselines run on concrete (host-packed)
    graphs, which the ops fuse host-side on first use, and the
    gat/gat_edge kinds need the host-side :func:`learnable_edge_packing`
    (fused eid arenas) anyway."""
    def body(lw, h, _const):
        if kind == "sage":
            w_nbr, w_self = lw
            agg = ops.spmm(adj, adj_t, h, backend=backend)
            h = jax.nn.relu(agg @ w_nbr + h @ w_self)
        elif kind == "gat":
            w, a = lw
            hw = h @ w
            # single-head GAT, source-score attention plus an explicit
            # self-attention term.  The additive GATv1 logit
            # e_ij = σ(s_dst_i + s_src_j) factorizes in exp space and the
            # destination part cancels in the softmax ratio — but the self
            # pair (i, i) keeps its full joint score, which is what lets
            # attention upweight a node's own features.
            lr_src = jax.nn.leaky_relu(hw @ a[: hw.shape[1]])
            lr_self = jax.nn.leaky_relu(
                hw @ a[: hw.shape[1]] + hw @ a[hw.shape[1]:])
            # Exponentiating unbounded logits overflows for large-magnitude
            # features (exp→inf, num/den→NaN).  num and den are both linear
            # in the exp'd scores, so a per-destination shift cancels in
            # the ratio: subtract each destination's max incoming logit
            # before exp.  (A global max would keep exp finite but
            # underflow every node far below the hottest one to 0/0; the
            # per-destination form keeps the largest term at exp(0) for
            # EVERY node.)  The per-edge gather routes the aggregation
            # through the fused learnable op; adj's mean-normalization
            # weights ride along in the attention, so moderate-scale
            # numerics match the SpMM-decomposed form exactly.
            fwd_e, bwd_e, dst_c, src_c, w_c, nnz = \
                learnable_edge_packing(adj)
            e_log = lr_src[src_c]                     # (nnz,) per-edge score
            m = jnp.maximum(
                jax.ops.segment_max(e_log, dst_c, num_segments=adj.n_dst),
                lr_self)
            m = jax.lax.stop_gradient(jnp.where(jnp.isfinite(m), m, 0.0))
            att = jnp.asarray(w_c) * jnp.exp(e_log - m[dst_c])
            s_self = jnp.exp(lr_self - m)
            xi = jnp.broadcast_to(
                jnp.arange(hw.shape[1], dtype=jnp.int32)[None, :], hw.shape)
            num = ops.drspmm_learnable(fwd_e, bwd_e, nnz, att, hw, xi,
                                       hw.shape[1], backend=backend)
            den = jax.ops.segment_sum(att, dst_c, num_segments=adj.n_dst)
            num = num + s_self[:, None] * hw
            den = den + s_self
            h = jax.nn.relu(num / jnp.maximum(den, 1e-6)[:, None])
        elif kind == "gat_edge":
            # Learnable per-edge attention through the fused learnable op:
            # every edge carries a free logit s_e; softmax over each
            # destination's in-edges (self-loops are already in the
            # homogenized edge set) weights the aggregation, and dL/ds
            # flows through drspmm_learnable's sampled dw reduction.
            w, s = lw
            hw = h @ w
            fwd_e, bwd_e, dst_c, _src_c, _w_c, nnz = \
                learnable_edge_packing(adj)
            logit = jax.nn.leaky_relu(s)
            # per-destination max subtraction (exact softmax stabilization:
            # per-edge logits make the per-node max expressible, unlike the
            # factorized "gat" branch above)
            m = jax.ops.segment_max(logit, dst_c, num_segments=adj.n_dst)
            m = jnp.where(jnp.isfinite(m), m, 0.0)    # edge-less rows: -inf
            att = jnp.exp(logit - jax.lax.stop_gradient(m)[dst_c])
            # dense h as trivially-CBSR operand: k = hidden, idx = iota
            xi = jnp.broadcast_to(
                jnp.arange(hw.shape[1], dtype=jnp.int32)[None, :], hw.shape)
            num = ops.drspmm_learnable(fwd_e, bwd_e, nnz, att, hw, xi,
                                       hw.shape[1], backend=backend)
            den = jax.ops.segment_sum(att, dst_c, num_segments=adj.n_dst)
            h = jax.nn.relu(num / jnp.maximum(den, 1e-6)[:, None])
        else:
            agg = ops.spmm(adj, adj_t, h, backend=backend)
            h = jax.nn.relu(agg @ lw)
        return h
    return body


def homo_forward(params: HomoParams, adj, adj_t, x, n_cell: int,
                 kind: str = "gcn",
                 backend: ops.Backend = ops.DEFAULT_BACKEND,
                 spec: Optional[BackboneSpec] = None) -> jax.Array:
    if spec is None:
        spec = spec_for(params.w_layers, params.head_w.shape[0])
    h = x @ params.w_in
    h = apply_stack(params.w_layers, h, _homo_body(kind, adj, adj_t, backend),
                    spec, None)
    pred = jax.nn.sigmoid(h @ params.head_w + params.head_b)
    return pred[:n_cell, 0]
