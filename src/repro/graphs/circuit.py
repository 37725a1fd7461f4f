"""Heterogeneous circuit graph container (CircuitNet schema).

Two node types (``cell``, ``net``), three edge types:

    near   : cell -> cell   (geometric)
    pin    : cell -> net    (topological)
    pinned : net  -> cell   (= pinᵀ)

Each edge type carries a forward (row-major over destinations) and transposed
(row-major over sources) degree-bucketed ELL packing — the CSR/CSC pair the
paper preprocesses in Alg. 1 stage 1 / Alg. 2 stage 1.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.graphs.ell import (BucketedELL, FusedELL, RelationPlan,
                              build_relation_plan, degree_stats,
                              fuse_bucketed, pack_ell_pair)
from repro.obs import span
from repro.sharding.plan_shard import (ShardedRelationPlan,
                                       shard_relation_plan)

EDGE_TYPES = ("near", "pin", "pinned")
# (source node type, destination node type) per edge type.
EDGE_SCHEMA = {"near": ("cell", "cell"), "pin": ("cell", "net"),
               "pinned": ("net", "cell")}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeSet:
    # BucketedELL as packed; FusedELL once fused for a traced step
    # (collated batches, ``with_fused_edges``)
    adj: BucketedELL | FusedELL      # A   (n_dst x n_src)
    adj_t: BucketedELL | FusedELL    # Aᵀ  (n_src x n_dst)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CircuitGraph:
    n_cell: int = dataclasses.field(metadata=dict(static=True))
    n_net: int = dataclasses.field(metadata=dict(static=True))
    edges: Dict[str, EdgeSet]
    x_cell: jax.Array            # (n_cell, f_cell) input features
    x_net: jax.Array             # (n_net, f_net)
    y_cell: jax.Array            # (n_cell,) congestion label
    # Optional relation-fused super-arena pair for the whole-layer
    # message-passing dispatch (graphs/ell.py::RelationPlan, DESIGN.md §9),
    # or its mesh-partitioned form (sharding/plan_shard.py::
    # ShardedRelationPlan, DESIGN.md §12) for graphs larger than one
    # device.  Attached by the collator / ``with_plan`` /
    # ``with_sharded_plan`` so plan-driven layers work even when the graph
    # is a TRACED jit argument (host packing is impossible there); ``None``
    # falls back to the serial per-direction path in core/hetero_mp.py.
    plan: Optional[RelationPlan | ShardedRelationPlan] = None

    def n_nodes(self, ntype: str) -> int:
        return self.n_cell if ntype == "cell" else self.n_net


# id-keyed memo with weakref guards (the _FUSE_CACHE pattern): plan packing
# is one-time host-side preprocessing per graph.
_PLAN_CACHE: Dict[int, tuple] = {}


def relation_plan_of(graph: CircuitGraph,
                     dense_threshold: Optional[int] = None) -> RelationPlan:
    """Memoized :class:`RelationPlan` covering every edge type of
    ``graph`` — the one-kernel-per-direction-group packing of its whole
    hetero layer.  Requires concrete (non-traced) bucketed adjacencies; the
    collator attaches pre-quantized plans to collated graphs instead.
    ``dense_threshold`` overrides the measured dense-tier nnz crossover
    (DESIGN.md §14); distinct thresholds memoize separately."""
    if isinstance(graph.plan, RelationPlan) and dense_threshold is None:
        return graph.plan
    key = (id(graph), dense_threshold)
    hit = _PLAN_CACHE.get(key)
    if hit is not None and hit[0]() is graph:
        return hit[1]
    with span("graph.relation_plan"):
        ets = [et for et in EDGE_TYPES if et in graph.edges]
        # The graph's own forward/transposed packings, fetched to the host
        # in one batched transfer: no COO round trip and no second pack.
        # The host copies die with this call, and fuse_bucketed's memo
        # entries for them with them, so only the plan outlives it.
        packed = jax.device_get(
            {et: (graph.edges[et].adj, graph.edges[et].adj_t) for et in ets})
        plan = build_relation_plan(
            [(et,) + EDGE_SCHEMA[et] for et in ets],
            {"cell": graph.n_cell, "net": graph.n_net}, packed=packed,
            dense_threshold=dense_threshold)
    _PLAN_CACHE[key] = (
        weakref.ref(graph, lambda _: _PLAN_CACHE.pop(key, None)), plan)
    return plan


def with_plan(graph: CircuitGraph) -> CircuitGraph:
    """``graph`` with its relation plan attached as a pytree child — the
    form to pass into jitted step functions that take the graph as a traced
    argument (the plan's arrays trace along; its segment table is static
    aux data, so equal-shaped graphs still share one compiled executable).
    """
    if graph.plan is not None:
        return graph
    return dataclasses.replace(graph, plan=relation_plan_of(graph))


def with_fused_edges(graph: CircuitGraph) -> CircuitGraph:
    """``graph`` with every edge set's packings fused host-side
    (:func:`fuse_bucketed`, memoized per packing) — the form the serial
    per-relation path takes inside a jitted step, where the ops accept no
    :class:`BucketedELL`."""
    edges = {et: EdgeSet(adj=fuse_bucketed(es.adj),
                         adj_t=fuse_bucketed(es.adj_t))
             for et, es in graph.edges.items()}
    return dataclasses.replace(graph, edges=edges)


# (id(graph), n_shards)-keyed memo, weakref-guarded like _PLAN_CACHE: the
# mesh partition is host-side numpy work done once per (graph, mesh size).
_SHARDED_PLAN_CACHE: Dict[tuple, tuple] = {}


def sharded_plan_of(graph: CircuitGraph, n_shards: int,
                    registry=None) -> ShardedRelationPlan:
    """Memoized mesh partition of ``graph``'s relation plan (DESIGN.md
    §12): every device of a ``("shard",)`` mesh owns one destination slab
    of the super-arena plus the halo index tables for its cross-shard
    source rows.  Consumed by ``ops.drspmm_multi_sharded``."""
    key = (id(graph), int(n_shards))
    hit = _SHARDED_PLAN_CACHE.get(key)
    if hit is not None and hit[0]() is graph:
        return hit[1]
    splan = shard_relation_plan(relation_plan_of(graph), n_shards,
                                registry=registry)
    _SHARDED_PLAN_CACHE[key] = (
        weakref.ref(graph, lambda _: _SHARDED_PLAN_CACHE.pop(key, None)),
        splan)
    return splan


def with_sharded_plan(graph: CircuitGraph, n_shards: int) -> CircuitGraph:
    """``graph`` with its mesh-partitioned plan attached as a pytree child
    — the giant-graph analogue of :func:`with_plan` for jitted steps that
    take the graph as a traced argument."""
    if isinstance(graph.plan, ShardedRelationPlan) \
            and graph.plan.n_shards == n_shards:
        return graph
    base = dataclasses.replace(graph, plan=None) \
        if graph.plan is not None else graph
    return dataclasses.replace(base, plan=sharded_plan_of(graph, n_shards))


def build_circuit_graph(coo: Dict[str, Tuple[np.ndarray, np.ndarray]],
                        n_cell: int, n_net: int,
                        x_cell, x_net, y_cell,
                        normalize: str = "mean") -> CircuitGraph:
    """Pack COO edge dicts {etype: (dst, src)} into a CircuitGraph.

    ``normalize="mean"`` row-normalizes edge weights (SAGE mean aggregator /
    GraphConv style); ``"none"`` keeps unit weights.
    """
    sizes = {"cell": n_cell, "net": n_net}
    edges = {}
    for et, (dst, src) in coo.items():
        s_t, d_t = EDGE_SCHEMA[et]
        n_dst, n_src = sizes[d_t], sizes[s_t]
        if normalize == "mean":
            deg = np.bincount(dst, minlength=n_dst).astype(np.float32)
            w = 1.0 / np.maximum(deg[dst], 1.0)
        else:
            w = np.ones(len(dst), np.float32)
        adj, adj_t = pack_ell_pair(dst, src, w, n_dst, n_src)
        edges[et] = EdgeSet(adj=adj, adj_t=adj_t)
    return CircuitGraph(n_cell=n_cell, n_net=n_net, edges=edges,
                        x_cell=jnp.asarray(x_cell), x_net=jnp.asarray(x_net),
                        y_cell=jnp.asarray(y_cell))


def graph_degree_stats(coo: Dict[str, Tuple[np.ndarray, np.ndarray]],
                       n_cell: int, n_net: int) -> Dict[str, dict]:
    sizes = {"cell": n_cell, "net": n_net}
    out = {}
    for et, (dst, src) in coo.items():
        s_t, d_t = EDGE_SCHEMA[et]
        st = degree_stats(np.asarray(dst), sizes[d_t])
        st["src_type"] = s_t
        out[et] = st
    return out
