"""Heterogeneous message passing with D-ReLU + DR-SpMM (the paper's core).

One HeteroConv layer (paper Fig. 1 / Fig. 5) = three edge-type modules:

    near   : SageConv   cell -> cell
    pinned : SageConv   net  -> cell
    pin    : GraphConv  cell -> net

with the cell-side merge Y_cell = max(near_out, pinned_out) (Eq. 8) and
Y_net = pin_out (Eq. 9).  Eqs. 12–14 (the mask-routed backward through the
max merge) fall out of autodiff over ``jnp.maximum``; the SSpMM backward of
each DR-SpMM is the custom VJP in kernels/ops.py.

Two execution strategies share the math:

* **plan path** (the default): ALL edge-type directions
  of the layer run as ONE dispatch per direction-group over a
  :class:`~repro.graphs.ell.RelationPlan` super-arena
  (``ops.drspmm_multi`` — one forward ``pallas_call``, one transposed
  backward, DESIGN.md §9).  The plan comes from the graph itself
  (``graph.plan``, attached by the collator / ``with_plan``) or is built
  lazily and memoized when the graph is concrete.  Per-type D-ReLU/CBSR is
  computed once and shared by every relation consuming that type.
* **serial path** (the reference, and the fallback for dense aggregation
  or graphs without a plan): the per-relation loop, one
  ``drspmm``/``spmm`` per edge type —
  but the per-type D-ReLU/CBSR is shared across relations here too
  (``near`` and ``pin`` both consume the cell slab; 2 sparsifications per
  layer, not 3 — tests/test_backbone.py pins the dispatch count).
  ``HeteroMPConfig(use_plan=False)`` pins it for parity tests.

Stack callers (models/backbone.py) additionally hoist the layer-invariant
plan resolution once per stack application and pass it via
``hetero_conv(..., plan=...)``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.cbsr import cbsr_from_dense
from repro.core.drelu import drelu
from repro.graphs.circuit import (CircuitGraph, relation_plan_of,
                                  sharded_plan_of)
from repro.graphs.ell import FusedELL, RelationPlan
from repro.kernels import ops
from repro.sharding.plan_shard import ShardedRelationPlan


@dataclasses.dataclass(frozen=True)
class HeteroMPConfig:
    hidden: int = 64
    k_cell: int = 16          # D-ReLU K for cell-sourced embeddings
    k_net: int = 16           # D-ReLU K for net-sourced embeddings
    # "pallas_fused" on TPU (one kernel dispatch per edge-type direction,
    # DESIGN.md §1), "xla_fused" on CPU — the same arena in plain XLA.
    backend: ops.Backend = ops.DEFAULT_BACKEND
    use_drelu: bool = True    # False => dense baseline path (plain SpMM)
    drelu_backend: str = "topk"   # topk (lax.top_k) | pallas (binary search)
    # Relation-fused layer dispatch (DESIGN.md §9): a layer's whole
    # message passing runs as ONE dispatch per direction-group
    # via the graph's RelationPlan.  False pins the serial per-direction
    # reference loop (exact parity: tests/test_relation_plan.py).
    use_plan: bool = True
    # Giant-graph mesh sharding (DESIGN.md §12): > 1 partitions the plan
    # over that many mesh devices and routes the layer through
    # ``ops.drspmm_multi_sharded`` (needs that many visible devices).  A
    # graph arriving with a ShardedRelationPlan already attached uses it
    # regardless of this knob.
    n_shards: int = 0
    # Dense-tier nnz crossover override (DESIGN.md §14): None takes the
    # measured ``DENSE_TIER_NNZ`` constant; <= -1 pins every relation to
    # the arena tier.  Applies only to plans this module builds itself —
    # attached (collated/sharded) plans were tiered at pack time.
    dense_threshold: Optional[int] = None


class HeteroLayerParams(NamedTuple):
    """Per-edge-type weights (Eq. 4's W^ψ) + SAGE self paths."""
    w_near: jax.Array          # (H, H) neighbor transform, near
    w_near_self: jax.Array     # (H, H)
    w_pinned: jax.Array        # (H, H)
    w_pinned_self: jax.Array   # unused by merge (self path shared) — kept for SAGE form
    w_pin: jax.Array           # (H, H) GraphConv weight
    b_cell: jax.Array          # (H,)
    b_net: jax.Array           # (H,)


def init_hetero_layer(key, hidden: int) -> HeteroLayerParams:
    ks = jax.random.split(key, 5)
    s = 1.0 / jnp.sqrt(hidden)
    mk = lambda k: jax.random.uniform(k, (hidden, hidden), jnp.float32, -s, s)
    return HeteroLayerParams(
        w_near=mk(ks[0]), w_near_self=mk(ks[1]), w_pinned=mk(ks[2]),
        w_pinned_self=mk(ks[3]), w_pin=mk(ks[4]),
        b_cell=jnp.zeros((hidden,)), b_net=jnp.zeros((hidden,)))


def _sparsify(x_src: jax.Array, k: int, cfg: HeteroMPConfig):
    """D-ReLU -> CBSR.  Gradient routing: the CBSR values carry the
    autodiff path (top-k gather is differentiable wrt x), and the SSpMM
    backward samples at the preserved indices (Alg. 2)."""
    if cfg.drelu_backend == "pallas":
        # the paper's row-wise binary search as a Pallas kernel
        from repro.kernels.drelu_topk import drelu_pallas
        xs = drelu_pallas(jax.lax.stop_gradient(x_src), k)
        xs = xs + (x_src - jax.lax.stop_gradient(x_src)) * (xs != 0)
    else:
        xs = drelu(x_src, k)                   # dense w/ straight-through
    return cbsr_from_dense(xs, k)


def _aggregate(graph: CircuitGraph, etype: str, x_src: jax.Array,
               c, cfg: HeteroMPConfig) -> jax.Array:
    """A^ψ · D-ReLU(x_src) for one edge type, via DR-SpMM (or dense SpMM) —
    the serial per-direction reference.  ``c`` is the source type's
    pre-computed CBSR (None pins the dense SpMM path): the caller
    sparsifies each node type ONCE per layer and shares it across every
    relation consuming that type, exactly like the plan path — ``near``
    and ``pin`` both read the cell slab, so re-deriving its D-ReLU/CBSR
    per relation was pure recompute (and an extra top_k dispatch)."""
    es = graph.edges[etype]
    if c is not None:
        return ops.drspmm(es.adj, es.adj_t, c.values, c.idx,
                          x_src.shape[-1], backend=cfg.backend)
    return ops.spmm(es.adj, es.adj_t, x_src, backend=cfg.backend)


def _sparsify_types(x_cell: jax.Array, x_net: jax.Array,
                    cfg: HeteroMPConfig):
    """Per-type CBSR, computed once per layer and shared by every relation
    consuming the type (None where the type stays dense — k >= width or
    D-ReLU off).  The single sparsification site for BOTH execution
    strategies, so they cannot drift."""
    c_cell = _sparsify(x_cell, cfg.k_cell, cfg) \
        if cfg.use_drelu and cfg.k_cell < x_cell.shape[-1] else None
    c_net = _sparsify(x_net, cfg.k_net, cfg) \
        if cfg.use_drelu and cfg.k_net < x_net.shape[-1] else None
    return c_cell, c_net


def plan_applicable(cfg: HeteroMPConfig, hidden: int) -> bool:
    """True iff the plan path can serve this config: CBSR aggregation on
    both node types (dense SpMM stays serial).  The single gate shared by
    :func:`_plan_for` and the trainer's plan attachment, so the two cannot
    drift."""
    return (cfg.use_plan and cfg.use_drelu
            and cfg.k_cell < hidden and cfg.k_net < hidden)


def _plan_for(graph: CircuitGraph, cfg: HeteroMPConfig,
              hidden: int) -> RelationPlan | ShardedRelationPlan | None:
    """The layer's RelationPlan (possibly mesh-partitioned), or None when
    the serial path must run.

    Beyond :func:`plan_applicable`, a plan must actually be available:
    attached to the graph (collated batches / ``with_sharded_plan`` — works
    traced), or buildable host-side (concrete bucketed adjacencies,
    memoized per graph; partitioned when ``cfg.n_shards > 1``)."""
    if not plan_applicable(cfg, hidden):
        return None
    if graph.plan is not None:
        return graph.plan
    adj = graph.edges["near"].adj
    if isinstance(adj, FusedELL):
        return None    # pre-fused (collated) graph without an attached plan
    if isinstance(adj.buckets[0].nbr, jax.core.Tracer):
        return None    # traced graph argument: host packing impossible
    if cfg.n_shards > 1:
        return sharded_plan_of(graph, cfg.n_shards)
    return relation_plan_of(graph, dense_threshold=cfg.dense_threshold)


def _merge(params: HeteroLayerParams, x_cell: jax.Array,
           agg_near: jax.Array, agg_pinned: jax.Array,
           agg_pin: jax.Array) -> Tuple[jax.Array, jax.Array]:
    with jax.named_scope("merge"):
        # --- per-edge W^ψ (Eq. 4) ---
        near_out = agg_near @ params.w_near + x_cell @ params.w_near_self
        pinned_out = (agg_pinned @ params.w_pinned
                      + x_cell @ params.w_pinned_self)
        pin_out = agg_pin @ params.w_pin
        # --- merge (Eqs. 8-9); Eqs. 12-14 are the autodiff of the max ---
        y_cell = jnp.maximum(near_out, pinned_out) + params.b_cell
        y_net = pin_out + params.b_net
    return y_cell, y_net


# sentinel: "resolve the plan yourself" (the back-compat default) vs an
# explicit plan=None, which pins the serial path
_RESOLVE_PLAN = object()


def hetero_conv(params: HeteroLayerParams, graph: CircuitGraph,
                x_cell: jax.Array, x_net: jax.Array,
                cfg: HeteroMPConfig, *,
                plan=_RESOLVE_PLAN) -> Tuple[jax.Array, jax.Array]:
    """One HeteroConv layer.  Returns (y_cell, y_net).

    With a :class:`RelationPlan` available (see :func:`_plan_for`) the
    layer's entire message passing is ONE ``drspmm_multi`` dispatch per
    direction-group.  Both strategies sparsify each node type once per
    layer and share the CBSR across the relations consuming it
    (:func:`_sparsify_types` — identical values, so the paths agree
    exactly).

    ``plan`` lets a stack caller (models/backbone.py) hoist the
    layer-invariant plan resolution once per stack application and thread
    it remat-safely through every layer: pass the resolved plan (or
    ``None`` to pin the serial reference); the default sentinel keeps the
    per-call resolution for standalone use."""
    if plan is _RESOLVE_PLAN:
        plan = _plan_for(graph, cfg, x_cell.shape[-1])
    if plan is not None:
        c_cell, c_net = _sparsify_types(x_cell, x_net, cfg)
        op = ops.drspmm_multi_sharded \
            if isinstance(plan, ShardedRelationPlan) else ops.drspmm_multi
        aggs = op(
            plan, {"cell": (c_cell.values, c_cell.idx),
                   "net": (c_net.values, c_net.idx)},
            x_cell.shape[-1], backend=cfg.backend)
        return _merge(params, x_cell, aggs["near"], aggs["pinned"],
                      aggs["pin"])

    # --- serial reference: three edge-type message passings over the two
    # --- shared per-type CBSRs (cell feeds both near and pin) -------------
    c_cell, c_net = _sparsify_types(x_cell, x_net, cfg)
    agg_near = _aggregate(graph, "near", x_cell, c_cell, cfg)    # cell->cell
    agg_pinned = _aggregate(graph, "pinned", x_net, c_net, cfg)  # net->cell
    agg_pin = _aggregate(graph, "pin", x_cell, c_cell, cfg)      # cell->net
    return _merge(params, x_cell, agg_near, agg_pinned, agg_pin)
