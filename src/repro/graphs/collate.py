"""Block-diagonal collation: N CircuitGraphs → ONE CircuitGraph per batch.

The serve/train hot path dispatches the HGNN once per *batch* instead of
once per graph.  Member graphs are laid out block-diagonally in a shared
node space per node type:

    cell ids of member i live in [cell_off_i, cell_off_i + n_cell_i)
    net  ids of member i live in [net_off_i,  net_off_i  + n_net_i)

Edges never cross members, so every aggregation over the collated graph is
exactly the direct sum of the members' aggregations — batched forward and
gradients match the per-graph loop bit-for-bit up to f32 summation order
(tests/test_collate.py).

Compile-once comes from **shape quantization** (the HOGA/GSR-GNN-motivated
move): member node counts are padded up to a small geometric bucket grid,
and the fused arenas' chunk/row counts are padded the same way, so the
jitted forward — which takes the collated graph as a *traced argument* —
compiles once per shape bucket instead of once per graph.  Padding is inert
by construction: padded node rows carry zero features and no edges, and
padded arena chunks carry zero weights routed into rows the output gather
never reads.

Member edges are recovered host-side from their ELL packings
(``ell_to_coo``), offset, and re-packed in one fused-arena repack per edge
type (``pack_fused_pair``'s two directions).  Member weights (already
row-normalized per member) are carried through unchanged — block-diagonal
row norms are member-local, so no renormalization is needed.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.graphs.circuit import (CircuitGraph, EDGE_SCHEMA, EDGE_TYPES,
                                  EdgeSet)
from repro.graphs.ell import (DEFAULT_BOUNDS, DENSE_TIER_AREA,
                              DENSE_TIER_NNZ, FusedELL, RelationPlan,
                              arena_stats, build_relation_plan, ell_to_coo,
                              fuse_bucketed, pack_ell,
                              pack_fused_eid_pair, pad_fused_arena, _round_up)
from repro.obs.metrics import DEFAULT_REGISTRY as _METRICS

# Default bucket-grid resolutions (mantissa bits of the geometric grid):
# node slabs pay padding linearly (features, gather), so they get a finer
# grid; arena chunk counts only pay inert zero-weight chunks, so a coarser
# grid buys fewer shape buckets (= fewer compiles) cheaply.
NODE_GRID_BITS = 2     # grid {m·2^e : m ∈ [4, 8)} — ≤ ~25% padding
ARENA_GRID_BITS = 1    # grid {m·2^e : m ∈ [2, 4)} — ≤ ~50% padding
# Chunk-count headroom applied when a bucket's layout is FIRST recorded:
# later batches whose chunk count stays within this factor of the first
# batch's reuse its signature (batch-to-batch jitter shrinks ~1/√B, so 15%
# covers typical mixed streams); growth beyond it costs one extra compile
# and raises the bucket's floor.
ARENA_HEADROOM = 1.15


def quantize_up(n: int, mantissa_bits: int = NODE_GRID_BITS,
                minimum: int = 8) -> int:
    """Round ``n`` up to the next point of a geometric grid with
    ``2**mantissa_bits`` points per octave.  Max relative padding is
    ``2**-mantissa_bits``; the grid is what bounds the number of distinct
    compiled shapes to O(log total-size-range)."""
    n = max(int(n), minimum)
    if n <= minimum:
        return minimum
    e = n.bit_length() - 1 - mantissa_bits
    if e <= 0:
        return n
    step = 1 << e
    return _round_up(n, step)


@dataclasses.dataclass
class BucketLayout:
    """Per-shape-bucket fused-arena layout record (owned by the serve
    engine, one per request bucket).  The first batch of a bucket pins the
    chunk width per edge-type direction; chunk counts only grow (and only
    to quantized values), so batch signatures within a bucket converge —
    typically on the very first batch, worst-case after a few early growth
    steps, each of which is one extra compile."""

    chunk: Dict[Tuple[str, str], int] = dataclasses.field(
        default_factory=dict)        # (etype, "fwd"|"bwd") -> Ec
    min_chunks: Dict[Tuple[str, str], int] = dataclasses.field(
        default_factory=dict)        # (etype, "fwd"|"bwd") -> padded C
    # Relation-plan layout (DESIGN.md §9): the super-arena's SHARED chunk
    # width per direction, per-relation chunk-count floors, and the
    # quantized learnable-edge nnz floor — pinned/floored exactly like the
    # per-edge-type arenas so plan signatures converge per shape bucket.
    plan_chunk: Dict[str, int] = dataclasses.field(
        default_factory=dict)        # "fwd"|"bwd" -> Ec
    plan_min_chunks: Dict[Tuple[str, str], int] = dataclasses.field(
        default_factory=dict)        # (etype, "fwd"|"bwd") -> padded C
    min_nnz: Dict[str, int] = dataclasses.field(
        default_factory=dict)        # etype -> quantized eid-arena nnz
    # Relation tier (DESIGN.md §14): dense-vs-arena routing changes the
    # plan's dense-table SHAPES, so a tier flip mid-bucket would change the
    # graph signature.  The first batch of a bucket pins each edge type's
    # tier; later batches reuse it even if their nnz drifts across the
    # crossover — correctness is tier-independent, only speed is at stake.
    plan_tier: Dict[str, str] = dataclasses.field(
        default_factory=dict)        # etype -> "dense"|"arena"


class LayoutTable:
    """LRU table of per-shape-bucket :class:`BucketLayout` records.

    A long-lived serving loop accumulates one layout per request bucket —
    and, in the engine, one compile-cache's worth of executables per bucket.
    Under a long tail of one-off shapes that state grows without bound, so
    the table bounds it: ``get(key)`` creates-or-touches a bucket (LRU
    refresh) and, when the table exceeds ``max_live`` buckets, evicts the
    least-recently-used one, firing ``on_evict(key, layout)`` so the owner
    can release derived state (compiled executables, locks, signature
    counters).  An evicted bucket that returns starts from a fresh layout:
    its first batch re-pins chunk widths and re-floors chunk counts, i.e. it
    costs at most the bucket's original compile again (GSR-GNN's bounded
    layout-reuse property).

    ``max_live=None`` disables eviction (training-style fixed bucket sets).
    Callers serialize access themselves (the engine holds its queue lock).
    """

    def __init__(self, max_live: Optional[int] = None,
                 on_evict: Optional[Callable[[tuple, "BucketLayout"],
                                             None]] = None,
                 metrics=None, recorder=None):
        assert max_live is None or max_live >= 1, max_live
        self.max_live = max_live
        self.on_evict = on_evict
        self.evictions = 0
        # obs hooks (DESIGN.md §11): ``metrics`` (a MetricsRegistry) counts
        # layout.creates / layout.evictions; ``recorder`` annotates each
        # create/evict as an instant on the "layout" trace track.  Both
        # default to off — no observability state is touched when unset.
        self.metrics = metrics
        self.recorder = recorder
        self._table: "OrderedDict[tuple, BucketLayout]" = OrderedDict()

    def get(self, key: tuple) -> BucketLayout:
        """Layout for ``key`` (created on first use), refreshed to
        most-recently-used; may evict the LRU bucket (never ``key``)."""
        layout = self._table.get(key)
        if layout is None:
            layout = self._table[key] = BucketLayout()
            if self.metrics is not None:
                self.metrics.inc("layout.creates")
            if self.recorder is not None and self.recorder.enabled:
                self.recorder.instant("layout", "bucket_create",
                                      bucket=str(key))
        self._table.move_to_end(key)
        while self.max_live is not None and len(self._table) > self.max_live:
            k, v = self._table.popitem(last=False)
            self.evictions += 1
            if self.metrics is not None:
                self.metrics.inc("layout.evictions")
            if self.recorder is not None and self.recorder.enabled:
                self.recorder.instant("layout", "bucket_evict",
                                      bucket=str(k))
            if self.on_evict is not None:
                self.on_evict(k, v)
        return layout

    def __len__(self) -> int:
        return len(self._table)

    def __contains__(self, key: tuple) -> bool:
        return key in self._table

    def keys(self):
        return self._table.keys()


def _arena_row_cap(n_dst: int, bounds: Sequence[int], row_block: int) -> int:
    """Deterministic upper bound on a fused arena's row count: every
    non-empty destination row occupies exactly one arena row, each of the
    ≤ len(bounds)+1 degree buckets rounds its row count up to the row
    block, and the sentinel adds one more block.  It depends only on the
    *padded node count*, so every batch of a shape bucket pads its arenas
    to the same row count — node-quantization alone fixes this dimension.
    """
    return _round_up(max(n_dst, 1), row_block) + (len(bounds) + 2) * row_block


@dataclasses.dataclass(frozen=True)
class MemberSlice:
    """Where one member graph lives inside the collated node spaces."""
    cell_off: int
    n_cell: int
    net_off: int
    n_net: int


@dataclasses.dataclass
class CollatedBatch:
    """One collated dispatch unit.

    ``graph`` is a regular :class:`CircuitGraph` (padded sizes) whose edge
    sets hold pre-packed :class:`FusedELL` arenas, so the ops run even when
    the graph is a traced jit argument.
    ``cell_weight`` holds 1/(n_real·n_cell_i) on member i's rows and 0 on
    padding — ``Σ w·(pred−y)²`` over the batch equals the mean of per-graph
    mean-MSE losses, so batched gradients match the per-graph loop.
    """

    graph: CircuitGraph
    members: Tuple[MemberSlice, ...]
    cell_weight: jax.Array          # (n_cell_pad,)
    n_real: int                     # members that carry real requests
    # with_eids collation: per-edge-type QUANTIZED edge count (the size the
    # traced weight vector is padded to — grid-bucketed so mixed streams
    # stop adding one jit entry per distinct nnz), the exact count, and
    # per-member offsets into the batch-canonical edge order.
    edge_nnz: Dict[str, int] = dataclasses.field(default_factory=dict)
    edge_nnz_exact: Dict[str, int] = dataclasses.field(default_factory=dict)
    edge_eid_offsets: Dict[str, Tuple[int, ...]] = dataclasses.field(
        default_factory=dict)

    @property
    def plan(self) -> Optional[RelationPlan]:
        """The batch graph's relation plan (``with_plan`` collation)."""
        return self.graph.plan

    def concat_edge_weights(self, etype: str, member_ws) -> jax.Array:
        """Member canonical weight vectors → the batch canonical vector.

        Member i's edges occupy ``[edge_eid_offsets[etype][i], +nnz_i)`` of
        the batch order (member node-id blocks are disjoint and increasing,
        so the batch dst-stable sort concatenates the members' canonical
        orders).  Provide one (nnz_i,) vector per member — fillers included,
        typically a reuse of the replicated member's vector.  The result is
        zero-padded up to the quantized ``edge_nnz`` (padded ids are never
        gathered, so the pad slots are inert and receive zero gradient).
        """
        assert len(member_ws) == len(self.members), \
            (len(member_ws), len(self.members))
        w = jnp.concatenate([jnp.asarray(wi) for wi in member_ws])
        exact = self.edge_nnz_exact.get(etype, self.edge_nnz[etype])
        assert w.shape[0] == exact, (w.shape[0], exact)
        pad = self.edge_nnz[etype] - exact
        if pad:
            w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)])
        return w

    def split_cell(self, y_cell) -> List[jax.Array]:
        """Per-real-member views of a per-cell output of the batched model."""
        return [y_cell[m.cell_off:m.cell_off + m.n_cell]
                for m in self.members[: self.n_real]]

    def split_net(self, y_net) -> List[jax.Array]:
        return [y_net[m.net_off:m.net_off + m.n_net]
                for m in self.members[: self.n_real]]

    @property
    def signature(self) -> tuple:
        return graph_signature(self.graph)


def graph_signature(graph: CircuitGraph) -> tuple:
    """Hashable padded-shape signature: the pytree structure (which carries
    the static fields) plus every leaf's shape/dtype.  Two graphs with equal
    signatures hit the same jit-compiled executable when passed as traced
    arguments — this is exactly jit's cache key restricted to shapes.

    Signatures are a property of the DATA alone: model depth, wiring, and
    remat (the BackboneSpec, DESIGN.md §13) never enter — a 2-layer and a
    15-layer backbone bucket identically, and flipping remat on a trainer
    or serve engine cannot invalidate collated layouts or batches
    (tests/test_backbone.py pins the independence)."""
    leaves, treedef = jax.tree_util.tree_flatten(graph)
    return (treedef,
            tuple((tuple(l.shape), np.dtype(l.dtype).name) for l in leaves))


# Shape-bucket-stable arena padding now lives with the packers
# (graphs/ell.py::pad_fused_arena) so the relation-plan builder shares it;
# kept under the historical private name for this module's call sites.
_pad_fused_arena = pad_fused_arena


def _chunk_for(chunk, etype: str) -> Optional[int]:
    if isinstance(chunk, dict):
        return chunk.get(etype)
    return chunk


def collate_graphs(graphs: Sequence[CircuitGraph], *,
                   quantize: bool = True,
                   node_bits: int = NODE_GRID_BITS,
                   arena_bits: int = ARENA_GRID_BITS,
                   chunk: Union[None, int, Dict[str, int]] = None,
                   layout: Optional[BucketLayout] = None,
                   n_real: Optional[int] = None,
                   with_eids: bool = False,
                   with_plan: bool = True,
                   bounds: Sequence[int] = DEFAULT_BOUNDS) -> CollatedBatch:
    """Merge member graphs into one block-diagonal :class:`CircuitGraph`.

    Parameters
    ----------
    quantize : pad member node slabs and arena dims up the bucket grid;
        ``False`` gives the exact-size collation.
    chunk : pin the fused arenas' chunk width (int, or per-edge-type dict);
        ``None`` lets ``fuse_bucketed`` pick per packing from the degree
        histogram.
    layout : mutable per-shape-bucket record (:class:`BucketLayout`): pins
        chunk widths to the bucket's first batch and floors chunk counts at
        the bucket's running max, so same-bucket batches share a signature.
    n_real : members that carry real requests; trailing members are filler
        (their outputs are dropped and their loss weight is zero).
    with_eids : additionally attach a batch-canonical edge-id arena to every
        fused edge direction (member eids offset by the preceding members'
        edge counts), so the collated batch can carry learnable per-edge
        weights through ``ops.drspmm_learnable`` — the batch weight vector
        is the concatenation of the members' canonical vectors
        (:meth:`CollatedBatch.concat_edge_weights`).  With ``quantize``,
        the per-edge-type nnz is rounded up the arena grid (and floored at
        the bucket's running max when a ``layout`` tracks it): the traced
        weight vector is zero-padded to that size, so mixed
        learnable-weight streams add one jit entry per GRID POINT instead
        of one per distinct nnz.
    with_plan : attach a :class:`RelationPlan` super-arena pair to the
        collated graph (``batch.graph.plan``) so hetero layers run ONE
        dispatch per direction-group even with the graph traced
        (DESIGN.md §9).  The plan's per-relation segments are
        quantized/floored under the same ``layout`` as the per-edge-type
        arenas, so plan signatures are bucket-stable.
    """
    assert graphs, "collate_graphs needs at least one member"
    n_real = len(graphs) if n_real is None else n_real
    f_cell = graphs[0].x_cell.shape[1]
    f_net = graphs[0].x_net.shape[1]
    assert all(g.x_cell.shape[1] == f_cell and g.x_net.shape[1] == f_net
               for g in graphs), "members must share feature widths"

    # --- member slabs (per-member padding keeps offsets deterministic
    # within a shape bucket: the batch signature depends only on the
    # members' quantized sizes, not their exact ones) ---
    members, cell_off, net_off = [], 0, 0
    for g in graphs:
        members.append(MemberSlice(cell_off=cell_off, n_cell=g.n_cell,
                                   net_off=net_off, n_net=g.n_net))
        cell_off += quantize_up(g.n_cell, node_bits) if quantize else g.n_cell
        net_off += quantize_up(g.n_net, node_bits) if quantize else g.n_net
    n_cell_pad, n_net_pad = cell_off, net_off
    sizes_pad = {"cell": n_cell_pad, "net": n_net_pad}

    # --- features / labels / loss weights ---
    x_cell = np.zeros((n_cell_pad, f_cell), np.float32)
    x_net = np.zeros((n_net_pad, f_net), np.float32)
    y_cell = np.zeros(n_cell_pad, np.float32)
    w_cell = np.zeros(n_cell_pad, np.float32)
    for i, (g, m) in enumerate(zip(graphs, members)):
        x_cell[m.cell_off:m.cell_off + m.n_cell] = np.asarray(g.x_cell)
        x_net[m.net_off:m.net_off + m.n_net] = np.asarray(g.x_net)
        y_cell[m.cell_off:m.cell_off + m.n_cell] = np.asarray(g.y_cell)
        if i < n_real:
            w_cell[m.cell_off:m.cell_off + m.n_cell] = \
                1.0 / (n_real * m.n_cell)

    # --- merged COO per edge type, member weights carried through ---
    off_of = {"cell": [m.cell_off for m in members],
              "net": [m.net_off for m in members]}
    edges = {}
    coo_of: Dict[str, tuple] = {}
    bucketed_of: Dict[str, tuple] = {}
    edge_nnz: Dict[str, int] = {}
    edge_nnz_exact: Dict[str, int] = {}
    edge_eid_offsets: Dict[str, Tuple[int, ...]] = {}
    for et in EDGE_TYPES:
        s_t, d_t = EDGE_SCHEMA[et]
        ds, ss, ws, m_nnz = [], [], [], []
        for i, g in enumerate(graphs):
            dst, src, w = ell_to_coo(g.edges[et].adj)
            ds.append(dst + off_of[d_t][i])
            ss.append(src + off_of[s_t][i])
            ws.append(w)
            m_nnz.append(int(dst.shape[0]))
        dst = np.concatenate(ds)
        src = np.concatenate(ss)
        w = np.concatenate(ws)
        n_dst, n_src = sizes_pad[d_t], sizes_pad[s_t]
        coo_of[et] = (dst, src, w)
        # one degree-bucketed pack per direction, SHARED by the
        # per-edge-type arena and the relation plan (fusing at each
        # consumer's chunk width is memoized per (packing, width))
        bucketed = {"fwd": pack_ell(dst, src, w, n_dst, n_src, bounds),
                    "bwd": pack_ell(src, dst, w, n_src, n_dst, bounds)}
        bucketed_of[et] = (bucketed["fwd"], bucketed["bwd"])
        packed = {}
        for dname in ("fwd", "bwd"):
            ck = layout.chunk.get((et, dname)) if layout else None
            if ck is None:
                ck = _chunk_for(chunk, et)
            a = fuse_bucketed(bucketed[dname], chunk=ck)
            if layout is not None:
                layout.chunk.setdefault((et, dname), a.chunk)
            # Pack-time arena efficiency gauges (DESIGN.md §11): cheap
            # — static fields and bucket shapes only, no array scans —
            # and labeled by (etype, dir), a bounded cardinality.
            st = arena_stats(a, bucketed[dname])
            for gname in ("fill_ratio", "padded_slots", "slots",
                          "chunk", "slot_saving"):
                _METRICS.set(f"arena.{gname}", st[gname],
                             etype=et, dir=dname)
            if quantize:
                a = _quantize_arena(a, arena_bits, bounds, layout,
                                    (et, dname))
            packed[dname] = a
        if with_eids:
            # Batch-canonical edge ids: member node-id blocks are
            # disjoint and increasing, so the batch dst-stable sort is
            # the concatenation of the members' canonical orders —
            # member i's ids are its own canonical ids + Σ_{j<i} nnz_j.
            # Member weights are all non-zero (ell_to_coo masks), so the
            # eid packing sorts/chunks identically to the weight packing
            # and the eid table drops straight onto the weight arena.
            efwd, ebwd, _order, et_nnz = pack_fused_eid_pair(
                dst, src, n_dst, n_src, bounds,
                chunk=(packed["fwd"].chunk, packed["bwd"].chunk))
            for dname, ea in (("fwd", efwd), ("bwd", ebwd)):
                a = packed[dname]
                if quantize:
                    ea = _pad_fused_arena(ea, a.n_chunks,
                                          a.n_arena_rows)
                assert ea.nbr.shape == a.nbr.shape, (et, dname)
                packed[dname] = dataclasses.replace(
                    a, eid=np.asarray(ea.eid))
            # Shape-bucketed nnz (ROADMAP): the learnable weight vector
            # is a TRACED operand sized by nnz, so a distinct nnz per
            # batch means one jit entry per batch.  Round it up the
            # arena grid (floored at the bucket's running max) and let
            # concat_edge_weights zero-pad — padded ids are never
            # gathered, so the pad slots are inert.
            nnz_pad = et_nnz
            if quantize:
                nnz_pad = quantize_up(et_nnz, arena_bits, minimum=8)
                if layout is not None:
                    floor = layout.min_nnz.get(et)
                    if floor is None:   # first batch: headroom, like
                        floor = quantize_up(   # the chunk-count floors
                            int(np.ceil(et_nnz * ARENA_HEADROOM)),
                            arena_bits, minimum=8)
                    nnz_pad = max(nnz_pad, floor)
                    layout.min_nnz[et] = nnz_pad
            edge_nnz[et] = nnz_pad
            edge_nnz_exact[et] = et_nnz
            edge_eid_offsets[et] = tuple(
                int(o) for o in np.cumsum([0] + m_nnz[:-1]))
        edges[et] = EdgeSet(adj=packed["fwd"], adj_t=packed["bwd"])

    plan = None
    if with_plan:
        plan = _build_batch_plan(coo_of, bucketed_of, sizes_pad, quantize,
                                 arena_bits, layout, bounds)
    graph = CircuitGraph(n_cell=n_cell_pad, n_net=n_net_pad, edges=edges,
                         x_cell=jnp.asarray(x_cell), x_net=jnp.asarray(x_net),
                         y_cell=jnp.asarray(y_cell), plan=plan)
    return CollatedBatch(graph=graph, members=tuple(members),
                         cell_weight=jnp.asarray(w_cell), n_real=n_real,
                         edge_nnz=edge_nnz, edge_nnz_exact=edge_nnz_exact,
                         edge_eid_offsets=edge_eid_offsets)


def _build_batch_plan(coo_of: Dict[str, tuple],
                      bucketed_of: Dict[str, tuple],
                      sizes_pad: Dict[str, int],
                      quantize: bool, arena_bits: int,
                      layout: Optional[BucketLayout],
                      bounds: Sequence[int]) -> RelationPlan:
    """RelationPlan over the batch's merged edge sets, quantized for
    signature stability: the super-arena's shared chunk width per direction
    is pinned to the bucket's first batch (``BucketLayout.plan_chunk``) and
    every relation segment's chunk count is padded up the arena grid and
    floored at the bucket's running max (``plan_min_chunks``) — the same
    discipline ``_quantize_arena`` applies to the per-edge-type arenas.
    Row counts take the deterministic cap, so they never vary in-bucket."""
    relations = [(et,) + EDGE_SCHEMA[et] + coo_of[et]
                 for et in EDGE_TYPES if et in coo_of]
    chunk = None
    if layout is not None and layout.plan_chunk:
        chunk = (layout.plan_chunk.get("fwd"), layout.plan_chunk.get("bwd"))

    pad = None
    if quantize:
        def pad(et, dname, arena):
            r_cap = _arena_row_cap(arena.n_dst, bounds, arena.row_block)
            c_pad = quantize_up(arena.n_chunks, arena_bits, minimum=1)
            if layout is not None:
                floor = layout.plan_min_chunks.get((et, dname))
                if floor is None:   # first batch of the bucket: headroom
                    floor = quantize_up(
                        int(np.ceil(arena.n_chunks * ARENA_HEADROOM)),
                        arena_bits, minimum=1)
                c_pad = max(c_pad, floor)
                layout.plan_min_chunks[(et, dname)] = c_pad
            return c_pad, r_cap

    # Tier pinning (DESIGN.md §14): classify each edge type from the
    # batch's EXACT merged-COO nnz (padded plan arenas reset ``nnz``, so
    # build_relation_plan's own count would see the padded slab) against
    # the padded type sizes, then pin the FIRST batch's verdict per bucket
    # — a tier flip changes dense-table shapes, hence the signature.
    tiers = None
    if layout is not None:
        for et, st, dt, dst, _src, _w in relations:
            area = int(sizes_pad[dt]) * int(sizes_pad[st])
            t = ("dense" if (int(dst.shape[0]) <= DENSE_TIER_NNZ
                             and area <= DENSE_TIER_AREA) else "arena")
            layout.plan_tier.setdefault(et, t)
        tiers = dict(layout.plan_tier)

    plan = build_relation_plan(relations, sizes_pad, bounds=bounds,
                               chunk=chunk, pad=pad,
                               packed=bucketed_of, tiers=tiers)
    if layout is not None:
        layout.plan_chunk.setdefault("fwd", plan.fwd.chunk)
        layout.plan_chunk.setdefault("bwd", plan.bwd.chunk)
    # Super-arena efficiency gauges: real slots are the summed ARENA-tier
    # relation edge counts (known from the merged COO — padded plan arenas
    # reset ``nnz``, and scanning the arena per batch would not be cheap).
    # Dense-tier relations occupy no arena slots.
    arena_ets = {s.etype for s in plan.arena_segments}
    real = sum(int(r[3].shape[0]) for r in relations if r[0] in arena_ets)
    for dname, arena in (("fwd", plan.fwd), ("bwd", plan.bwd)):
        c, br, ec = (int(s) for s in np.shape(arena.nbr))
        slots = c * br * ec
        _METRICS.set("arena.slots", slots, etype="__plan__", dir=dname)
        _METRICS.set("arena.padded_slots", slots - real,
                     etype="__plan__", dir=dname)
        _METRICS.set("arena.fill_ratio", real / slots if slots else 0.0,
                     etype="__plan__", dir=dname)
        _METRICS.set("arena.chunk", ec, etype="__plan__", dir=dname)
    return plan


def _quantize_arena(f: FusedELL, arena_bits: int, bounds: Sequence[int],
                    layout: Optional[BucketLayout],
                    key: Tuple[str, str]) -> FusedELL:
    """Pad the arena to shape-bucket-stable dims: rows to the deterministic
    cap (a function of the padded node count alone), chunks up the bucket
    grid, floored at the bucket's running max when a layout is tracking."""
    r_cap = _arena_row_cap(f.n_dst, bounds, f.row_block)
    assert f.n_arena_rows <= r_cap, (f.n_arena_rows, r_cap)
    c_pad = quantize_up(f.n_chunks, arena_bits, minimum=1)
    if layout is not None:
        floor = layout.min_chunks.get(key)
        if floor is None:       # first batch of the bucket: add headroom
            floor = quantize_up(int(np.ceil(f.n_chunks * ARENA_HEADROOM)),
                                arena_bits, minimum=1)
        c_pad = max(c_pad, floor)
        layout.min_chunks[key] = c_pad
    return _pad_fused_arena(f, c_pad, r_cap)
