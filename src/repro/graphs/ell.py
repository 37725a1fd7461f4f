"""Degree-bucketed ELL adjacency — the TPU analogue of dynamic warp partitioning.

The paper (Alg. 1, stage 2) classifies neighbor groups (rows) by degree and
partitions warps accordingly so that evil rows do not stall a whole warp.  On
TPU the execution unit is a Pallas grid cell over a *statically shaped* tile,
so the equivalent move is structural: bin rows by degree, pad each bin to its
own max degree (ELL), and dispatch each bin as its own kernel grid with a
block shape tuned to that bin.  Short rows never pay for evil rows' padding,
and evil rows get wide, deep tiles.

Two packings live here:

* :class:`BucketedELL` — one slab per degree bucket: the packing graphs
  are built and stored in, and the source every arena is fused from.
* :class:`FusedELL` — all bucket slabs re-chunked into a single uniform
  chunk arena plus a per-chunk metadata table, so the *entire* bucketed
  aggregation runs as ONE ``pallas_call`` (DESIGN.md §1).  Output rows are
  laid out arena-contiguously; a single inverse-permutation gather replaces
  a per-bucket ``y.at[rows].add`` combine.  Every op executes over it
  (kernels/ops.py).

All packing is host-side numpy (one-time preprocessing, matching the paper's
CSR/CSC preprocessing stage).
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Dict, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.obs.metrics import DEFAULT_REGISTRY as _METRICS

# Row-block granularity of the Pallas grid; bucket row counts are padded to it.
ROW_BLOCK = 8
# Default degree-bucket upper bounds (inclusive); last bucket is open-ended.
DEFAULT_BOUNDS = (4, 16, 64, 256)
# Neighbor-chunk width of the fused arena: each fused grid step contracts
# EDGE_CHUNK neighbors at once (an (BR, Ec·k) × (BR, Ec·k, D) MXU issue).
# 8 × k=16 = 128 = one MXU contraction dim; small enough that narrow rows
# (pin/pinned fan-outs of 2–6) waste at most one chunk of padding.
# This is the *fallback* width: ``fuse_bucketed`` picks the slot-minimizing
# width per packing from its degree histogram (``pick_chunk``) unless the
# caller pins one explicitly.
EDGE_CHUNK = 8
# Candidate chunk widths ``pick_chunk`` chooses between.  Powers of two so
# Ec·k stays MXU-aligned for the usual k ∈ {8, 16, 32}.
CHUNK_CANDIDATES = (4, 8, 16)
# Row-block height of the fused arena.  Kept at the Pallas grid granularity:
# the degree-sort makes a block's chunk count track the max width of just
# these 8 rows, so smaller blocks mean tighter adaptive widths.
FUSED_ROW_BLOCK = 8
# Dense-tier crossover: relations at or below this nnz run as ONE masked
# dense matmul instead of the chunk-walk arena (DESIGN.md §14).  Measured on
# CPU (xla timing, dim=64, k=16): at nnz≈2k the dense fwd/bwd are 2–4x
# faster than the arena, at nnz≈6–7k the arena is competitive on grad and
# ahead on TPU-shaped work — 4096 splits the measured gap.  Interpret-mode
# timings are meaningless here (ROADMAP: re-tune on real TPU).
DENSE_TIER_NNZ = 4096
# Safety valve on the dense-tier table: never densify a relation whose
# n_dst·n_src exceeds this (a 4M-entry f32 table is 16 MiB per direction —
# past that the arena wins on memory regardless of nnz).
DENSE_TIER_AREA = 1 << 22


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ELLBucket:
    """One degree bin: ``rows[r]`` is the destination row that ``nbr[r]``
    describes.  Padded neighbor slots have weight 0 and index 0; padded row
    slots have ``rows == 0`` and all-zero weights (inert under scatter-add).
    """

    rows: jax.Array   # (R,) int32 destination row ids
    nbr: jax.Array    # (R, E) int32 source ids
    w: jax.Array      # (R, E) float edge weights

    @property
    def n_rows(self) -> int:
        return self.rows.shape[0]

    @property
    def width(self) -> int:
        return self.nbr.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BucketedELL:
    """A sparse (n_dst x n_src) matrix as a tuple of degree-bucketed ELL slabs.

    ``nnz`` is counted once at pack time (host-side) and stored as a static
    field — reading it never forces a device→host sync.  ``-1`` means the
    packing predates the count (hand-built instances); consumers treat that
    as unknown.
    """

    buckets: Tuple[ELLBucket, ...]
    n_dst: int = dataclasses.field(metadata=dict(static=True))
    n_src: int = dataclasses.field(metadata=dict(static=True))
    nnz: int = dataclasses.field(metadata=dict(static=True), default=-1)

    def to_dense(self) -> jax.Array:
        a = jnp.zeros((self.n_dst, self.n_src), jnp.float32)
        for b in self.buckets:
            r = jnp.repeat(b.rows[:, None], b.width, axis=1)
            a = a.at[r, b.nbr].add(b.w)
        return a


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _stable_order(keys: np.ndarray) -> np.ndarray:
    """``np.argsort(keys, kind="stable")`` for non-negative ids, by LSD
    radix passes over 16-bit digits.  numpy's stable sort of a 16-bit
    dtype is a counting (radix) sort, linear in ``keys.size``, where the
    comparison sort of int64 ids is n log n; a stable sort's permutation
    is unique, so the result is the same."""
    keys = np.asarray(keys, np.int64)
    top = int(keys.max()) if keys.size else 0
    order = np.argsort((keys & 0xFFFF).astype(np.uint16), kind="stable")
    shift = 16
    while top >> shift:
        digit = ((keys[order] >> shift) & 0xFFFF).astype(np.uint16)
        order = order[np.argsort(digit, kind="stable")]
        shift += 16
    return order


def pack_ell(dst: np.ndarray, src: np.ndarray, w: np.ndarray | None,
             n_dst: int, n_src: int,
             bounds: Sequence[int] = DEFAULT_BOUNDS,
             row_block: int = ROW_BLOCK) -> BucketedELL:
    """Pack COO edges into degree-bucketed ELL.

    Parameters
    ----------
    dst, src : int arrays (nnz,) — edge endpoints (dst aggregates from src).
    w : float array (nnz,) or None for unit weights.
    bounds : inclusive degree upper bounds for all but the last bucket.

    Each row keeps its edges in their COO order (a stable sort by ``dst``).
    Every bucket's slab is a window of one flat buffer, which a single
    scatter fills: an edge lands at its row's start in the buffer (the
    row's place in its bucket × the bucket's width) plus its slot in the
    row, ``arange(nnz) − rowptr[dst]``.
    """
    dst = np.asarray(dst, np.int64)
    src = np.asarray(src, np.int64)
    if w is None:
        w = np.ones(dst.shape[0], np.float32)
    w = np.asarray(w, np.float32)

    # CSR-ify (stage 1 of Alg. 1).
    order = _stable_order(dst)
    dst, src, w = dst[order], src[order], w[order]
    deg = np.bincount(dst, minlength=n_dst)
    rowptr = np.zeros(n_dst + 1, np.int64)
    np.cumsum(deg, out=rowptr[1:])

    # Stage 2: classify rows by degree.  Empty rows are dropped entirely.
    nonempty = np.nonzero(deg > 0)[0]
    start = np.zeros(deg.size, np.int64)    # row's first slot in the buffer
    slabs = []                              # (rows, n_r, width, offset)
    size = 0
    lo = 1
    bnds = list(bounds) + [int(deg.max()) if deg.size and deg.max() > 0 else 1]
    for hi in bnds:
        if hi < lo:
            continue
        rows = nonempty[(deg[nonempty] >= lo) & (deg[nonempty] <= hi)]
        lo = hi + 1
        if rows.size == 0:
            continue
        width = int(deg[rows].max())
        n_r = _round_up(rows.size, row_block)
        start[rows] = size + np.arange(rows.size) * width
        slabs.append((rows, n_r, width, size))
        size += n_r * width

    nbr = np.zeros(size, np.int32)
    wts = np.zeros(size, np.float32)
    at = (start - rowptr[:-1])[dst] + np.arange(dst.size)
    nbr[at] = src
    wts[at] = w
    nnz = int((wts != 0).sum())
    buckets = []
    for rows, n_r, width, off in slabs:
        rid = np.zeros(n_r, np.int32)
        rid[: rows.size] = rows
        window = slice(off, off + n_r * width)
        buckets.append(ELLBucket(
            rows=jnp.asarray(rid),
            nbr=jnp.asarray(nbr[window].reshape(n_r, width)),
            w=jnp.asarray(wts[window].reshape(n_r, width))))
    if not buckets:  # empty matrix — keep one inert bucket for shape sanity
        buckets = [ELLBucket(rows=jnp.zeros((row_block,), jnp.int32),
                             nbr=jnp.zeros((row_block, 1), jnp.int32),
                             w=jnp.zeros((row_block, 1), jnp.float32))]
    return BucketedELL(buckets=tuple(buckets), n_dst=n_dst, n_src=n_src,
                       nnz=nnz)


def pack_eid_slabs(dst: np.ndarray, src: np.ndarray, n_dst: int, n_src: int,
                   bounds: Sequence[int] = DEFAULT_BOUNDS):
    """Edge-id slabs aligned with :func:`pack_ell`'s bucketing.

    Packs edge *indices* (into the canonical dst-stable-sorted edge order)
    instead of weights, with ``nnz`` as the padding sentinel.  Lets a
    learnable weight vector w (nnz,) be gathered into the exact slab layout
    pack_ell produces — the basis of differentiable edge weights
    (``kernels/ops.py::drspmm_learnable`` over their
    ``fuse_bucketed(..., eids=True)`` arenas).  Returns (fwd_slabs, bwd_slabs, order, nnz):
    slabs are BucketedELL whose ``w`` holds f32-encoded edge ids (exact up
    to 2^24 edges); ``order`` maps the canonical order back to the caller's
    COO order.
    """
    dst = np.asarray(dst, np.int64)
    src = np.asarray(src, np.int64)
    nnz = dst.shape[0]
    assert nnz < (1 << 24), "edge ids exceed f32 exact-integer range"
    order = _stable_order(dst)                       # pack_ell's canonical
    eid = np.empty(nnz, np.int64)
    eid[order] = np.arange(nnz)                      # caller-order -> canon
    fwd = pack_ell(dst, src, eid.astype(np.float32) + 1.0, n_dst, n_src,
                   bounds)
    bwd = pack_ell(src, dst, eid.astype(np.float32) + 1.0, n_src, n_dst,
                   bounds)
    # ids stored +1 so padding (0.0) maps to sentinel −1 after decode
    return fwd, bwd, order, nnz


def decode_eids(slab_w) -> "jax.Array":
    """f32-encoded (id+1) slab -> int32 ids with −1 padding sentinel."""
    return (slab_w.astype(jnp.int32)) - 1


def pack_ell_pair(dst: np.ndarray, src: np.ndarray, w: np.ndarray | None,
                  n_dst: int, n_src: int,
                  bounds: Sequence[int] = DEFAULT_BOUNDS
                  ) -> Tuple[BucketedELL, BucketedELL]:
    """Forward (A, row-major over dst) and backward (Aᵀ, row-major over src)
    packings — the CSR/CSC pair of Alg. 1/Alg. 2.  The transposed packing
    makes every *source* row owned by exactly one grid cell, so the backward
    needs no atomics (see DESIGN.md §2)."""
    fwd = pack_ell(dst, src, w, n_dst, n_src, bounds)
    bwd = pack_ell(src, dst, w, n_src, n_dst, bounds)
    return fwd, bwd


def degree_stats(dst: np.ndarray, n_dst: int) -> dict:
    deg = np.bincount(np.asarray(dst, np.int64), minlength=n_dst)
    return dict(degrees=deg, max=int(deg.max()) if deg.size else 0,
                mean=float(deg.mean()) if deg.size else 0.0)


def ell_to_coo(adj: BucketedELL) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side inverse of :func:`pack_ell`: (dst, src, w) of the non-zero
    slots.  Zero-weight slots are padding by construction, so the round trip
    preserves exactly the ``nnz`` edges the packing represents.  Used by the
    block-diagonal collator (graphs/collate.py), which re-packs member
    graphs' edges with per-member node-id offsets."""
    ds, ss, ws = [], [], []
    for b in adj.buckets:
        w = np.asarray(b.w, np.float32)
        mask = w != 0
        if not mask.any():
            continue
        rows = np.broadcast_to(np.asarray(b.rows, np.int64)[:, None], w.shape)
        ds.append(rows[mask])
        ss.append(np.asarray(b.nbr, np.int64)[mask])
        ws.append(w[mask])
    if not ds:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros(0, np.float32))
    return np.concatenate(ds), np.concatenate(ss), np.concatenate(ws)


# ---------------------------------------------------------------------------
# FusedELL — single-dispatch arena packing (DESIGN.md §1)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FusedELL:
    """All degree buckets re-chunked into one uniform (C, BR, Ec) arena.

    Every chunk holds ``row_block`` rows × ``chunk`` neighbor slots of ONE
    bucket's ELL slab; zero-weight slots are inert padding.  Chunks of the
    same output row-block are stored consecutively, so a Pallas grid over
    chunks revisits each output block in an unbroken run — the grouped-matmul
    accumulation pattern that needs no atomics and no host-side combine.

    ``block_of``/``start`` are the scalar-prefetch metadata table: the output
    row-block each chunk accumulates into, and whether the chunk opens its
    block (→ zero-init).  ``rows`` maps arena rows back to original row ids
    (padding → 0 with zero weights); ``gather`` is the inverse map used to
    read the final (n_dst, D) output out of the arena with ONE gather —
    original rows absent from every bucket point at the trailing sentinel
    block, which is written as all-zeros.
    """

    nbr: jax.Array       # (C, BR, Ec) int32 source ids
    w: jax.Array         # (C, BR, Ec) f32 edge weights (0 = padding)
    block_of: jax.Array  # (C,) int32 output row-block per chunk
    start: jax.Array     # (C,) int32 1 iff chunk opens its row-block
    rows: jax.Array      # (R_arena,) int32 original row per arena row
    gather: jax.Array    # (n_dst,) int32 arena row per original row
    n_dst: int = dataclasses.field(metadata=dict(static=True))
    n_src: int = dataclasses.field(metadata=dict(static=True))
    nnz: int = dataclasses.field(metadata=dict(static=True))
    row_block: int = dataclasses.field(metadata=dict(static=True))
    chunk: int = dataclasses.field(metadata=dict(static=True))
    # Edge-id arena for learnable per-edge weights (kernels/ops.py::
    # drspmm_learnable): (C, BR, Ec) int32 canonical edge ids, padding
    # slots -> −1.  Chunked exactly like ``w``, so a canonical weight
    # vector (nnz,) gathers straight into arena layout.  ``None`` for
    # fixed-weight packings.
    eid: jax.Array | None = None
    # Relation id per chunk for relation-fused super-arenas
    # (:func:`build_relation_plan`): (C,) int32 index into the plan's
    # segment tuple.  The kernels never read it — relation selection is
    # baked into ``nbr``/``block_of``/``rows`` offsets at pack time — but
    # it makes every chunk's provenance auditable (segment round-trip
    # tests, bench dispatch accounting).  ``None`` for single-relation
    # arenas.
    rel: jax.Array | None = None

    @property
    def n_chunks(self) -> int:
        return self.nbr.shape[0]

    @property
    def n_arena_rows(self) -> int:
        return self.rows.shape[0]

    def to_dense(self) -> np.ndarray:
        """Host-side dense reconstruction (round-trip tests)."""
        a = np.zeros((self.n_dst, self.n_src), np.float32)
        nbr = np.asarray(self.nbr)
        w = np.asarray(self.w)
        blk = np.asarray(self.block_of)
        rows = np.asarray(self.rows)
        br = self.row_block
        for c in range(nbr.shape[0]):
            for b in range(br):
                rid = rows[blk[c] * br + b]
                mask = w[c, b] != 0
                np.add.at(a[rid], nbr[c, b][mask], w[c, b][mask])
        return a


# id-keyed memo: fusing is host-side numpy work we only want once per packing.
_FUSE_CACHE: Dict[tuple, tuple] = {}


def _effective_widths(w: np.ndarray) -> np.ndarray:
    """Per-row count of slots up to the last non-zero one (pack_ell fills
    rows left-to-right, so this is the row's effective degree)."""
    nz = w != 0
    e = w.shape[1]
    return np.where(nz.any(axis=1), e - np.argmax(nz[:, ::-1], axis=1), 0)


def _block_widths(adj: BucketedELL, row_block: int) -> np.ndarray:
    """Max effective width of each fused row-block, after the descending
    degree sort each bucket undergoes inside :func:`fuse_bucketed` — i.e.
    exactly the widths the arena's chunk counts are derived from."""
    bws = []
    for b in adj.buckets:
        width_r = np.sort(_effective_widths(np.asarray(b.w, np.float32)))[::-1]
        rpad = _round_up(max(width_r.size, 1), row_block)
        width_r = np.concatenate(
            [width_r, np.zeros(rpad - width_r.size, width_r.dtype)])
        bws.append(width_r.reshape(-1, row_block).max(axis=1))
    return np.concatenate([np.zeros(0, np.int64)] + bws)


def _min_slot_chunk(bws: np.ndarray, row_block: int,
                    candidates: Sequence[int]) -> int:
    """The candidate chunk width minimizing Σ_blocks BR·Ec·max(1,
    ceil(bw/Ec)) over the block widths ``bws``; ties go to the wider."""
    cands = np.asarray(candidates, np.int64)
    bws = np.asarray(bws, np.int64)
    n_chunks = np.maximum(1, -(-bws[None, :] // cands[:, None]))
    slots = row_block * cands * n_chunks.sum(axis=1)
    _slots, neg_chunk = min(zip(slots.tolist(), (-cands).tolist()))
    return -neg_chunk


def pick_chunk(adj: BucketedELL, row_block: int = None,
               candidates: Sequence[int] = CHUNK_CANDIDATES) -> int:
    """Slot-minimizing arena chunk width for this packing (ROADMAP item).

    ``EDGE_CHUNK = 8`` is tuned for the heavy-tailed ``near`` degrees; the
    narrow ``pin``/``pinned`` fan-outs (2–6) pay up to 2× slot padding at
    width 8.  This picks, from the packing's own degree histogram, the
    candidate minimizing total arena slots Σ_blocks BR·Ec·ceil(bw/Ec); ties
    go to the wider chunk (fewer grid steps, bigger MXU contractions).
    """
    if row_block is None:
        row_block = FUSED_ROW_BLOCK
    return _min_slot_chunk(_block_widths(adj, row_block), row_block,
                           candidates)


def fuse_bucketed(adj: BucketedELL, row_block: int = None,
                  chunk: int = None, *, eids: bool = False) -> FusedELL:
    """Re-pack a :class:`BucketedELL` into the single-dispatch fused arena.

    ``chunk=None`` picks the slot-minimizing width from the packing's degree
    histogram (:func:`pick_chunk`); pass an int to pin the layout (the
    collator does, so batches of the same shape bucket share a signature).

    ``eids=True`` treats ``adj`` as an edge-id slab packing
    (:func:`pack_eid_slabs` layout: ``w`` holds f32-encoded ``id+1``,
    0 = padding).  The arena then carries a decoded int32 ``eid`` table
    (padding slots → −1) chunked exactly like the weight arena, and ``w``
    becomes the 0/1 real-slot mask — the layout the fused learnable
    executors (kernels/drspmm.py) gather a canonical weight vector into.

    Pure host-side preprocessing; results are memoized per (packing, layout)
    so jit re-traces and repeated layer calls never re-pack.
    """
    if row_block is None:
        row_block = FUSED_ROW_BLOCK
    # chunk=None is memoized under the None key, so a cache hit skips even
    # the pick_chunk histogram scan.
    key = (id(adj), row_block, chunk, eids)
    hit = _FUSE_CACHE.get(key)
    if hit is not None and hit[0]() is adj:
        return hit[1]
    if chunk is None:
        chunk = pick_chunk(adj, row_block)

    # Per bucket, one reshape/transpose turns the padded (rows, width) slab
    # into its (blocks, chunks, BR, Ec) chunk grid, and a mask keeps each
    # row-block's first max(1, ceil(bw/Ec)) chunks, in (block, chunk) order.
    nbr_parts, w_parts, blk_parts, start_parts = [], [], [], []
    rows_parts = []
    gather = np.full(adj.n_dst, -1, np.int64)
    blk = 0
    arena_off = 0
    for b in adj.buckets:
        nb = np.asarray(b.nbr)
        wt = np.asarray(b.w, np.float32)
        rid = np.asarray(b.rows, np.int64)
        r, e = nb.shape
        rpad = _round_up(max(r, 1), row_block)
        epad = _round_up(max(e, 1), chunk)
        nb_p = np.zeros((rpad, epad), np.int32)
        wt_p = np.zeros((rpad, epad), np.float32)
        nb_p[:r, :e] = nb
        wt_p[:r, :e] = wt
        rid_p = np.zeros(rpad, np.int32)
        rid_p[:r] = rid
        # Effective row width = last carried weight (pack_ell fills rows
        # left-to-right; zero-weight slots contribute nothing either way).
        width_r = _effective_widths(wt_p)
        # Finer-than-bucket adaptivity: order rows by effective width so
        # each row-block's chunk count tracks its OWN max degree, not the
        # bucket's.  A degree-17 row in a width-64 bucket then costs
        # ceil(17/Ec) chunks instead of the whole slab (DESIGN.md §1.2).
        order = np.argsort(-width_r, kind="stable")
        nb_p, wt_p, rid_p, width_r = (nb_p[order], wt_p[order],
                                      rid_p[order], width_r[order])
        # A row is "real" iff it carries any weight; all-zero rows produce
        # all-zero output either way, so routing them to the sentinel is
        # equivalent (DESIGN.md §1.3).
        real = width_r > 0
        gather[rid_p[real]] = arena_off + np.nonzero(real)[0]
        rows_parts.append(rid_p)
        arena_off += rpad
        n_blk, n_ch = rpad // row_block, epad // chunk
        bw = width_r.reshape(n_blk, row_block).max(axis=1)
        nch = np.maximum(1, -(-bw // chunk))      # ≥1 so the block inits
        keep = np.arange(n_ch)[None, :] < nch[:, None]    # (blocks, chunks)

        def chunks(a):
            return a.reshape(n_blk, row_block, n_ch, chunk) \
                .transpose(0, 2, 1, 3)[keep]

        nbr_parts.append(chunks(nb_p))
        w_parts.append(chunks(wt_p))
        blk_parts.append(np.repeat(blk + np.arange(n_blk), nch))
        start_parts.append(np.nonzero(keep)[1] == 0)
        blk += n_blk

    # Trailing sentinel block: BR guaranteed-zero arena rows that empty
    # original rows gather from.
    nbr_parts.append(np.zeros((1, row_block, chunk), np.int32))
    w_parts.append(np.zeros((1, row_block, chunk), np.float32))
    blk_parts.append(np.asarray([blk]))
    start_parts.append(np.ones(1, bool))
    sentinel_row = arena_off
    rows_parts.append(np.zeros(row_block, np.int32))
    gather[gather < 0] = sentinel_row

    nnz = adj.nnz if adj.nnz >= 0 else int(
        sum(int((np.asarray(b.w) != 0).sum()) for b in adj.buckets))
    w_arena = np.concatenate(w_parts)
    eid_arena = None
    if eids:
        # w slots hold f32(id+1) with 0 padding (exact up to 2^24 edges,
        # asserted at pack time): decode to −1-padded int32 ids and leave
        # the 0/1 real-slot mask as the arena weight.
        eid_arena = w_arena.astype(np.int32) - 1
        w_arena = (w_arena != 0).astype(np.float32)
    # NB: leaves stay host numpy — fusing may run lazily inside a jit trace
    # (first call of a jitted layer), where jnp.asarray would capture
    # tracers into the memo and leak them out of the trace.  numpy leaves
    # are trace-safe constants.
    fused = FusedELL(
        nbr=np.concatenate(nbr_parts),
        w=w_arena,
        block_of=np.concatenate(blk_parts).astype(np.int32),
        start=np.concatenate(start_parts).astype(np.int32),
        rows=np.concatenate(rows_parts).astype(np.int32),
        gather=gather.astype(np.int32),
        n_dst=adj.n_dst, n_src=adj.n_src, nnz=nnz,
        row_block=row_block, chunk=chunk, eid=eid_arena)
    # Evict promptly when the packing dies — a dead entry would otherwise
    # pin its whole fused arena (id reuse is also why the hit path
    # re-checks `ref() is adj`).
    _FUSE_CACHE[key] = (weakref.ref(adj, lambda _: _FUSE_CACHE.pop(key, None)),
                        fused)
    return fused


def arena_stats(f: FusedELL, bucketed: BucketedELL | None = None) -> dict:
    """Pack-time arena efficiency report (DESIGN.md §11).

    The numbers behind the §1 chunking math, made observable instead of
    hand-derivable: total arena slots ``C·BR·Ec``, how many carry real
    edges, the padding overhead, and the chunk-width choice.  With the
    source ``bucketed`` packing, also the bucket-slab baseline (each ELL
    bucket dispatched as its own rows×width slab, the pre-PR-1 layout) and
    ``slot_saving`` — slab slots per arena slot, the adaptive-chunking win
    (~1.9x on heavy-tailed ``near`` degrees; asserted in
    tests/test_obs_arena.py).

    Works on padded arenas too (``pad_fused_arena`` resets ``nnz`` to −1,
    so real slots fall back to a host-side non-zero count of ``w``).
    """
    c, br, ec = (int(s) for s in np.shape(f.nbr))
    slots = c * br * ec
    real = f.nnz if f.nnz >= 0 else int(np.count_nonzero(np.asarray(f.w)))
    out = dict(n_chunks=c, row_block=br, chunk=ec, slots=slots,
               real_slots=real, padded_slots=slots - real,
               fill_ratio=real / slots if slots else 0.0)
    if bucketed is not None:
        slab = sum(int(np.shape(b.nbr)[0]) * int(np.shape(b.nbr)[1])
                   for b in bucketed.buckets)
        out["slab_slots"] = slab
        out["slot_saving"] = slab / slots if slots else 0.0
    return out


def pack_fused(dst: np.ndarray, src: np.ndarray, w: np.ndarray | None,
               n_dst: int, n_src: int,
               bounds: Sequence[int] = DEFAULT_BOUNDS,
               row_block: int = None,
               chunk: int = None) -> FusedELL:
    """COO → fused single-dispatch arena (pack_ell then fuse)."""
    return fuse_bucketed(pack_ell(dst, src, w, n_dst, n_src, bounds),
                         row_block=row_block, chunk=chunk)


def pack_fused_pair(dst: np.ndarray, src: np.ndarray, w: np.ndarray | None,
                    n_dst: int, n_src: int,
                    bounds: Sequence[int] = DEFAULT_BOUNDS
                    ) -> Tuple[FusedELL, FusedELL]:
    """Fused forward/transposed pair (the CSR/CSC analogue of Alg. 1/2)."""
    return (pack_fused(dst, src, w, n_dst, n_src, bounds),
            pack_fused(src, dst, w, n_src, n_dst, bounds))


def pack_fused_eid_pair(dst: np.ndarray, src: np.ndarray,
                        n_dst: int, n_src: int,
                        bounds: Sequence[int] = DEFAULT_BOUNDS,
                        row_block: int = None,
                        chunk: Union[int, None, Tuple] = None
                        ) -> Tuple[FusedELL, FusedELL, np.ndarray, int]:
    """Fused edge-id arena pair for learnable per-edge weights.

    The eid analogue of :func:`pack_fused_pair`: packs edge *indices* (into
    the canonical dst-stable-sorted order, :func:`pack_eid_slabs`) and fuses
    both directions into arenas carrying ``eid`` tables (−1 padding), so a
    learnable weight vector w (nnz,) gathers straight into arena layout on
    the single-dispatch path (kernels/ops.py::drspmm_learnable).

    ``chunk`` pins the arena chunk width: an int for both directions, or a
    ``(fwd, bwd)`` tuple (the collator pins per direction).  Returns
    ``(fwd_arena, bwd_arena, order, nnz)`` with ``order`` mapping the
    canonical order back to the caller's COO order.
    """
    fwd, bwd, order, nnz = pack_eid_slabs(dst, src, n_dst, n_src, bounds)
    ck_f, ck_b = chunk if isinstance(chunk, tuple) else (chunk, chunk)
    return (fuse_bucketed(fwd, row_block, ck_f, eids=True),
            fuse_bucketed(bwd, row_block, ck_b, eids=True),
            order, nnz)


def pad_fused_arena(f: FusedELL, n_chunks: int, n_rows: int) -> FusedELL:
    """Pad a fused arena to (n_chunks, ·, ·) chunks / n_rows arena rows.

    Padding chunks carry zero weights and extend the run of the arena's
    LAST block — the all-zero sentinel ``fuse_bucketed`` always emits last —
    with ``start=0``, so the grouped-matmul revisit invariant (unbroken
    chunk run per block, DESIGN.md §1) holds and the sentinel stays zero.
    Padding rows are simply appended: no chunk references them and the
    output gather never reads them, so they need no initializing chunk.
    ``nnz`` is reset to −1 (unknown): batches of one shape bucket differ in
    nnz, and a static nnz would split the jit cache per batch.

    Used by the block-diagonal collator (graphs/collate.py) for
    shape-bucket-stable batch arenas, and by :func:`build_relation_plan`
    for bucket-stable per-relation segments of a super-arena.
    """
    c, br, ec = f.nbr.shape
    r = f.n_arena_rows
    assert n_rows % br == 0 and n_rows >= r and n_chunks >= c
    pad_chunks = n_chunks - c
    sentinel = r // br - 1
    zpad = lambda a, n, dt: np.concatenate(
        [np.asarray(a), np.zeros((n,) + np.asarray(a).shape[1:], dt)])
    eid = None
    if f.eid is not None:        # learnable-edge arena: padding slots → −1
        eid = np.concatenate(
            [np.asarray(f.eid),
             np.full((pad_chunks, br, ec), -1, np.int32)])
    rel = None
    if f.rel is not None:        # padding chunks stay in the last relation
        rel = np.concatenate(
            [np.asarray(f.rel),
             np.full(pad_chunks, int(np.asarray(f.rel)[-1]), np.int32)])
    return FusedELL(
        nbr=zpad(f.nbr, pad_chunks, np.int32),
        w=zpad(f.w, pad_chunks, np.float32),
        block_of=np.concatenate([np.asarray(f.block_of),
                                 np.full(pad_chunks, sentinel, np.int32)]),
        start=np.concatenate([np.asarray(f.start),
                              np.zeros(pad_chunks, np.int32)]),
        rows=zpad(f.rows, n_rows - r, np.int32),
        gather=np.asarray(f.gather),
        n_dst=f.n_dst, n_src=f.n_src, nnz=-1,
        row_block=f.row_block, chunk=f.chunk, eid=eid, rel=rel)


def fused_to_coo(f: FusedELL) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side inverse of :func:`fuse_bucketed`: (dst, src, w) of the
    non-zero slots, in the arena's OWN coordinates.

    For a plain arena that means original row ids; for a super-arena
    (:func:`build_relation_plan`) dst comes out in relation-concat output
    coordinates and src in the type-concat source slab — exactly the global
    coordinate pair the mesh partitioner (sharding/plan_shard.py) shards on.
    Zero-weight slots are padding by construction, so the round trip yields
    exactly the edges the packing represents (vectorized, no chunk loop).
    """
    w = np.asarray(f.w, np.float32)                       # (C, BR, Ec)
    blk = np.asarray(f.block_of, np.int64)
    rows = np.asarray(f.rows, np.int64)
    br = f.row_block
    slot_row = rows[blk[:, None] * br + np.arange(br)]    # (C, BR)
    mask = w != 0
    dst = np.broadcast_to(slot_row[:, :, None], w.shape)[mask]
    src = np.asarray(f.nbr, np.int64)[mask]
    return dst, src, w[mask]


# ---------------------------------------------------------------------------
# RelationPlan — cross-relation super-arena (DESIGN.md §9)
# ---------------------------------------------------------------------------
#
# A hetero layer's message passing is one DR-SpMM per edge-type direction;
# PR 1–4 fused each direction into ONE dispatch but still walked the
# directions serially in Python.  The super-arena collapses that loop: every
# relation's fused arena is concatenated into one (C_total, BR, Ec) arena
# whose metadata bakes the relation routing in —
#
#   * ``nbr``     += the relation's source-type offset in the type-concat
#                    source slab  [x_cell; x_net]
#   * ``rows``    += (fwd) the relation's row offset in the concatenated
#                    output / (bwd) its source-type offset
#   * ``block_of``+= the preceding relations' block counts
#   * ``gather``   = per-relation gathers shifted by the preceding
#                    relations' arena rows
#
# so the §1 kernels run UNCHANGED over the whole direction-group: one
# pallas_call forward, one transposed pallas_call backward, per layer.

@dataclasses.dataclass(frozen=True)
class RelationSegment:
    """Where one relation lives inside a :class:`RelationPlan` (all static:
    part of the plan's pytree aux data, stable within a shape bucket).

    ``out_off`` is ALWAYS the relation's row offset in the full output
    concat (the y/gy slab every tier shares).  Arena-tier segments
    additionally carry ``arena_out_off`` (row offset in the arena-only fwd
    output concat) and ``src_out_off`` (offset in the arena-only dx concat);
    dense-tier segments carry ``dense_off`` (row offset in the plan's
    ``dense_fwd`` table) and leave the arena coordinates at −1 / (0, 0).
    """

    etype: str
    src_type: str
    dst_type: str
    n_dst: int                   # relation destination rows
    n_src: int                   # relation source rows
    out_off: int                 # row offset in the concat output / gy slab
    src_out_off: int             # row offset in the concat per-relation dx
    fwd_chunks: Tuple[int, int]  # [lo, hi) chunk range in the fwd arena
    bwd_chunks: Tuple[int, int]
    fwd_rows: Tuple[int, int]    # [lo, hi) arena-row range in the fwd arena
    bwd_rows: Tuple[int, int]
    tier: str = "arena"          # "arena" (chunk walk) | "dense" (matmul)
    dense_off: int = -1          # row offset in dense_fwd (dense tier only)
    arena_out_off: int = -1      # row offset in the arena-only fwd concat


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RelationPlan:
    """One hetero layer's whole message passing as a fwd/bwd super-arena
    pair plus the relation segment table.

    ``fwd`` aggregates every ARENA-tier relation in ONE dispatch over the
    type-concat source slab (n_src = Σ node-type sizes) into the arena-only
    output concat (n_dst = Σ arena-tier destinations); ``bwd`` is the
    transposed super-arena over the FULL concatenated output cotangents
    (its ``gather`` yields the arena-tier dx concat, summed per source type
    by the op).  DENSE-tier relations (sub-crossover nnz, DESIGN.md §14)
    bypass the chunk walk entirely: ``dense_fwd`` stacks their masked dense
    matrices over the full type-concat source width, and ``dense_bwd`` is
    its exact transpose, so the whole tier is one batched matmul per
    direction.  When no relation lands in a tier, that tier's tables are an
    inert placeholder (empty dense table / sentinel-only arena) the
    executor skips.  Consumed by :func:`repro.kernels.ops.drspmm_multi`.
    """

    fwd: FusedELL
    bwd: FusedELL
    # Type-concat source id per bwd ARENA row: the §2 xi gather reads
    # ``x_idx_concat[bwd_src_rows]``.  Kept separate from ``bwd.rows`` so
    # the bwd arena stays self-consistent over the relation-concat dx space
    # (``rows``/``gather`` are inverse maps there, ``to_dense`` is the
    # block matrix of the transposed relations).
    bwd_src_rows: jax.Array
    # Dense-tier tables: (dense_rows_total, n_src_total) f32 — segment d's
    # matrix occupies rows [dense_off, dense_off + n_dst) and columns
    # [src_off[src_type], + n_src); everything else is structural zero.
    # ``dense_bwd`` is dense_fwd.T, materialized so the backward matmul
    # reads a contiguous operand.  (0, n_src_total)/(n_src_total, 0) when
    # no relation is dense-tier.
    dense_fwd: jax.Array
    dense_bwd: jax.Array
    segments: Tuple[RelationSegment, ...] = dataclasses.field(
        metadata=dict(static=True))
    src_types: Tuple[str, ...] = dataclasses.field(
        metadata=dict(static=True))   # node types, source-concat order
    src_off: Tuple[int, ...] = dataclasses.field(
        metadata=dict(static=True))   # per-type offset in the source concat
    src_sizes: Tuple[int, ...] = dataclasses.field(
        metadata=dict(static=True))   # per-type node count

    @property
    def n_src_total(self) -> int:
        return self.fwd.n_src

    @property
    def n_out_total(self) -> int:
        return self.segments[-1].out_off + self.segments[-1].n_dst \
            if self.segments else self.fwd.n_dst

    @property
    def arena_segments(self) -> Tuple[RelationSegment, ...]:
        return tuple(s for s in self.segments if s.tier == "arena")

    @property
    def dense_segments(self) -> Tuple[RelationSegment, ...]:
        return tuple(s for s in self.segments if s.tier == "dense")

    @property
    def has_arena(self) -> bool:
        return any(s.tier == "arena" for s in self.segments)

    @property
    def has_dense(self) -> bool:
        return any(s.tier == "dense" for s in self.segments)

    def segment(self, etype: str) -> RelationSegment:
        for s in self.segments:
            if s.etype == etype:
                return s
        raise KeyError(etype)

    def to_dense(self) -> np.ndarray:
        """Full (n_out_total, n_src_total) block matrix across BOTH tiers —
        the oracle every executor path must match (round-trip tests)."""
        a = np.zeros((self.n_out_total, self.n_src_total), np.float32)
        if self.has_arena:
            fa = np.asarray(self.fwd.to_dense(), np.float32)
            for s in self.arena_segments:
                a[s.out_off:s.out_off + s.n_dst] = \
                    fa[s.arena_out_off:s.arena_out_off + s.n_dst]
        df = np.asarray(self.dense_fwd, np.float32)
        for s in self.dense_segments:
            a[s.out_off:s.out_off + s.n_dst] = \
                df[s.dense_off:s.dense_off + s.n_dst]
        return a


def pick_chunk_multi(packings: Sequence[BucketedELL], row_block: int = None,
                     candidates: Sequence[int] = CHUNK_CANDIDATES) -> int:
    """Slot-minimizing SHARED chunk width for a super-arena.

    A super-arena is one uniform (C, BR, Ec) arena, so all relations must
    agree on Ec; this reuses :func:`pick_chunk`'s per-relation degree
    histogram (``_block_widths``) and minimizes the SUMMED slot count
    Σ_relations Σ_blocks BR·Ec·ceil(bw/Ec).  Ties go to the wider chunk,
    matching ``pick_chunk``."""
    if row_block is None:
        row_block = FUSED_ROW_BLOCK
    bws = np.concatenate([np.zeros(0, np.int64)]
                         + [_block_widths(p, row_block) for p in packings])
    return _min_slot_chunk(bws, row_block, candidates)


def _empty_super_arena(n_dst: int, n_src: int, row_block: int,
                       chunk: int) -> FusedELL:
    """Inert placeholder arena for a tier nothing landed in: one all-zero
    sentinel chunk/block, every output row gathering from the zero block.
    The executors never dispatch it (``plan.has_arena`` gates the call),
    but keeping the pytree structure uniform means tier composition never
    changes the plan's leaf COUNT — only leaf shapes, which the collator's
    bucket pinning already keeps stable."""
    return FusedELL(
        nbr=np.zeros((1, row_block, chunk), np.int32),
        w=np.zeros((1, row_block, chunk), np.float32),
        block_of=np.zeros(1, np.int32),
        start=np.ones(1, np.int32),
        rows=np.zeros(row_block, np.int32),
        gather=np.zeros(n_dst, np.int32),
        n_dst=n_dst, n_src=n_src, nnz=0,
        row_block=row_block, chunk=chunk,
        rel=np.zeros(1, np.int32))


def plan_to_coo(plan: "RelationPlan"
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side (dst, src, w) of EVERY edge a plan represents, across both
    tiers, in full-output-concat / type-concat-source coordinates — the
    global coordinate pair the mesh partitioner (sharding/plan_shard.py)
    shards on.  Arena-tier edges come from :func:`fused_to_coo` with their
    arena-concat rows remapped to full output rows; dense-tier edges come
    straight from the non-zeros of ``dense_fwd``."""
    ds, ss, ws = [], [], []
    if plan.has_arena:
        d, s, w = fused_to_coo(plan.fwd)
        shift = np.zeros(plan.fwd.n_dst, np.int64)
        for seg in plan.arena_segments:
            shift[seg.arena_out_off:seg.arena_out_off + seg.n_dst] = \
                seg.out_off - seg.arena_out_off
        ds.append(d + shift[d])
        ss.append(s)
        ws.append(w)
    if plan.has_dense:
        df = np.asarray(plan.dense_fwd, np.float32)
        for seg in plan.dense_segments:
            blk = df[seg.dense_off:seg.dense_off + seg.n_dst]
            r, c = np.nonzero(blk)
            ds.append(r.astype(np.int64) + seg.out_off)
            ss.append(c.astype(np.int64))
            ws.append(blk[r, c])
    if not ds:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros(0, np.float32))
    return np.concatenate(ds), np.concatenate(ss), np.concatenate(ws)


def _concat_arenas(arenas: Sequence[FusedELL], nbr_offs: Sequence[int],
                   rows_offs: Sequence[int], n_dst: int, n_src: int
                   ) -> Tuple[FusedELL, list]:
    """Concatenate per-relation fused arenas into one super-arena.

    ``nbr_offs[i]``/``rows_offs[i]`` are added to arena i's neighbor ids /
    row ids (padding slots get offset too — they carry zero weights, so
    pointing them at row ``off`` instead of 0 is equally inert and keeps
    every id in range).  Each arena keeps its own sentinel block, so the
    per-relation gathers stay valid after shifting.  Returns the super
    arena plus per-relation (chunk_off, row_off) pairs for the segment
    table."""
    br = arenas[0].row_block
    ck = arenas[0].chunk
    assert all(a.row_block == br and a.chunk == ck for a in arenas), \
        "super-arena members must share (row_block, chunk)"
    offs, c_off, r_off = [], 0, 0
    nbr, w, blk, start, rows, gather, rel = [], [], [], [], [], [], []
    for i, (a, no, ro) in enumerate(zip(arenas, nbr_offs, rows_offs)):
        offs.append((c_off, r_off))
        nbr.append(np.asarray(a.nbr) + np.int32(no))
        w.append(np.asarray(a.w))
        blk.append(np.asarray(a.block_of) + np.int32(r_off // br))
        start.append(np.asarray(a.start))
        rows.append(np.asarray(a.rows) + np.int32(ro))
        gather.append(np.asarray(a.gather) + np.int32(r_off))
        rel.append(np.full(a.n_chunks, i, np.int32))
        c_off += a.n_chunks
        r_off += a.n_arena_rows
    nnzs = [a.nnz for a in arenas]
    fused = FusedELL(
        nbr=np.concatenate(nbr), w=np.concatenate(w),
        block_of=np.concatenate(blk), start=np.concatenate(start),
        rows=np.concatenate(rows), gather=np.concatenate(gather),
        n_dst=n_dst, n_src=n_src,
        nnz=-1 if any(n < 0 for n in nnzs) else int(sum(nnzs)),
        row_block=br, chunk=ck, rel=np.concatenate(rel))
    return fused, offs


def build_relation_plan(relations: Sequence[tuple], n_of: Dict[str, int], *,
                        bounds: Sequence[int] = DEFAULT_BOUNDS,
                        row_block: int = None,
                        chunk: Union[int, None, Tuple] = None,
                        pad: Dict[str, Dict[str, Tuple[int, int]]] = None,
                        packed: Dict[str, Tuple[BucketedELL,
                                                BucketedELL]] = None,
                        dense_threshold: int = None,
                        tiers: Dict[str, str] = None
                        ) -> RelationPlan:
    """Pack every relation of a hetero layer into one fwd/bwd super-arena
    plus a dense-tier table for sub-crossover relations (DESIGN.md §14).

    Parameters
    ----------
    relations : sequence of ``(etype, src_type, dst_type, dst, src, w)``
        COO edge lists per relation; the sequence order fixes the segment
        (and output-concat) order.  With ``packed`` whose packings carry
        their ``nnz`` (as :func:`pack_ell`'s do) the COO is not read, and
        ``(etype, src_type, dst_type)`` alone will do.
    n_of : ordered ``{node_type: count}`` — the order fixes the source
        concat layout ``[type0; type1; …]`` the caller's CBSR operands are
        stacked in.
    chunk : shared arena chunk width — an int for both directions, a
        ``(fwd, bwd)`` tuple, or ``None`` to pick the summed-slot-minimizing
        width per direction from the relations' degree histograms
        (:func:`pick_chunk_multi`).  The collator pins it per shape bucket.
    pad : optional ``{etype: {"fwd"|"bwd": (n_chunks, n_rows)}}`` — or a
        callable ``(etype, "fwd"|"bwd", arena) -> (n_chunks, n_rows)`` —
        padding each relation's sub-arena to bucket-stable dims BEFORE
        concatenation (:func:`pad_fused_arena`), so collated plans of one
        shape bucket share a signature (the collator passes a closure over
        its quantization grid + ``BucketLayout`` floors).
    packed : optional ``{etype: (fwd_bucketed, bwd_bucketed)}`` — reuse
        already-built degree-bucketed packings instead of re-running
        ``pack_ell`` (:func:`repro.graphs.circuit.relation_plan_of` passes
        the graph's own pair; the collator shares the pair it packs for
        the per-edge-type arenas; fusing at the plan's shared chunk width
        is memoized separately per (packing, width)).  Plans built here
        count in ``graph.plan_builds{source="packed"|"coo"}``.
    dense_threshold : nnz at or below which a relation is routed to the
        dense tier (default :data:`DENSE_TIER_NNZ`); the
        :data:`DENSE_TIER_AREA` table-size guard always applies on top.
    tiers : optional ``{etype: "arena"|"dense"}`` overriding the nnz
        classification per relation — the collator pins the first-seen
        tiering per shape bucket with this, so padded members of one bucket
        share segment statics (and thus a jit signature) even when filler
        members' nnz straddles the threshold.
    """
    if row_block is None:
        row_block = FUSED_ROW_BLOCK
    src_types = tuple(n_of)
    src_off, off = {}, 0
    for t in src_types:
        src_off[t] = off
        off += int(n_of[t])
    n_src_total = off
    thr = DENSE_TIER_NNZ if dense_threshold is None else int(dense_threshold)
    _METRICS.inc("graph.plan_builds",
                 source="coo" if packed is None else "packed")

    # Plan packing may run lazily inside a jit trace (first call of a
    # jitted layer over a concrete graph): force the pack_ell slabs to be
    # concrete there — otherwise their jnp leaves become traced constants
    # the host-side fuser cannot np.asarray.  The resulting plan stores
    # host numpy leaves only (trace-safe constants, like _FUSE_CACHE's).
    with jax.ensure_compile_time_eval():
        if packed is not None:
            fwd_b = [packed[r[0]][0] for r in relations]
            bwd_b = [packed[r[0]][1] for r in relations]
        else:
            fwd_b = [pack_ell(dst, src, w, int(n_of[dt]), int(n_of[st]),
                              bounds)
                     for _et, st, dt, dst, src, w in relations]
            bwd_b = [pack_ell(src, dst, w, int(n_of[st]), int(n_of[dt]),
                              bounds)
                     for _et, st, dt, dst, src, w in relations]

        # Tier classification: exact nnz (pack-time count) against the
        # measured crossover, with the table-area guard on top.  An
        # explicit ``tiers`` entry wins — that's how collated buckets stay
        # signature-stable across members.
        tier_of = []
        for i, r in enumerate(relations):
            et, st, dt = r[0], r[1], r[2]
            nnz_i = fwd_b[i].nnz
            if nnz_i < 0:
                nnz_i = int(np.asarray(r[3]).shape[0])
            area = int(n_of[dt]) * int(n_of[st])
            t = "dense" if (nnz_i <= thr and area <= DENSE_TIER_AREA) \
                else "arena"
            if tiers is not None and et in tiers:
                t = tiers[et]
            tier_of.append(t)
            for d in ("fwd", "bwd"):
                _METRICS.set("arena.tier", 1.0 if t == "dense" else 0.0,
                             etype=et, dir=d)
                _METRICS.set("arena.tier_nnz", float(nnz_i), etype=et, dir=d)
                _METRICS.set("arena.tier_threshold", float(thr),
                             etype=et, dir=d)
        arena_idx = [i for i, t in enumerate(tier_of) if t == "arena"]
        dense_idx = [i for i, t in enumerate(tier_of) if t == "dense"]

        ck_f, ck_b = chunk if isinstance(chunk, tuple) else (chunk, chunk)
        if ck_f is None:
            ck_f = pick_chunk_multi([fwd_b[i] for i in arena_idx], row_block)
        if ck_b is None:
            ck_b = pick_chunk_multi([bwd_b[i] for i in arena_idx], row_block)
        fwd_a = [fuse_bucketed(fwd_b[i], row_block, ck_f) for i in arena_idx]
        bwd_a = [fuse_bucketed(bwd_b[i], row_block, ck_b) for i in arena_idx]

        # Dense-tier tables: each relation's exact edge set (straight from
        # its bucketed packing, so zero-weight padding is dropped the same
        # way the arena drops it) scattered into a stacked matrix over the
        # full type-concat source width; bwd is the materialized transpose.
        dense_offs, doff = {}, 0
        for i in dense_idx:
            dense_offs[i] = doff
            doff += int(n_of[relations[i][2]])
        dense_fwd = np.zeros((doff, n_src_total), np.float32)
        for i in dense_idx:
            d, s, wv = ell_to_coo(fwd_b[i])
            np.add.at(dense_fwd,
                      (d + dense_offs[i], s + src_off[relations[i][1]]), wv)
        dense_bwd = np.ascontiguousarray(dense_fwd.T)

    if pad is not None:
        target = pad if callable(pad) else (lambda et, d, _a: pad[et][d])
        fwd_a = [pad_fused_arena(a, *target(relations[i][0], "fwd", a))
                 for a, i in zip(fwd_a, arena_idx)]
        bwd_a = [pad_fused_arena(a, *target(relations[i][0], "bwd", a))
                 for a, i in zip(bwd_a, arena_idx)]

    # Full output concat over ALL relations (y/gy live here, both tiers);
    # the fwd arena's own output space covers arena-tier rows only.
    out_offs = np.cumsum([0] + [int(n_of[r[2]]) for r in relations])
    arena_out_offs = np.cumsum([0] + [a.n_dst for a in fwd_a])
    src_out_offs = np.cumsum([0] + [a.n_dst for a in bwd_a])  # arena dx
    if arena_idx:
        # fwd: sources live in the type-concat slab, outputs in the
        # arena-only concat; bwd: "sources" are the FULL fwd outputs (gy
        # concat — dense-tier rows are simply never referenced), rows are
        # type-concat source ids (the §2 xi gather reads them).
        fwd, f_offs = _concat_arenas(
            fwd_a,
            nbr_offs=[src_off[relations[i][1]] for i in arena_idx],
            rows_offs=[int(o) for o in arena_out_offs[:-1]],
            n_dst=int(arena_out_offs[-1]), n_src=n_src_total)
        bwd, b_offs = _concat_arenas(
            bwd_a,
            nbr_offs=[int(out_offs[i]) for i in arena_idx],
            rows_offs=[int(o) for o in src_out_offs[:-1]],
            n_dst=int(src_out_offs[-1]), n_src=int(out_offs[-1]))
        bwd_src_rows = np.concatenate(
            [np.asarray(a.rows) + np.int32(src_off[relations[i][1]])
             for a, i in zip(bwd_a, arena_idx)])
    else:
        fwd = _empty_super_arena(0, n_src_total, row_block, int(ck_f or 16))
        bwd = _empty_super_arena(0, int(out_offs[-1]), row_block,
                                 int(ck_b or 16))
        bwd_src_rows = np.zeros(row_block, np.int32)
        f_offs = b_offs = []

    segments = []
    a_pos = 0
    for i, (et, st, dt) in enumerate(r[:3] for r in relations):
        if tier_of[i] == "arena":
            fa, ba = fwd_a[a_pos], bwd_a[a_pos]
            (fc, fr), (bc, brr) = f_offs[a_pos], b_offs[a_pos]
            segments.append(RelationSegment(
                etype=et, src_type=st, dst_type=dt,
                n_dst=fa.n_dst, n_src=fa.n_src,
                out_off=int(out_offs[i]),
                src_out_off=int(src_out_offs[a_pos]),
                fwd_chunks=(fc, fc + fa.n_chunks),
                bwd_chunks=(bc, bc + ba.n_chunks),
                fwd_rows=(fr, fr + fa.n_arena_rows),
                bwd_rows=(brr, brr + ba.n_arena_rows),
                tier="arena", dense_off=-1,
                arena_out_off=int(arena_out_offs[a_pos])))
            a_pos += 1
        else:
            segments.append(RelationSegment(
                etype=et, src_type=st, dst_type=dt,
                n_dst=int(n_of[dt]), n_src=int(n_of[st]),
                out_off=int(out_offs[i]), src_out_off=-1,
                fwd_chunks=(0, 0), bwd_chunks=(0, 0),
                fwd_rows=(0, 0), bwd_rows=(0, 0),
                tier="dense", dense_off=int(dense_offs[i]),
                arena_out_off=-1))
    return RelationPlan(fwd=fwd, bwd=bwd, bwd_src_rows=bwd_src_rows,
                        dense_fwd=dense_fwd, dense_bwd=dense_bwd,
                        segments=tuple(segments),
                        src_types=src_types,
                        src_off=tuple(src_off[t] for t in src_types),
                        src_sizes=tuple(int(n_of[t]) for t in src_types))
