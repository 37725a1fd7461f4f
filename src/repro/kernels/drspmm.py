"""Pallas TPU kernels for DR-SpMM (forward) and sampled DR-SpMM (backward).

Forward (Alg. 1):   Y[i, :] += w_ij * scatter(X_vals[j], X_idx[j])   over j∈N(i)
Backward (Alg. 2):  dV[j, t]  += w_ij * dY[i, X_idx[j, t]]           over i∈N(j)

Layout / TPU mapping
--------------------
Every sparse executor here is ONE chunk-walk kernel over an arena of
uniform (BR, Ec) neighbour chunks (DESIGN.md §1):

* **Fused** (default hot path): the :class:`~repro.graphs.ell.FusedELL`
  arena holds ALL degree buckets; a scalar-prefetch metadata table routes
  each chunk's accumulation into its output row-block (grouped-matmul
  revisit pattern — consecutive grid steps hit the same output block, so it
  stays VMEM-resident and no atomics or host-side combines are needed).
* **Per-bucket** (reference): one launch per degree bucket, the bucket's
  (R, E) ELL slab read as an arena of R/BR chunks of width E, one per
  row-block — the paper's dynamic warp partitioning expressed structurally.

Kernel-body idioms, all in a form Mosaic lowers (DESIGN.md §1.4):

* **Row gathers by DMA.**  The gathered operand (the CBSR slab, dY, or a
  dense x) stays in HBM; each grid step reads its chunk's BR·Ec neighbour ids
  from an SMEM block and issues one row DMA per id into a VMEM scratch, all
  in flight at once, then drains them.  No VMEM block grows with the number
  of source rows.  A DMA row must span whole 128-lane tiles, so operands are
  lane-padded first; the CBSR operand travels as one int32 row table
  (value bits | indices | zero padding), one DMA per neighbour.
* **Scatter by compare-select.**  The k CBSR values of a gathered row are
  densified into a (rows, D-tile) slice with one lane-iota compare-select per
  kept position (TPUs have no fast in-kernel scatter).
* **Neighbour sum on the MXU.**  A (BR, BR·Ec) selection matrix carrying the
  chunk's edge weights contracts the gathered rows into the row-block.
* **SSpMM sampling by compare-and-reduce.**  dV[j, t] = g[j, idx[j, t]] is a
  lane-iota compare and a lane reduction per kept position.
* **D-tiling**: when the embedding dim exceeds ``D_TILE`` (128, one MXU
  lane-width) and divides evenly, the forward grid gains a D-tile dimension
  and each step densifies only a (…, D_TILE) slice.
* Accumulation is fp32 in VMEM regardless of input dtype; contractions run
  at ``Precision.HIGHEST`` so the chip agrees with the f32 oracle.

Where the kernels run natively and where they are interpreted is decided
when the enclosing program is lowered for its platform (:func:`run_pallas`),
not by the host the code was traced on.  Validated with the interpreter on
CPU against kernels/ref.py, and compiled for TPU v5e at full-scale shapes
by tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.graphs.ell import ELLBucket, FusedELL, ROW_BLOCK, _round_up

# One MXU lane-width: D-tiling granularity for wide embeddings, and the
# lane-tile width a DMA row must span.
D_TILE = 128
LANES = 128

_HI = jax.lax.Precision.HIGHEST


def run_pallas(call, interpret: bool | None = None):
    """``call(interpret)`` — a ``pallas_call`` closure — natively on TPU and
    interpreted on every other platform.

    The branch is chosen when the enclosing program is lowered
    (``lax.platform_dependent``), from the platform it is lowered for: a jit
    traced on a CPU host and compiled for a described TPU takes the native
    branch, exactly like a run on the chip, and a TPU program never
    interprets.  An explicit ``interpret`` pins the branch."""
    if interpret is not None:
        return call(interpret)
    return jax.lax.platform_dependent(tpu=lambda: call(False),
                                      default=lambda: call(True))


def _d_tiling(dim: int) -> tuple:
    """(tile, n_tiles): tile the D axis at 128 when it divides evenly."""
    if dim > D_TILE and dim % D_TILE == 0:
        return D_TILE, dim // D_TILE
    return dim, 1


def _lane_pad(x: jax.Array) -> jax.Array:
    """(N, W) → (N, W rounded up to whole lane tiles), zero-filled."""
    w = x.shape[1]
    wp = _round_up(max(w, 1), LANES)
    return x if w == wp else jnp.pad(x, ((0, 0), (0, wp - w)))


def _cbsr_rows(x_vals: jax.Array, x_idx: jax.Array) -> jax.Array:
    """CBSR operand as one lane-padded int32 row table: f32 value bits in
    lanes [0, k), column indices in [k, 2k) — one DMA fetches both."""
    bits = jax.lax.bitcast_convert_type(x_vals.astype(jnp.float32), jnp.int32)
    return _lane_pad(jnp.concatenate([bits, x_idx.astype(jnp.int32)], axis=1))


# ---------------------------------------------------------------------------
# kernel-body helpers
# ---------------------------------------------------------------------------

def _gather_rows(src_hbm, ids_ref, rows_ref, sem):
    """rows_ref[r] = src_hbm[ids_ref[0, 0, r]] for every r: one row DMA per
    id, all started before the first wait (same-size copies on one
    semaphore, so any descriptor of that size drains one)."""
    def start(r, carry):
        pltpu.make_async_copy(src_hbm.at[pl.ds(ids_ref[0, 0, r], 1)],
                              rows_ref.at[pl.ds(r, 1)], sem).start()
        return carry

    def wait(r, carry):
        pltpu.make_async_copy(src_hbm.at[pl.ds(0, 1)],
                              rows_ref.at[pl.ds(0, 1)], sem).wait()
        return carry

    n = rows_ref.shape[0]
    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)


def _row_select(w_row, br: int, ec: int):
    """(BR, BR·Ec) matrix with w[b·Ec + e] at (b, b·Ec + e), zero elsewhere:
    contracting it against the chunk's gathered rows is the weighted
    per-destination neighbour sum."""
    n = br * ec
    col = jax.lax.broadcasted_iota(jnp.int32, (br, n), 1)
    lo = jax.lax.broadcasted_iota(jnp.int32, (br, n), 0) * ec
    return jnp.where((col >= lo) & (col < lo + ec),
                     w_row.astype(jnp.float32), 0.0)


def _densify(vals, idx, k: int, d_base, d_tile: int):
    """(R, k) CBSR values/indices → dense (R, d_tile) slice of columns
    [d_base, d_base + d_tile): one compare-select per kept position."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (vals.shape[0], d_tile),
                                    1) + d_base
    out = jnp.zeros((vals.shape[0], d_tile), jnp.float32)
    for t in range(k):
        out = out + jnp.where(idx[:, t:t + 1] == iota, vals[:, t:t + 1], 0.0)
    return out


def _sample(g, idx, k: int):
    """out[r, t] = g[r, idx[r, t]] — the SSpMM gather at each row's own CBSR
    indices, as a lane-iota compare and a lane reduction per position."""
    r, d = g.shape
    iota_d = jax.lax.broadcasted_iota(jnp.int32, (r, d), 1)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (r, k), 1)
    out = jnp.zeros((r, k), jnp.float32)
    for t in range(k):
        col = jnp.sum(jnp.where(iota_d == idx[:, t:t + 1], g, 0.0), axis=1,
                      keepdims=True)
        out = jnp.where(iota_k == t, col, out)
    return out


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=_HI)


# ---------------------------------------------------------------------------
# chunk-walk executors over (block_of, start, nbr, w) arena tables
# ---------------------------------------------------------------------------
#
# Grid = ([D-tiles,] chunks).  Chunks of the same output row-block are
# consecutive in the arena, so the output BlockSpec's scalar-prefetch index
# map (blk[c]) revisits each block in an unbroken run: the block stays
# VMEM-resident across its chunks and is zero-initialized by the chunk whose
# ``start`` flag is set.  See DESIGN.md §1.

def _chunk_specs(r: int, n_lead: int):
    """(ids in SMEM, weights in VMEM) block specs for chunk tables stored
    (C, 1, r); ``n_lead`` grid axes precede the chunk axis."""
    def idx(*a):
        return (a[n_lead], 0, 0)
    return (pl.BlockSpec((1, 1, r), idx, memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, r), idx))


def _chunk_tables(nbr, w):
    c, br, ec = nbr.shape
    return (jnp.reshape(jnp.asarray(nbr, jnp.int32), (c, 1, br * ec)),
            jnp.reshape(jnp.asarray(w), (c, 1, br * ec)))


def _arena_fwd_kernel(blk_ref, st_ref, nbr_ref, w_ref, x_hbm, out_ref,
                      rows_ref, sem, *, k: int, ec: int, d_tile: int):
    c = pl.program_id(1)

    @pl.when(st_ref[c] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _gather_rows(x_hbm, nbr_ref, rows_ref, sem)
    rows = rows_ref[...]                                  # (BR·Ec, lanes)
    vals = jax.lax.bitcast_convert_type(rows[:, :k], jnp.float32)
    xd = _densify(vals, rows[:, k:2 * k], k, pl.program_id(0) * d_tile,
                  d_tile)                                 # (BR·Ec, DT)
    out_ref[...] += _dot(_row_select(w_ref[0], out_ref.shape[0], ec), xd)


def _arena_fwd(block_of, start, nbr, w, x_vals, x_idx, dim: int,
               n_rows: int, interpret):
    """fp32 (n_rows, dim) Y over the arena: CBSR rows gathered by DMA."""
    c, br, ec = nbr.shape
    k = x_vals.shape[1]
    dt, ndt = _d_tiling(dim)
    rows = _cbsr_rows(x_vals, x_idx)
    ids, wts = _chunk_tables(nbr, w)
    ids_spec, w_spec = _chunk_specs(br * ec, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ndt, c),
        in_specs=[ids_spec, w_spec, pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((br, dt), lambda d, i, blk, st: (blk[i], d)),
        scratch_shapes=[pltpu.VMEM((br * ec, rows.shape[1]), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
    )
    return run_pallas(lambda interp: pl.pallas_call(
        functools.partial(_arena_fwd_kernel, k=k, ec=ec, d_tile=dt),
        grid_spec=grid_spec,
        # fp32 accumulator arena regardless of input dtype (chunk revisits
        # accumulate in the out buffer); the op wrapper casts after gather.
        out_shape=jax.ShapeDtypeStruct((n_rows, dim), jnp.float32),
        interpret=interp,
    )(jnp.asarray(block_of), jnp.asarray(start), ids, wts, rows), interpret)


def _arena_bwd_kernel(blk_ref, st_ref, nbr_ref, w_ref, gy_hbm, xi_ref,
                      out_ref, rows_ref, sem, *, k: int, ec: int):
    c = pl.program_id(0)

    @pl.when(st_ref[c] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _gather_rows(gy_hbm, nbr_ref, rows_ref, sem)
    br = out_ref.shape[0]
    # the sampled indices depend on the source row only, so the weighted
    # target sum comes first and the sampling runs once per row
    g = _dot(_row_select(w_ref[0], br, ec), rows_ref[...])   # (BR, Dp)
    out_ref[...] += _sample(g, xi_ref[...], k)


def _arena_bwd(block_of, start, tnbr, tw, gy, xi_rows, n_rows: int,
               interpret):
    """fp32 (n_rows, k) dV over the transposed arena: dY rows by DMA,
    sampled at each arena row's CBSR indices (``xi_rows``, arena order)."""
    c, br, ec = tnbr.shape
    k = xi_rows.shape[1]
    gyp = _lane_pad(gy.astype(jnp.float32))
    ids, wts = _chunk_tables(tnbr, tw)
    ids_spec, w_spec = _chunk_specs(br * ec, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(c,),
        in_specs=[ids_spec, w_spec, pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec((br, k), lambda i, blk, st: (blk[i], 0))],
        out_specs=pl.BlockSpec((br, k), lambda i, blk, st: (blk[i], 0)),
        scratch_shapes=[pltpu.VMEM((br * ec, gyp.shape[1]), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    return run_pallas(lambda interp: pl.pallas_call(
        functools.partial(_arena_bwd_kernel, k=k, ec=ec),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, k), jnp.float32),
        interpret=interp,
    )(jnp.asarray(block_of), jnp.asarray(start), ids, wts, gyp,
      xi_rows.astype(jnp.int32)), interpret)


def _arena_spmm_kernel(blk_ref, st_ref, nbr_ref, w_ref, x_hbm, out_ref,
                       rows_ref, sem, *, ec: int):
    c = pl.program_id(0)

    @pl.when(st_ref[c] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _gather_rows(x_hbm, nbr_ref, rows_ref, sem)
    br, d = out_ref.shape
    g = _dot(_row_select(w_ref[0], br, ec), rows_ref[...])   # (BR, Dp)
    out_ref[...] += g[:, :d]


def _arena_spmm(block_of, start, nbr, w, x, n_rows: int, interpret):
    """fp32 (n_rows, D) dense-operand SpMM over the arena."""
    c, br, ec = nbr.shape
    d = x.shape[1]
    xp = _lane_pad(x.astype(jnp.float32))
    ids, wts = _chunk_tables(nbr, w)
    ids_spec, w_spec = _chunk_specs(br * ec, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(c,),
        in_specs=[ids_spec, w_spec, pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((br, d), lambda i, blk, st: (blk[i], 0)),
        scratch_shapes=[pltpu.VMEM((br * ec, xp.shape[1]), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    return run_pallas(lambda interp: pl.pallas_call(
        functools.partial(_arena_spmm_kernel, ec=ec),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, d), jnp.float32),
        interpret=interp,
    )(jnp.asarray(block_of), jnp.asarray(start), ids, wts, xp), interpret)


def _bucket_arena(bucket: ELLBucket):
    """A degree bucket's (R, E) slab as arena tables: R/BR chunks of width
    E, chunk i opening row-block i."""
    r, e = bucket.nbr.shape
    br = min(ROW_BLOCK, r)
    nb = r // br
    return (jnp.arange(nb, dtype=jnp.int32), jnp.ones((nb,), jnp.int32),
            jnp.reshape(bucket.nbr, (nb, br, e)),
            jnp.reshape(bucket.w, (nb, br, e)))


# ---------------------------------------------------------------------------
# per-bucket executors (reference path): one launch per degree bucket
# ---------------------------------------------------------------------------

def drspmm_fwd_bucket(bucket: ELLBucket, x_vals: jax.Array, x_idx: jax.Array,
                      dim: int, *, interpret: bool | None = None) -> jax.Array:
    """Y_bucket (R, dim) for one degree bucket (rows still bucket-local)."""
    r = bucket.nbr.shape[0]
    return _arena_fwd(*_bucket_arena(bucket), x_vals, x_idx, dim, r,
                      interpret).astype(x_vals.dtype)


def drspmm_bwd_bucket(bucket: ELLBucket, gy: jax.Array, xi_rows: jax.Array,
                      *, interpret: bool | None = None) -> jax.Array:
    """dV_bucket (R, k) for one transposed-ELL bucket.

    ``tnbr``/``tw`` come from the *transposed* ELL packing, so each source
    row j is owned by exactly one grid cell — accumulation is a private VMEM
    reduction, no atomics (DESIGN.md §2).  ``xi_rows`` is x_idx gathered at
    this bucket's source rows, shape (R, k).
    """
    r = bucket.nbr.shape[0]
    return _arena_bwd(*_bucket_arena(bucket), gy, xi_rows, r,
                      interpret).astype(gy.dtype)


def spmm_dense_bucket(bucket: ELLBucket, x: jax.Array,
                      *, interpret: bool | None = None) -> jax.Array:
    """Dense-operand SpMM for one bucket (baseline, cuSPARSE-analogue): the
    same traversal with an (N, D) operand, so benchmarks can compare the CBSR
    gather traffic (N·k) against the dense gather traffic (N·D)."""
    r = bucket.nbr.shape[0]
    return _arena_spmm(*_bucket_arena(bucket), x, r,
                       interpret).astype(x.dtype)


# ---------------------------------------------------------------------------
# fused single-dispatch executors — ONE pallas_call for ALL buckets
# ---------------------------------------------------------------------------

def drspmm_fwd_fused(fused: FusedELL, x_vals: jax.Array, x_idx: jax.Array,
                     dim: int, *, interpret: bool | None = None) -> jax.Array:
    """Arena-ordered Y (R_arena, dim) in ONE kernel launch.

    Read the caller-ordered output with ``jnp.take(y, fused.gather, 0)``.
    """
    return _arena_fwd(fused.block_of, fused.start, fused.nbr, fused.w,
                      x_vals, x_idx, dim, fused.n_arena_rows, interpret)


def drspmm_bwd_fused(fused_t: FusedELL, gy: jax.Array, xi_arena: jax.Array,
                     *, interpret: bool | None = None) -> jax.Array:
    """Arena-ordered dV (R_arena, k) in ONE kernel launch.

    ``fused_t`` is the fused *transposed* packing; ``xi_arena`` is x_idx
    gathered at ``fused_t.rows`` (arena source order), shape (R_arena, k).
    """
    return _arena_bwd(fused_t.block_of, fused_t.start, fused_t.nbr,
                      fused_t.w, gy, xi_arena, fused_t.n_arena_rows,
                      interpret)


def spmm_dense_fused(fused: FusedELL, x: jax.Array,
                     *, interpret: bool | None = None) -> jax.Array:
    """Dense-operand SpMM over the fused arena — ONE kernel launch."""
    return _arena_spmm(fused.block_of, fused.start, fused.nbr, fused.w, x,
                       fused.n_arena_rows, interpret)


# ---------------------------------------------------------------------------
# relation-fused super-arena executors — ONE pallas_call for a hetero layer's
# WHOLE direction-group (every edge-type direction at once, DESIGN.md §9).
#
# A RelationPlan (graphs/ell.py) bakes the relation routing into the §1
# metadata: `nbr` is pre-offset into the type-concat source slab, `block_of`
# spans the per-relation chunk segments, and the output rows are the
# relation-concat arena.  The kernel bodies above therefore run UNCHANGED —
# relation selection costs zero in-kernel work; what these wrappers add is
# the super-arena contract (a `rel` chunk table must be present) and, for
# the backward, the arena-ordered xi gather at the plan's type-concat source
# row map.
# ---------------------------------------------------------------------------

def drspmm_fwd_multi(super_fwd: FusedELL, x_vals: jax.Array,
                     x_idx: jax.Array, dim: int,
                     *, interpret: bool | None = None) -> jax.Array:
    """Arena-ordered Y for ALL relations of a direction-group in ONE
    ``pallas_call``.

    ``x_vals``/``x_idx`` are the type-concat CBSR operands (every source
    node type stacked, k padded to the group max); read the relation-concat
    output with ``jnp.take(y, super_fwd.gather, 0)`` and slice per relation
    at the plan's ``out_off`` offsets.
    """
    assert super_fwd.rel is not None, \
        "drspmm_fwd_multi needs a relation-fused super-arena (RelationPlan)"
    return drspmm_fwd_fused(super_fwd, x_vals, x_idx, dim,
                            interpret=interpret)


def drspmm_bwd_multi(super_bwd: FusedELL, bwd_src_rows: jax.Array,
                     gy_cat: jax.Array, x_idx: jax.Array,
                     *, interpret: bool | None = None) -> jax.Array:
    """Arena-ordered dV for ALL relations in ONE transposed ``pallas_call``.

    ``gy_cat`` is the concatenated per-relation output cotangent (the
    forward's relation-concat order); ``bwd_src_rows`` maps bwd arena rows
    to type-concat source ids, so the §2 sampled backward reads each arena
    row's own CBSR indices out of the type-concat ``x_idx``.  Read the
    relation-concat dV with ``jnp.take(dv, super_bwd.gather, 0)`` and sum
    segments per source type (a node type feeding several relations — cell
    feeds both ``near`` and ``pin`` — accumulates across its segments).
    """
    assert super_bwd.rel is not None, \
        "drspmm_bwd_multi needs a relation-fused super-arena (RelationPlan)"
    xi_arena = jnp.take(x_idx, jnp.asarray(bwd_src_rows), axis=0)
    return drspmm_bwd_fused(super_bwd, gy_cat, xi_arena, interpret=interpret)


# ---------------------------------------------------------------------------
# dense-tier executors — tiny relations (nnz ≤ DENSE_TIER_NNZ, graphs/ell.py)
# skip the chunk-walk arena entirely: the plan materializes the relation
# stack as ONE dense matrix and the whole tier runs as a single tiled
# matmul (fwd) / single transposed matmul + in-kernel CBSR sampling (bwd).
# Same custom-vjp contract as the arena path: grad flows to x_vals only,
# sampled at x_idx (SSpMM).  Both grids tile the source axis as well as the
# output rows, so no block grows with the type-concat slab.  DESIGN.md §14.
# ---------------------------------------------------------------------------

DENSE_TIER_ROW_BLOCK = 256    # output rows per grid step (fwd M / bwd N)
DENSE_TIER_SRC_CHUNK = 512    # contraction columns per grid step


def _dense_tier_fwd_kernel(a_ref, xv_ref, xi_ref, out_ref, *, k: int,
                           d_tile: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xd = _densify(xv_ref[...].astype(jnp.float32), xi_ref[...], k,
                  pl.program_id(0) * d_tile, d_tile)      # (NC, DT)
    out_ref[...] += _dot(a_ref[...].astype(jnp.float32), xd)


def _tiles(n: int, cap: int, align: int) -> tuple:
    """(block, padded extent) for an axis of size n: blocks of at most
    ``cap``, aligned to ``align``."""
    block = min(cap, _round_up(max(n, 1), align))
    return block, _round_up(max(n, 1), block)


def drspmm_dense_tier_fwd(a_dense: jax.Array, x_vals: jax.Array,
                          x_idx: jax.Array, dim: int,
                          *, interpret: bool | None = None) -> jax.Array:
    """Y = A·dense(CBSR(x)) for a dense-tier relation stack in ONE launch.

    ``a_dense`` is the (M, N) dense relation matrix (the plan's
    ``dense_fwd``); the CBSR operand is scatter-densified in-kernel one
    source chunk at a time, so no (N, dim) intermediate is ever materialized
    in HBM.  Returns fp32 (M, dim); the op wrapper casts.
    """
    m, n = a_dense.shape
    k = x_vals.shape[1]
    if m == 0 or n == 0:
        return jnp.zeros((m, dim), jnp.float32)
    rb, mp = _tiles(m, DENSE_TIER_ROW_BLOCK, 8)
    nc, npad = _tiles(n, DENSE_TIER_SRC_CHUNK, LANES)
    # zero pads are inert: padded x_idx rows point at column 0 but carry
    # zero values, padded A rows/columns are zero
    a_p = jnp.pad(a_dense, ((0, mp - m), (0, npad - n)))
    xv_p = jnp.pad(x_vals, ((0, npad - n), (0, 0)))
    xi_p = jnp.pad(x_idx.astype(jnp.int32), ((0, npad - n), (0, 0)))
    dt, ndt = _d_tiling(dim)
    y = run_pallas(lambda interp: pl.pallas_call(
        functools.partial(_dense_tier_fwd_kernel, k=k, d_tile=dt),
        grid=(ndt, mp // rb, npad // nc),
        in_specs=[
            pl.BlockSpec((rb, nc), lambda d, i, j: (i, j)),
            pl.BlockSpec((nc, k), lambda d, i, j: (j, 0)),
            pl.BlockSpec((nc, k), lambda d, i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((rb, dt), lambda d, i, j: (i, d)),
        out_shape=jax.ShapeDtypeStruct((mp, dim), jnp.float32),
        interpret=interp,
    )(a_p, xv_p, xi_p), interpret)
    return y[:m]


def _dense_tier_bwd_kernel(at_ref, gy_ref, xi_ref, out_ref, acc_ref, *,
                           k: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(at_ref[...].astype(jnp.float32),
                         gy_ref[...].astype(jnp.float32))

    @pl.when(j == pl.num_programs(1) - 1)
    def _sample_out():
        out_ref[...] = _sample(acc_ref[...], xi_ref[...], k)


def drspmm_dense_tier_bwd(a_dense_t: jax.Array, gy: jax.Array,
                          x_idx: jax.Array,
                          *, interpret: bool | None = None) -> jax.Array:
    """dV = sample(Aᵀ·gY, x_idx) for the dense tier in ONE launch.

    ``a_dense_t`` is the transposed relation matrix (the plan's
    ``dense_bwd``, (N, M)); the (rows, dim) dense cotangent accumulates in a
    VMEM scratch over M chunks and is sampled in-kernel at each source row's
    own CBSR indices, so it never leaves VMEM.  Returns fp32 (N, k).
    """
    n, m = a_dense_t.shape
    k = x_idx.shape[1]
    if n == 0 or m == 0:
        return jnp.zeros((n, k), jnp.float32)
    d = gy.shape[1]
    rb, npad = _tiles(n, DENSE_TIER_ROW_BLOCK, 8)
    mc, mpad = _tiles(m, DENSE_TIER_SRC_CHUNK, LANES)
    at_p = jnp.pad(a_dense_t, ((0, npad - n), (0, mpad - m)))
    gy_p = jnp.pad(gy, ((0, mpad - m), (0, 0)))
    xi_p = jnp.pad(x_idx.astype(jnp.int32), ((0, npad - n), (0, 0)))
    dv = run_pallas(lambda interp: pl.pallas_call(
        functools.partial(_dense_tier_bwd_kernel, k=k),
        grid=(npad // rb, mpad // mc),
        in_specs=[
            pl.BlockSpec((rb, mc), lambda i, j: (i, j)),
            pl.BlockSpec((mc, d), lambda i, j: (j, 0)),
            pl.BlockSpec((rb, k), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rb, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((npad, k), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rb, d), jnp.float32)],
        interpret=interp,
    )(at_p, gy_p, xi_p), interpret)
    return dv[:n]


# ---------------------------------------------------------------------------
# fused learnable-edge executors — Y = A(w)·dense(CBSR(x)) with the weight
# vector w (nnz,) gathered into arena order from the eid table (one XLA
# gather of C·BR·Ec weights), then the fixed-weight chunk walk above: the
# differentiable-edge path (kernels/ops.py::drspmm_learnable) is the same
# single dispatch per direction as the fixed-weight path.  DESIGN.md §8.
# ---------------------------------------------------------------------------

def _slot_weights(eid, nnz: int, w_canon: jax.Array) -> jax.Array:
    """(C, BR, Ec) arena weights from the canonical vector; −1-padded eids
    read an appended zero, so padding stays inert."""
    wp = jnp.concatenate([w_canon.astype(jnp.float32),
                          jnp.zeros((1,), jnp.float32)])
    eid = jnp.asarray(eid)
    return jnp.take(wp, jnp.where(eid < 0, nnz, eid), axis=0)


def drspmm_fwd_learnable_fused(fused: FusedELL, nnz: int,
                               w_canon: jax.Array, x_vals: jax.Array,
                               x_idx: jax.Array, dim: int,
                               *, interpret: bool | None = None) -> jax.Array:
    """Arena-ordered Y = A(w)·dense(CBSR(x)) in ONE kernel launch.

    ``fused`` must carry an eid arena (``fuse_bucketed(..., eids=True)``).
    Read the caller-ordered output with ``jnp.take(y, fused.gather, 0)``.
    """
    assert fused.eid is not None, "learnable executor needs an eid arena"
    return _arena_fwd(fused.block_of, fused.start, fused.nbr,
                      _slot_weights(fused.eid, nnz, w_canon), x_vals, x_idx,
                      dim, fused.n_arena_rows, interpret)


def drspmm_bwd_learnable_fused(fused_t: FusedELL, nnz: int,
                               w_canon: jax.Array, gy: jax.Array,
                               xi_arena: jax.Array,
                               *, interpret: bool | None = None) -> jax.Array:
    """Arena-ordered dL/dx_vals (R_arena, k) in ONE kernel launch — the
    transposed sampled backward over the same arena-order weights."""
    assert fused_t.eid is not None, "learnable executor needs an eid arena"
    return _arena_bwd(fused_t.block_of, fused_t.start, fused_t.nbr,
                      _slot_weights(fused_t.eid, nnz, w_canon), gy,
                      xi_arena, fused_t.n_arena_rows, interpret)


def _arena_dw_kernel(blk_ref, nbr_ref, x_hbm, gy_ref, out_ref, rows_ref, sem,
                     *, k: int, ec: int):
    """Per-slot sampled dot: out[0, 0, b·Ec + e] = Σ_t dY[row_b, idx[n, t]] ·
    vals[n, t] with n = nbr[b, e].  Same gather as the forward with the roles
    of weight and value swapped (kernels/learnable.py); the scatter of slot
    contributions into canonical w order happens OUTSIDE the kernel (one XLA
    scatter — TPUs have no fast in-kernel scatter)."""
    _gather_rows(x_hbm, nbr_ref, rows_ref, sem)
    rows = rows_ref[...]
    gy = gy_ref[...].astype(jnp.float32)              # (BR, D) chunk dY rows
    br, d = gy.shape
    vals = jax.lax.bitcast_convert_type(rows[:, :k], jnp.float32)
    xd = _densify(vals, rows[:, k:2 * k], k, 0, d)    # (BR·Ec, D)
    # broadcast each destination's dY row to its Ec slots (0/1 selection)
    gy_slots = jax.lax.dot_general(
        _row_select(jnp.ones((1, br * ec), jnp.float32), br, ec), gy,
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_HI)                                 # (BR·Ec, D)
    # slot sums as a lane-major row: ones (1, D) · (xd ∘ gy_slots)ᵀ
    out_ref[0] = jax.lax.dot_general(
        jnp.ones((1, d), jnp.float32), xd * gy_slots,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_HI)


def drspmm_dw_learnable_fused(fused: FusedELL, gy_arena: jax.Array,
                              x_vals: jax.Array, x_idx: jax.Array,
                              *, interpret: bool | None = None) -> jax.Array:
    """Per-arena-slot dL/dw contributions (C, BR, Ec) in ONE kernel launch.

    ``gy_arena`` is dY gathered at ``fused.rows`` (arena destination order).
    The caller reduces to canonical order with one scatter-add over the eid
    table: ``zeros(nnz+1).at[where(eid<0, nnz, eid)].add(contrib)[:nnz]``.
    """
    assert fused.eid is not None, "learnable executor needs an eid arena"
    c, br, ec = fused.nbr.shape
    k = x_vals.shape[1]
    d = gy_arena.shape[1]
    rows = _cbsr_rows(x_vals, x_idx)
    ids, _ = _chunk_tables(fused.nbr, fused.w)

    def idx(i, blk):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(c,),
        in_specs=[pl.BlockSpec((1, 1, br * ec), idx,
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec((br, d), lambda i, blk: (blk[i], 0))],
        out_specs=pl.BlockSpec((1, 1, br * ec), idx),
        scratch_shapes=[pltpu.VMEM((br * ec, rows.shape[1]), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = run_pallas(lambda interp: pl.pallas_call(
        functools.partial(_arena_dw_kernel, k=k, ec=ec),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c, 1, br * ec), jnp.float32),
        interpret=interp,
    )(jnp.asarray(fused.block_of), ids, rows, gy_arena), interpret)
    return out.reshape(c, br, ec)
