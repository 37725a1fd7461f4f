"""Jit-ready wrappers over the DR-SpMM Pallas kernels.

``drspmm`` is the public op: Y = A · dense(CBSR(x_vals, x_idx)), with a
custom VJP that runs the sampled backward kernel (SSpMM) over the transposed
packing, exactly as Alg. 2 reuses the forward's CBSR indices.

Every op runs over the fused arena layout (:class:`FusedELL`, DESIGN.md §1)
on one of two executors, named by ``backend``:

  * "pallas_fused" — the Pallas arena kernels: ONE dispatch per edge-type
                 direction, all degree buckets in a single kernel, and the
                 combine a single gather.  Default on TPU.
  * "xla_fused"  — the same arena tables walked in plain jnp (gather + one
                 segment-sum).  Default on CPU, where Pallas only
                 interprets.

The oracle both are tested against is kernels/ref.py: plain dense math that
no op here dispatches to.

A concrete :class:`BucketedELL` argument is fused host-side on first use
(``fuse_bucketed``, memoized per packing).  Inside a trace the ops take only
pre-fused adjacencies or a :class:`RelationPlan`: fusing is host packing, so
a traced ``BucketedELL`` raises ``TypeError``.

``drspmm_multi`` lifts the same contract one level: every edge-type
direction of a hetero layer runs over a :class:`RelationPlan` super-arena
as ONE dispatch per direction-group — one forward, one transposed backward
— instead of one per relation (DESIGN.md §9).  Execution is size-adaptive
(DESIGN.md §14): relations the plan classified as dense-tier at pack time
(nnz below the measured crossover) skip the chunk walk and run together as
at most one extra batched dense matmul per direction; ``drspmm`` applies
the same crossover to single tiny relations.
"""

from __future__ import annotations

import dataclasses
import weakref
from collections import OrderedDict, deque
from typing import Literal, get_args

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.ell import (DENSE_TIER_AREA, DENSE_TIER_NNZ, FusedELL,
                              RelationPlan, ell_to_coo, fuse_bucketed,
                              fused_to_coo)
from repro.kernels import drspmm as _k
from repro.obs.metrics import DEFAULT_REGISTRY as _METRICS

Backend = Literal["pallas_fused", "xla_fused"]
BACKENDS = get_args(Backend)
# The Pallas kernels are the hot path on real hardware; on CPU they only run
# in interpret mode (not wall-clock-representative), so the same arena
# layout executed in plain XLA is the default there.
DEFAULT_BACKEND: Backend = (
    "pallas_fused" if jax.default_backend() == "tpu" else "xla_fused")

# Trace-time dispatch log: every executor issue appends a "family:kind" tag
# ("pallas:fwd", "xla:multi_bwd", ...) while its op body runs (i.e. while
# TRACING under jit — compiled replays don't re-run Python, so count deltas
# around an explicit trace such as ``jax.make_jaxpr``).  This is how tests
# assert the one-dispatch-per-direction-group property for the xla family,
# where jaxpr ``pallas_call`` counting has nothing to count.  Bounded: a
# long-lived serve loop retraces per (bucket, device) compile and eviction
# return, and nothing outside tests ever drains the log.
FUSED_DISPATCH_LOG: "deque[str]" = deque(maxlen=4096)


def _record_dispatch(tag: str) -> None:
    FUSED_DISPATCH_LOG.append(tag)
    # Generalized per-executor dispatch counters (DESIGN.md §11): the same
    # trace-time semantics as the log, but labeled, unbounded-total, and
    # exportable — ``ops.dispatch{family=...,kind=...}`` in the default
    # metrics registry.  The deque stays the test-facing drainable probe.
    family, _, kind = tag.partition(":")
    _METRICS.inc("ops.dispatch", family=family, kind=kind)


def _check_backend(backend) -> None:
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")


def _family(backend: Backend) -> str:
    """Dispatch-tag family of an executor: "pallas" or "xla"."""
    return backend.removesuffix("_fused")


def _fused_of(adj, *, eids: bool = False) -> FusedELL:
    """The arena of ``adj``: itself when pre-fused, else its memoized
    host-side fusing (``eids``: the edge-id arena the learnable op needs).
    Fusing needs concrete arrays, so a traced BucketedELL is refused."""
    if isinstance(adj, FusedELL):
        if eids and adj.eid is None:
            raise ValueError(
                "the learnable op needs an eid arena "
                "(fuse_bucketed(..., eids=True) / pack_fused_eid_pair)")
        return adj
    if any(isinstance(b.nbr, jax.core.Tracer) for b in adj.buckets):
        raise TypeError(
            "a BucketedELL cannot be fused inside a trace: fuse it "
            "host-side (graphs.ell.fuse_bucketed) and pass the FusedELL, "
            "or attach a RelationPlan")
    return fuse_bucketed(adj, eids=eids)


# ----- fused arena executed in plain XLA (CPU hot path; same layout the
# ----- Pallas fused kernels consume, so the adaptive chunk packing's
# ----- ~2× slot reduction and the scatter-free combine carry over) -------

def _arena_rows(f: FusedELL):
    """(C, BR) arena row id of each chunk slot row."""
    return (jnp.asarray(f.block_of)[:, None] * f.row_block
            + jnp.arange(f.row_block, dtype=jnp.int32)[None, :])


def _fwd_fused_xla(f: FusedELL, x_vals, x_idx, dim: int):
    nbr = jnp.asarray(f.nbr)                          # (C, BR, Ec)
    w = jnp.asarray(f.w)
    v = jnp.take(x_vals, nbr, axis=0)                 # (C, BR, Ec, k)
    cols = jnp.take(x_idx, nbr, axis=0)
    vw = v * w[..., None]
    rows = _arena_rows(f)                             # (C, BR)
    y = jnp.zeros((f.n_arena_rows, dim), x_vals.dtype)
    y = y.at[jnp.broadcast_to(rows[:, :, None, None], cols.shape),
             cols].add(vw)
    return jnp.take(y, jnp.asarray(f.gather), axis=0)


def _bwd_fused_xla(ft: FusedELL, gy, x_idx, rows=None):
    """``rows`` overrides the arena-row → operand-row map used for the xi
    gather (default ``ft.rows``); the super-arena backward passes the
    plan's type-concat map (``RelationPlan.bwd_src_rows`` — ``ft.rows``
    live in the relation-concat dx space there)."""
    tnbr = jnp.asarray(ft.nbr)                        # (C, BR, Ec) targets
    tw = jnp.asarray(ft.w)
    k = x_idx.shape[1]
    g = jnp.take(gy, tnbr, axis=0)                    # (C, BR, Ec, D)
    xi_arena = jnp.take(
        x_idx, jnp.asarray(ft.rows if rows is None else rows),
        axis=0)                                       # (R_arena, k)
    xi_blocks = jnp.take(xi_arena, _arena_rows(ft), axis=0)   # (C, BR, k)
    sampled = jnp.take_along_axis(
        g, jnp.broadcast_to(xi_blocks[:, :, None, :], g.shape[:3] + (k,)),
        axis=3)                                       # (C, BR, Ec, k) — SSpMM
    contrib = jnp.sum(sampled * tw[..., None], axis=2)         # (C, BR, k)
    n_blocks = ft.n_arena_rows // ft.row_block
    dv = jax.ops.segment_sum(contrib, jnp.asarray(ft.block_of),
                             num_segments=n_blocks)
    dv = dv.reshape(ft.n_arena_rows, k)
    return jnp.take(dv, jnp.asarray(ft.gather), axis=0)


def _spmm_fused_xla(f: FusedELL, x):
    nbr = jnp.asarray(f.nbr)
    w = jnp.asarray(f.w)
    rows_x = jnp.take(x, nbr, axis=0)                 # (C, BR, Ec, D)
    contrib = jnp.sum(rows_x * w[..., None], axis=2)  # (C, BR, D)
    n_blocks = f.n_arena_rows // f.row_block
    y = jax.ops.segment_sum(contrib, jnp.asarray(f.block_of),
                            num_segments=n_blocks)
    y = y.reshape(f.n_arena_rows, x.shape[1])
    return jnp.take(y, jnp.asarray(f.gather), axis=0)


def _fwd_impl(f: FusedELL, x_vals, x_idx, dim: int, backend: Backend):
    _record_dispatch(f"{_family(backend)}:fwd")
    if backend == "pallas_fused":
        ya = _k.drspmm_fwd_fused(f, x_vals, x_idx, dim)   # fp32 arena
        return jnp.take(ya, f.gather, axis=0).astype(x_vals.dtype)
    return _fwd_fused_xla(f, x_vals, x_idx, dim)


def _bwd_impl(ft: FusedELL, gy, x_idx, backend: Backend):
    _record_dispatch(f"{_family(backend)}:bwd")
    if backend == "pallas_fused":
        xi_arena = jnp.take(x_idx, ft.rows, axis=0)   # (R_arena, k)
        ga = _k.drspmm_bwd_fused(ft, gy, xi_arena)    # fp32 arena
        return jnp.take(ga, ft.gather, axis=0).astype(gy.dtype)
    return _bwd_fused_xla(ft, gy, x_idx)


# ----- dense fast-path tier for tiny single relations ----------------------
#
# The chunk-walk arena LOSES on tiny relations (``pin``/``pinned`` at
# nnz≈2k ran 0.53–0.65x of a per-bucket walk on CPU): below the measured
# crossover (graphs/ell.py::DENSE_TIER_NNZ) the whole relation is ONE
# masked dense matmul — still a single dispatch, same custom-vjp contract
# (sampled backward at x_idx).  A collated arena (nnz == −1: padded filler,
# bucket-stable shape signature) never reroutes — tier decisions for
# collation are pinned at pack time by the plan (graphs/collate.py).

_DENSE_MAT_CACHE: "dict[int, tuple]" = {}


def _dense_mat_of(adj) -> np.ndarray:
    """Host-side (n_dst, n_src) dense matrix of a concrete packing,
    memoized per packing identity (same discipline as ``_FUSE_CACHE``)."""
    key = id(adj)
    hit = _DENSE_MAT_CACHE.get(key)
    if hit is not None and hit[0]() is adj:
        return hit[1]
    d, s, w = (fused_to_coo(adj) if isinstance(adj, FusedELL)
               else ell_to_coo(adj))
    a = np.zeros((adj.n_dst, adj.n_src), np.float32)
    np.add.at(a, (d, s), w)
    _DENSE_MAT_CACHE[key] = (
        weakref.ref(adj, lambda _, k=key: _DENSE_MAT_CACHE.pop(k, None)), a)
    return a


def _dense_tier_single(adj) -> bool:
    """True when a single-relation call should take the dense-tier fast
    path: concrete packing, known sub-threshold nnz, and a dense table small
    enough to be worth materializing."""
    leaf = adj.nbr if isinstance(adj, FusedELL) else adj.buckets[0].nbr
    if isinstance(leaf, jax.core.Tracer):
        return False
    return (adj.nnz >= 0 and adj.nnz <= DENSE_TIER_NNZ
            and adj.n_dst * adj.n_src <= DENSE_TIER_AREA)


def _drspmm_dense_single(adj, adj_t, x_vals, x_idx, dim: int,
                         backend: Backend) -> jax.Array:
    family = _family(backend)
    a = jnp.asarray(_dense_mat_of(adj))
    at = jnp.asarray(_dense_mat_of(adj_t))

    @jax.custom_vjp
    def f(xv):
        _record_dispatch(f"{family}:dense_fwd")
        if backend == "pallas_fused":
            return _k.drspmm_dense_tier_fwd(a, xv, x_idx,
                                            dim).astype(xv.dtype)
        n = xv.shape[0]
        xd = jnp.zeros((n, dim), jnp.float32).at[
            jnp.arange(n)[:, None], x_idx].add(xv.astype(jnp.float32))
        return (a @ xd).astype(xv.dtype)

    def f_fwd(xv):
        return f(xv), None

    def f_bwd(_, gy):
        _record_dispatch(f"{family}:dense_bwd")
        if backend == "pallas_fused":
            dv = _k.drspmm_dense_tier_bwd(at, gy, x_idx)
        else:
            dx = at @ gy.astype(jnp.float32)
            dv = jnp.take_along_axis(dx, x_idx, axis=1)
        return (dv.astype(gy.dtype),)

    f.defvjp(f_fwd, f_bwd)
    return f(x_vals)


def drspmm(adj, adj_t, x_vals: jax.Array, x_idx: jax.Array, dim: int, *,
           backend: Backend = DEFAULT_BACKEND) -> jax.Array:
    """Differentiable DR-SpMM over ``adj`` (A) and ``adj_t`` (Aᵀ), each a
    :class:`FusedELL` or a concrete :class:`BucketedELL`.  Gradient flows
    to ``x_vals`` only; the adjacency and the CBSR indices are structural.

    Size-adaptive: a concrete relation whose nnz sits below the measured
    dense crossover (``graphs/ell.py::DENSE_TIER_NNZ``) routes to the
    dense-tier executor — one masked dense matmul forward, one transposed
    matmul + SSpMM sampling backward — instead of walking the arena
    (DESIGN.md §14)."""
    _check_backend(backend)
    if _dense_tier_single(adj):
        return _drspmm_dense_single(adj, adj_t, x_vals, x_idx, dim, backend)

    # The arenas and the CBSR indices ride through the custom_vjp as
    # primals, not closure constants: inside a checkpointed layer they are
    # checkpoint-scope tracers, which a closure would leak into ``f_bwd``
    # (the same reason as ``_multi_traced``).
    @jax.custom_vjp
    def f(a, at, xv, xi):
        return _fwd_impl(a, xv, xi, dim, backend)

    def f_fwd(a, at, xv, xi):
        return f(a, at, xv, xi), (a, at, xi)

    def f_bwd(res, gy):
        a, at, xi = res
        return (_zero_cotangent(a), _zero_cotangent(at),
                _bwd_impl(at, gy, xi, backend), _zero_cotangent(xi))

    f.defvjp(f_fwd, f_bwd)
    return f(_fused_of(adj), _fused_of(adj_t), x_vals, x_idx)


def spmm(adj, adj_t, x: jax.Array, *,
         backend: Backend = DEFAULT_BACKEND) -> jax.Array:
    """Dense-operand SpMM baseline with full (not sampled) backward."""
    _check_backend(backend)

    # arenas as primals, as in :func:`drspmm`
    @jax.custom_vjp
    def f(a, at, xd):
        return _spmm_fwd(a, xd, backend)

    def f_fwd(a, at, xd):
        return f(a, at, xd), (a, at)

    def f_bwd(res, gy):
        a, at = res
        return (_zero_cotangent(a), _zero_cotangent(at),
                _spmm_fwd(at, gy, backend))

    f.defvjp(f_fwd, f_bwd)
    return f(_fused_of(adj), _fused_of(adj_t), x)


def _spmm_fwd(adj, x, backend: Backend):
    f = _fused_of(adj)
    _record_dispatch(f"{_family(backend)}:spmm")
    if backend == "pallas_fused":
        ya = _k.spmm_dense_fused(f, x)                # fp32 arena
        return jnp.take(ya, f.gather, axis=0).astype(x.dtype)
    return _spmm_fused_xla(f, x)


# ---------------------------------------------------------------------------
# drspmm_learnable — differentiable per-edge weights over the same arena
# (DESIGN.md §8).  The packing is an edge-ID arena (``fuse_bucketed(...,
# eids=True)`` of pack_eid_slabs slabs, or pack_fused_eid_pair): the
# canonical weight vector w (nnz,) is gathered into arena layout at
# execution time, so Y = A(w)·dense(CBSR(x)) has gradients in BOTH w and
# x_vals with the fixed-weight path's one dispatch per direction.
# ---------------------------------------------------------------------------

def _wpad(w_canon):
    """Append the inert slot padded eids (→ index nnz) gather from."""
    return jnp.concatenate([w_canon, jnp.zeros((1,), w_canon.dtype)])


def _safe_eids(eid, nnz: int):
    return jnp.where(jnp.asarray(eid) < 0, nnz, jnp.asarray(eid))


# ----- fused arena in plain XLA (CPU hot path): the fixed-weight walks over
# ----- the arena's slot weights -------------------------------------------

def _slot_weighted(f: FusedELL, nnz, w) -> FusedELL:
    """``f`` with its weights the canonical ``w`` gathered per slot."""
    return dataclasses.replace(f, w=_wpad(w)[_safe_eids(f.eid, nnz)])


def _dw_contrib_to_canon(f: FusedELL, nnz, contrib):
    """Reduce per-arena-slot contributions (C, BR, Ec) to canonical order:
    one scatter-add over the eid table; padding (−1 → slot nnz) dropped."""
    gw = jnp.zeros((nnz + 1,), contrib.dtype)
    gw = gw.at[_safe_eids(f.eid, nnz).reshape(-1)].add(contrib.reshape(-1))
    return gw[:nnz]


def _dw_learnable_fused_xla(f: FusedELL, nnz, gy, xv, xi):
    nbr = jnp.asarray(f.nbr)
    v = jnp.take(xv, nbr, axis=0)                     # (C, BR, Ec, k)
    cols = jnp.take(xi, nbr, axis=0)
    gy_arena = jnp.take(gy, jnp.asarray(f.rows), axis=0)       # (R_arena, D)
    gy_blocks = jnp.take(gy_arena, _arena_rows(f), axis=0)     # (C, BR, D)
    g = jnp.broadcast_to(gy_blocks[:, :, None, :],
                         cols.shape[:3] + (gy.shape[1],))
    sampled = jnp.take_along_axis(g, cols, axis=3)    # (C, BR, Ec, k)
    contrib = jnp.sum(sampled * v, axis=-1)           # (C, BR, Ec)
    return _dw_contrib_to_canon(f, nnz, contrib)


# ----- executor dispatch ---------------------------------------------------

def _learnable_fwd_impl(f: FusedELL, nnz, w, xv, xi, dim, backend: Backend):
    if backend == "pallas_fused":
        ya = _k.drspmm_fwd_learnable_fused(f, nnz, w, xv, xi, dim)
        return jnp.take(ya, f.gather, axis=0).astype(xv.dtype)
    return _fwd_fused_xla(_slot_weighted(f, nnz, w), xv, xi, dim)


def _learnable_dx_impl(ft: FusedELL, nnz, w, gy, xi, backend: Backend):
    if backend == "pallas_fused":
        xi_arena = jnp.take(xi, jnp.asarray(ft.rows), axis=0)
        ga = _k.drspmm_bwd_learnable_fused(ft, nnz, w, gy, xi_arena)
        return jnp.take(ga, ft.gather, axis=0).astype(gy.dtype)
    return _bwd_fused_xla(_slot_weighted(ft, nnz, w), gy, xi)


def _learnable_dw_impl(f: FusedELL, nnz, gy, xv, xi, backend: Backend):
    if backend == "pallas_fused":
        gy_arena = jnp.take(gy, jnp.asarray(f.rows), axis=0)
        contrib = _k.drspmm_dw_learnable_fused(f, gy_arena, xv, xi)
        return _dw_contrib_to_canon(f, nnz, contrib)
    return _dw_learnable_fused_xla(f, nnz, gy, xv, xi)


# The executor — custom-vjp wrapper + jit — is built ONCE per
# (packing pair, nnz, dim, backend) and memoized.  The seed defined the
# custom_vjp wrapper inside the op body, so every call built a fresh
# closure and defeated jit/trace caching — the same class of bug
# core/parallel.py's executable memo fixed for the scheduler
# (tests/test_learnable_edges.py has the cache-hit regression).
#
# Entries hold the packings STRONGLY (the jitted closure pins them anyway,
# so a weakref-eviction scheme like ``_FUSE_CACHE``'s could never fire),
# which also makes the id keys collision-free while an entry lives; the
# table is LRU-bounded instead so a long-lived serve loop over many
# collated packings cannot grow it without bound.
_LEARNABLE_EXE: "OrderedDict[tuple, tuple]" = OrderedDict()
_LEARNABLE_EXE_MAX = 64
# Trace probe: appended to each time an executor's forward is TRACED (the
# body runs only while tracing).  Repeated same-shape calls must not grow it.
_LEARNABLE_TRACES: list = []


def _learnable_vjp(fwdp: FusedELL, bwdp: FusedELL, nnz: int, dim: int,
                   backend: Backend, key):
    """The custom-vjp op over one arena pair (``key`` tags its traces)."""

    @jax.custom_vjp
    def f(w, xv, xi):
        _LEARNABLE_TRACES.append(key)
        return _learnable_fwd_impl(fwdp, nnz, w, xv, xi, dim, backend)

    def f_fwd(w, xv, xi):
        return f(w, xv, xi), (w, xv, xi)

    def f_bwd(res, gy):
        w, xv, xi = res
        gw = _learnable_dw_impl(fwdp, nnz, gy, xv, xi, backend)
        gx = _learnable_dx_impl(bwdp, nnz, w, gy, xi, backend)
        # xi is structural (integer): float0 cotangent
        return gw, gx, np.zeros(xi.shape, jax.dtypes.float0)

    f.defvjp(f_fwd, f_bwd)
    return f


def _learnable_executable(fwdp: FusedELL, bwdp: FusedELL, nnz: int,
                          dim: int, backend: Backend):
    key = (id(fwdp), id(bwdp), nnz, dim, backend)
    hit = _LEARNABLE_EXE.get(key)
    if hit is not None and hit[0] is fwdp and hit[1] is bwdp:
        _LEARNABLE_EXE.move_to_end(key)
        return hit[2]
    exe = jax.jit(_learnable_vjp(fwdp, bwdp, nnz, dim, backend, key))
    _LEARNABLE_EXE[key] = (fwdp, bwdp, exe)
    _LEARNABLE_EXE.move_to_end(key)
    while len(_LEARNABLE_EXE) > _LEARNABLE_EXE_MAX:
        _LEARNABLE_EXE.popitem(last=False)
    return exe


def drspmm_learnable(fwd, bwd, nnz: int, w_canon: jax.Array,
                     x_vals: jax.Array, x_idx: jax.Array, dim: int, *,
                     backend: Backend = DEFAULT_BACKEND) -> jax.Array:
    """Y = A(w)·dense(CBSR(x)), differentiable in BOTH ``w_canon`` (nnz,)
    and ``x_vals`` (N, k).

    ``fwd``/``bwd`` are the forward/transposed edge-ID packings: pre-fused
    eid arenas (:func:`~repro.graphs.ell.pack_fused_eid_pair`, collated
    batches) or concrete bucketed eid slabs
    (:func:`~repro.graphs.ell.pack_eid_slabs`), fused here on first use.
    ONE dispatch per direction — the weight gather w[eid] happens inside
    the arena computation — and dw is the sampled dot over the same arena
    plus one scatter to canonical order.  A concrete arena pair runs a
    memoized jitted executor; traced arenas trace inline.  Parity of both
    executors with the dense oracle (kernels/ref.py):
    tests/test_learnable_edges.py.
    """
    _check_backend(backend)
    fwd, bwd = _fused_of(fwd, eids=True), _fused_of(bwd, eids=True)
    if isinstance(fwd.nbr, jax.core.Tracer):
        # traced arenas (a collated batch as a jit argument): trace inline,
        # the outer jit owns the caching
        op = _learnable_vjp(fwd, bwd, nnz, dim, backend, None)
    else:
        op = _learnable_executable(fwd, bwd, nnz, dim, backend)
    return op(w_canon, x_vals, x_idx)


# ---------------------------------------------------------------------------
# drspmm_multi — one dispatch per DIRECTION-GROUP: every edge-type direction
# of a hetero layer runs over a RelationPlan super-arena (graphs/ell.py),
# collapsing the per-relation Python loop the serial hetero_conv pays into
# one forward and one transposed-backward executor call per layer
# (DESIGN.md §9).
# ---------------------------------------------------------------------------

def _multi_concat(plan: RelationPlan, vals, idxs):
    """Stack per-type CBSR operands into the plan's type-concat slab,
    padding k up to the group max (padded value columns are zero, so they
    contribute nothing forward; their sampled gradients are sliced off on
    the way back).

    Values and indices travel together as one (n_t, 2, k) stack per type —
    f32 values bitcast to int32 — so the assembly is ONE pad + ONE
    concatenate instead of a separate pad/concat pair per operand (the
    forward-path overhead a CPU profile attributed to the type-concat
    gather).  The shared container is int32, NOT float32: small column
    indices bitcast to f32 are denormals, and the jit partitioner is free
    to flush those to zero when this concat fuses with a shard_map reshard
    (observed on CPU: every xi reached the sharded kernel as 0).  Integer
    lanes are never flushed, and the int32 0 padding bitcasts back to an
    inert f32 +0.0 — identical padding semantics to the two-array form."""
    kmax = max(int(i.shape[1]) for i in idxs)
    vdt = vals[0].dtype
    parts = []
    for v, i in zip(vals, idxs):
        vi = jnp.stack(
            [jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.int32),
             i.astype(jnp.int32)],
            axis=1)                                    # (n_t, 2, k_t)
        k = int(i.shape[1])
        if k < kmax:
            vi = jnp.pad(vi, ((0, 0), (0, 0), (0, kmax - k)))
        parts.append(vi)
    cat = jnp.concatenate(parts)                       # (N, 2, kmax)
    xv = jax.lax.bitcast_convert_type(cat[:, 0, :], jnp.float32).astype(vdt)
    xi = cat[:, 1, :]
    return xv, xi, kmax


def _split_out(plan: RelationPlan, y_cat):
    """Relation-concat output → per-relation views (segment order)."""
    return tuple(y_cat[s.out_off:s.out_off + s.n_dst]
                 for s in plan.segments)


def _dx_cat_to_types(plan: RelationPlan, dx_cat, dv_dense, idxs):
    """Arena relation-concat dV (+ dense-tier type-concat dV) → per-type
    gradients.

    Arena segments of one source type accumulate (cell feeds both ``near``
    and ``pin``); the dense tier's ``dv_dense`` is already type-concat —
    ONE transposed matmul over the stacked ``dense_bwd`` table sums every
    dense relation's contribution per source row — so it adds at most once
    per consuming type.  Padded k columns are sliced off per type.  Either
    input may be ``None`` (single-tier plans)."""
    outs = []
    for ti, t in enumerate(plan.src_types):
        k_t = int(idxs[ti].shape[1])
        acc = None
        for s in plan.arena_segments:
            if s.src_type != t:
                continue
            part = dx_cat[s.src_out_off:s.src_out_off + s.n_src]
            acc = part if acc is None else acc + part
        if dv_dense is not None and any(s.src_type == t
                                        for s in plan.dense_segments):
            o = int(plan.src_off[ti])
            part = dv_dense[o:o + int(plan.src_sizes[ti])]
            acc = part if acc is None else acc + part
        if acc is None:
            ref = dx_cat if dx_cat is not None else dv_dense
            acc = jnp.zeros((int(plan.src_sizes[ti]), k_t), ref.dtype)
        outs.append(acc[:, :k_t])
    return tuple(outs)


def _densify_cbsr(xv, xi, dim: int):
    """Type-concat CBSR → dense (N, dim) operand: the shared densify the
    hybrid forward's tiers both consume (one scatter over N·k values, vs
    the nnz·k-element arena scatter ``_fwd_fused_xla`` pays)."""
    n = xv.shape[0]
    return jnp.zeros((n, dim), xv.dtype).at[
        jnp.arange(n)[:, None], xi].add(xv)


def _multi_fwd_impl(plan: RelationPlan, xv, xi, dim: int, backend: Backend,
                    xd=None):
    if backend == "pallas_fused":
        _record_dispatch("pallas:multi_fwd")
        ya = _k.drspmm_fwd_multi(plan.fwd, xv, xi, dim)       # fp32 arena
        return jnp.take(ya, jnp.asarray(plan.fwd.gather),
                        axis=0).astype(xv.dtype)
    # XLA family: densify once, then the dense-operand arena walk (gather +
    # segment-sum). The in-arena CBSR scatter (`_fwd_fused_xla`) is ~9x
    # slower on CPU at medium nnz — it stays the per-relation reference the
    # serial path runs, and the Pallas kernel keeps consuming CBSR directly
    # (in-register densify; materializing xd would waste TPU bandwidth).
    _record_dispatch("xla:multi_fwd")
    if xd is None:
        xd = _densify_cbsr(xv, xi, dim)
    return _spmm_fused_xla(plan.fwd, xd).astype(xv.dtype)


def _multi_bwd_impl(plan: RelationPlan, gy_cat, xi, backend: Backend):
    """Relation-concat dV (Σ n_src_r, kmax) — ONE transposed dispatch."""
    ft = plan.bwd
    if backend == "pallas_fused":
        _record_dispatch("pallas:multi_bwd")
        ga = _k.drspmm_bwd_multi(ft, plan.bwd_src_rows, gy_cat, xi)
        return jnp.take(ga, jnp.asarray(ft.gather),
                        axis=0).astype(gy_cat.dtype)
    _record_dispatch("xla:multi_bwd")
    return _bwd_fused_xla(ft, gy_cat, xi, rows=plan.bwd_src_rows)


def _multi_dense_fwd(plan: RelationPlan, xv, xi, dim: int, backend: Backend,
                     xd=None):
    """Dense-tier forward: ONE batched masked matmul over the stacked
    ``dense_fwd`` table — every dense-tier relation of the direction-group
    at once (rows are the dense relation-concat, columns the full
    type-concat source slab)."""
    if backend == "pallas_fused":
        _record_dispatch("pallas:multi_dense_fwd")
        return _k.drspmm_dense_tier_fwd(jnp.asarray(plan.dense_fwd), xv, xi,
                                        dim).astype(xv.dtype)
    _record_dispatch("xla:multi_dense_fwd")
    if xd is None:
        xd = _densify_cbsr(xv.astype(jnp.float32), xi, dim)
    return (jnp.asarray(plan.dense_fwd) @ xd.astype(jnp.float32)
            ).astype(xv.dtype)


def _multi_dense_bwd(plan: RelationPlan, gy_dense, xi, backend: Backend):
    """Dense-tier backward: ONE transposed matmul + SSpMM sampling, landing
    directly in type-concat coordinates (``dense_bwd`` is
    (n_src_total, Σ dense n_dst), so source rows outside any dense relation
    come back exactly zero)."""
    if backend == "pallas_fused":
        _record_dispatch("pallas:multi_dense_bwd")
        return _k.drspmm_dense_tier_bwd(jnp.asarray(plan.dense_bwd),
                                        gy_dense, xi).astype(gy_dense.dtype)
    _record_dispatch("xla:multi_dense_bwd")
    dx = jnp.asarray(plan.dense_bwd) @ gy_dense.astype(jnp.float32)
    return jnp.take_along_axis(dx, xi, axis=1).astype(gy_dense.dtype)


def _hybrid_fwd(plan: RelationPlan, xv, xi, dim: int, backend: Backend):
    """Tiered forward: ≤1 fused arena dispatch + ≤1 batched dense dispatch,
    reassembled into the full relation-concat output.  Single-tier plans
    skip the reassembly — their tier-local offsets coincide with the full
    ``out_off`` coordinates.

    On the XLA family the type-concat CBSR is densified ONCE and the
    shared (N, dim) operand feeds both tiers — the dense tier has to
    materialize it anyway, so the arena leg rides along for free and drops
    its nnz-scale scatter.  Pallas tiers keep consuming CBSR directly."""
    xd = None if backend == "pallas_fused" else _densify_cbsr(xv, xi, dim)
    ya = _multi_fwd_impl(plan, xv, xi, dim, backend, xd=xd) \
        if plan.has_arena else None
    yd = _multi_dense_fwd(plan, xv, xi, dim, backend, xd=xd) \
        if plan.has_dense else None
    if yd is None:
        return ya
    if ya is None:
        return yd
    return jnp.concatenate(
        [ya[s.arena_out_off:s.arena_out_off + s.n_dst] if s.tier == "arena"
         else yd[s.dense_off:s.dense_off + s.n_dst]
         for s in plan.segments])


def _hybrid_bwd(plan: RelationPlan, gy_cat, xi, backend: Backend):
    """Tiered backward → (arena relation-concat dV | None, dense type-concat
    dV | None).  The arena transposed super-arena already addresses the FULL
    output concat (its ``nbr`` are pre-offset at pack time), so ``gy_cat``
    feeds it unsliced; the dense tier gets its segments' cotangent slices
    re-stacked into ``dense_fwd`` row order."""
    dx_cat = _multi_bwd_impl(plan, gy_cat, xi, backend) \
        if plan.has_arena else None
    dv_dense = None
    if plan.has_dense:
        gy_dense = gy_cat if not plan.has_arena else jnp.concatenate(
            [gy_cat[s.out_off:s.out_off + s.n_dst]
             for s in plan.dense_segments])
        dv_dense = _multi_dense_bwd(plan, gy_dense, xi, backend)
    return dx_cat, dv_dense


def _build_multi(plan: RelationPlan, dim: int, backend: Backend,
                 trace_key):
    """Custom-vjp callable over (vals_tuple, idxs_tuple): at most one fused
    arena dispatch plus one batched dense-tier dispatch per direction —
    O(1) per layer, not O(relations) — with the type-concat ``xi`` saved as
    a forward residual so the backward never re-runs the concat."""

    def impl(vals, idxs):
        _MULTI_TRACES.append(trace_key)
        xv, xi, _ = _multi_concat(plan, vals, idxs)
        y_cat = _hybrid_fwd(plan, xv, xi, dim, backend)
        return _split_out(plan, y_cat), xi

    @jax.custom_vjp
    def f(vals, idxs):
        return impl(vals, idxs)[0]

    def f_fwd(vals, idxs):
        ys, xi = impl(vals, idxs)
        return ys, (xi, idxs)

    def f_bwd(res, gys):
        xi, idxs = res
        gy_cat = jnp.concatenate(list(gys))
        dx_cat, dv_dense = _hybrid_bwd(plan, gy_cat, xi, backend)
        return (_dx_cat_to_types(plan, dx_cat, dv_dense, idxs),
                tuple(np.zeros(np.shape(i), jax.dtypes.float0)
                      for i in idxs))

    f.defvjp(f_fwd, f_bwd)
    return f


def _zero_cotangent(tree):
    """Symbolic-zero cotangent pytree for a structural custom-vjp primal
    (a plan, an arena, CBSR indices): float0 for the integer tables, dense
    zeros for the float w arenas (custom_vjp requires real-dtype
    cotangents for float leaves)."""
    def z(x):
        if jnp.issubdtype(jnp.result_type(x), jnp.floating):
            return jnp.zeros_like(x)
        return np.zeros(np.shape(x), jax.dtypes.float0)
    return jax.tree.map(z, tree)


def _multi_traced(plan: RelationPlan, vals, idxs, dim: int, backend: Backend):
    """Traced-plan execution (collated serve batches / plan-attached trainer
    graphs, where the graph — plan included — is a jit argument).

    The plan rides through the custom_vjp as an explicit PRIMAL argument
    instead of a closure constant.  This is what makes the executor safe
    under layer-granular remat (``jax.checkpoint`` at the ``hetero_conv``
    boundary, models/backbone.py): the closure form would capture
    checkpoint-scope tracers inside ``f_bwd``, which are stale by the time
    the outer backward invokes it (UnexpectedTracerError).  As a primal the
    plan is a *saved residual* of the checkpointed layer: stored ONCE by
    reference (it is already a jit argument, so every layer's residual
    aliases the same buffers), never rematerialized in the backward, and
    never re-``device_put`` on recompute.  Cotangents for the plan leaves
    are symbolic zeros — the fixed-weight arenas carry no gradient."""

    def body(plan, vals, idxs):
        xv, xi, _ = _multi_concat(plan, vals, idxs)
        return _split_out(plan, _hybrid_fwd(plan, xv, xi, dim, backend)), xi

    @jax.custom_vjp
    def f(plan, vals, idxs):
        return body(plan, vals, idxs)[0]

    def f_fwd(plan, vals, idxs):
        ys, xi = body(plan, vals, idxs)
        # residuals: the plan (aliased jit args, see above) + type-concat xi
        return ys, (plan, xi, idxs)

    def f_bwd(res, gys):
        plan, xi, idxs = res
        gy_cat = jnp.concatenate(list(gys))
        dx_cat, dv_dense = _hybrid_bwd(plan, gy_cat, xi, backend)
        return (_zero_cotangent(plan),
                _dx_cat_to_types(plan, dx_cat, dv_dense, idxs),
                tuple(np.zeros(np.shape(i), jax.dtypes.float0)
                      for i in idxs))

    f.defvjp(f_fwd, f_bwd)
    return f(plan, vals, idxs)


# Same memoization discipline as the learnable executor (§8.3): the
# custom-vjp wrapper + jit is built ONCE per (plan identity, dim, backend)
# in a strong-ref LRU (the jitted closure pins the plan anyway), with a
# trace probe asserting repeat calls never retrace.  Remat interaction:
# ``jax.checkpoint`` traces its body, so a checkpointed layer always sees
# TRACED plan leaves and routes through ``_multi_traced`` — the LRU is only
# ever touched by non-checkpointed concrete-plan calls, so recompute cannot
# thrash it (guarded by tests/test_backbone.py::test_remat_no_retrace).
_MULTI_EXE: "OrderedDict[tuple, tuple]" = OrderedDict()
_MULTI_EXE_MAX = 64
_MULTI_TRACES: list = []


def _multi_executable(plan: RelationPlan, dim: int, backend: Backend):
    key = (id(plan), dim, backend)
    hit = _MULTI_EXE.get(key)
    if hit is not None and hit[0] is plan:
        _MULTI_EXE.move_to_end(key)
        return hit[1]
    exe = jax.jit(_build_multi(plan, dim, backend, trace_key=key))
    _MULTI_EXE[key] = (plan, exe)
    _MULTI_EXE.move_to_end(key)
    while len(_MULTI_EXE) > _MULTI_EXE_MAX:
        _MULTI_EXE.popitem(last=False)
    return exe


def drspmm_multi(plan: RelationPlan, cbsr, dim: int, *,
                 backend: Backend = DEFAULT_BACKEND):
    """Whole-direction-group DR-SpMM, tiered at pack time: the plan's
    arena-tier relations run as ONE fused super-arena dispatch and its
    dense-tier relations (tiny, sub-crossover nnz — graphs/ell.py §tiering)
    as at most ONE batched dense matmul, forward and transposed backward
    alike — dispatch stays O(1) per layer with mixed tiers (≤2 fwd,
    ≤2 bwd).

    ``cbsr`` maps each source node type of the plan to its CBSR pair
    ``{ntype: (vals (n_t, k_t), idx (n_t, k_t))}``; k may differ per type
    (padded to the group max internally, inert).  Returns ``{etype: y
    (n_dst_r, dim)}`` with gradients flowing to every type's ``vals``
    (summed across the relations that consume the type); ``idx`` is
    structural (float0 cotangent).

    A concrete plan routes through the id-keyed LRU executor cache (no
    retrace on repeat calls); a TRACED plan — e.g. a collated serve batch
    whose graph is a jit argument, or any plan seen inside a
    ``jax.checkpoint`` body — is executed inline with the plan threaded as
    a custom-vjp primal (``_multi_traced``: remat-safe, plan saved once as
    an aliased residual) and cached by the outer jit.  Parity with the
    serial per-relation path: tests/test_relation_plan.py.
    """
    _check_backend(backend)
    vals = tuple(cbsr[t][0] for t in plan.src_types)
    idxs = tuple(cbsr[t][1] for t in plan.src_types)
    if isinstance(plan.fwd.nbr, jax.core.Tracer):
        ys = _multi_traced(plan, vals, idxs, dim, backend)
    else:
        ys = _multi_executable(plan, dim, backend)(vals, idxs)
    return {s.etype: y for s, y in zip(plan.segments, ys)}


# ---------------------------------------------------------------------------
# softmax_aggr_multi — GENConv's softmax aggregation (DeeperGCN, DESIGN.md
# §15) over the same RelationPlan: one ``gen_aggr_fwd`` and one transposed
# ``gen_aggr_bwd`` per direction-group on ``pallas_fused``; ``xla_fused``
# walks the same arena tables in plain XLA.  Dense-tier relations (tiny,
# sub-crossover) run a plain masked implementation over the plan's dense
# table, differentiated by autodiff.
# ---------------------------------------------------------------------------

GEN_EPS = 1e-7           # GENConv's message offset: m = relu(x) + eps
_GEN_BIG = 1e30          # sentinel normaliser: exp(t·m − big) == 0


def _gen_remap(f: FusedELL, sentinel: int):
    """The arena's ids with padding slots pointed at ``sentinel``."""
    return jnp.where(jnp.asarray(f.w) != 0, jnp.asarray(f.nbr), sentinel)


def _gen_fwd_xla(f: FusedELL, t_rel, m_cat):
    """(out, lse), arena order, (R_arena, H): the kernel's math in XLA."""
    valid = (jnp.asarray(f.w) != 0)[..., None]
    xm = jnp.take(m_cat, jnp.asarray(f.nbr), axis=0)          # (C,BR,Ec,H)
    t = jnp.take(t_rel, jnp.asarray(f.rel))[:, None, None, None]
    z = jnp.where(valid, t * xm, _k.GEN_NEG)
    blk = jnp.asarray(f.block_of)
    n_blocks = f.n_arena_rows // f.row_block
    mx = jax.ops.segment_max(jnp.max(z, axis=2), blk, num_segments=n_blocks)
    p = jnp.where(valid, jnp.exp(z - jnp.take(mx, blk, axis=0)[:, :, None]),
                  0.0)
    s = jax.ops.segment_sum(jnp.sum(p, axis=2), blk, num_segments=n_blocks)
    a = jax.ops.segment_sum(jnp.sum(p * xm, axis=2), blk,
                            num_segments=n_blocks)
    nz = s > 0
    s1 = jnp.where(nz, s, 1.0)
    h = m_cat.shape[1]
    out = jnp.where(nz, a / s1, 0.0).reshape(f.n_arena_rows, h)
    lse = jnp.where(nz, mx + jnp.log(s1), 0.0).reshape(f.n_arena_rows, h)
    return out, lse


def _gen_bwd_xla(ft: FusedELL, t_rel, y_rows, m_arena):
    """(dm, dt), arena order: the backward kernel's math in XLA."""
    h = m_arena.shape[1]
    valid = (jnp.asarray(ft.w) != 0)[..., None]
    rows = jnp.take(y_rows, jnp.asarray(ft.nbr), axis=0)     # (C,BR,Ec,3H)
    g, a, lse = rows[..., :h], rows[..., h:2 * h], rows[..., 2 * h:]
    m = jnp.take(m_arena, _arena_rows(ft), axis=0)[:, :, None, :]
    t = jnp.take(t_rel, jnp.asarray(ft.rel))[:, None, None, None]
    q = jnp.where(valid, g * jnp.exp(t * m - lse), 0.0)
    blk = jnp.asarray(ft.block_of)
    n_blocks = ft.n_arena_rows // ft.row_block
    dm = jax.ops.segment_sum(jnp.sum(q * (1.0 + t * (m - a)), axis=2), blk,
                             num_segments=n_blocks)
    dt = jax.ops.segment_sum(jnp.sum(q * m * (m - a), axis=2), blk,
                             num_segments=n_blocks)
    return (dm.reshape(ft.n_arena_rows, h), dt.reshape(ft.n_arena_rows, h))


def _gen_arena_fwd(plan: RelationPlan, m_cat, t_rel, backend: Backend):
    """Arena-tier (y, lse) in the arena-only output concat."""
    _METRICS.inc("mp.gen_aggr_dispatches", dir="fwd")
    f = plan.fwd
    if backend == "pallas_fused":
        h = m_cat.shape[1]
        rows = _k._lane_pad(jnp.concatenate(
            [m_cat, jnp.zeros((1, h), m_cat.dtype)]))[:, None, :]
        y, lse = _k.gen_aggr_fwd(f, _gen_remap(f, m_cat.shape[0]), t_rel,
                                 rows)
        y, lse = y[:, :h], lse[:, :h]
    else:
        y, lse = _gen_fwd_xla(f, t_rel, m_cat)
    gather = jnp.asarray(f.gather)
    return jnp.take(y, gather, axis=0), jnp.take(lse, gather, axis=0)


def _gen_arena_bwd(plan: RelationPlan, m_cat, t_rel, y, lse, gy,
                   backend: Backend):
    """Cotangents of (m_cat, t_rel) from the arena tier's output cotangent
    ``gy`` (arena-only concat) over the transposed super-arena."""
    _METRICS.inc("mp.gen_aggr_dispatches", dir="bwd")
    ft = plan.bwd
    h = m_cat.shape[1]
    # [g | a | lse] in the FULL output concat the transposed arena's ids
    # address (dense-tier rows are never referenced: zeros)
    zero = lambda n: jnp.zeros((n, 3 * h), jnp.float32)
    tab = jnp.concatenate([gy, y, lse], axis=1)
    parts = [tab[s.arena_out_off:s.arena_out_off + s.n_dst]
             if s.tier == "arena" else zero(s.n_dst) for s in plan.segments]
    m_arena = jnp.take(m_cat, jnp.asarray(plan.bwd_src_rows), axis=0)
    if backend == "pallas_fused":
        n_out = plan.n_out_total
        sentinel = jnp.concatenate([jnp.zeros((1, 2 * h), jnp.float32),
                                    jnp.full((1, h), _GEN_BIG, jnp.float32)],
                                   axis=1)
        pad3 = lambda x: jnp.concatenate(
            [_k._lane_pad(x[:, i * h:(i + 1) * h]) for i in range(3)], axis=1)
        rows = pad3(jnp.concatenate(parts + [sentinel]))[:, None, :]
        dm, dt = _k.gen_aggr_bwd(ft, _gen_remap(ft, n_out), t_rel, rows,
                                 _k._lane_pad(m_arena))
        dm, dt = dm[:, :h], dt[:, :h]
    else:
        dm, dt = _gen_bwd_xla(ft, t_rel, jnp.concatenate(parts), m_arena)
    dx = jnp.take(dm, jnp.asarray(ft.gather), axis=0)   # relation-concat
    dm_cat = jnp.zeros_like(m_cat)
    for s in plan.arena_segments:
        o = plan.src_off[plan.src_types.index(s.src_type)]
        dm_cat = dm_cat.at[o:o + s.n_src].add(
            dx[s.src_out_off:s.src_out_off + s.n_src])
    dt_rel = jnp.stack([jnp.sum(dt[s.bwd_rows[0]:s.bwd_rows[1]])
                        for s in plan.arena_segments])
    return dm_cat, dt_rel


def _gen_arena(plan: RelationPlan, m_cat, t_rel, backend: Backend):
    """Custom-vjp arena tier; the plan rides as a primal (as in
    ``_multi_traced``) so the op is safe inside scan and checkpoint."""

    @jax.custom_vjp
    def f(plan, m_cat, t_rel):
        return _gen_arena_fwd(plan, m_cat, t_rel, backend)[0]

    def f_fwd(plan, m_cat, t_rel):
        y, lse = _gen_arena_fwd(plan, m_cat, t_rel, backend)
        return y, (plan, m_cat, t_rel, y, lse)

    def f_bwd(res, gy):
        plan, m_cat, t_rel, y, lse = res
        dm, dt = _gen_arena_bwd(plan, m_cat, t_rel, y, lse, gy, backend)
        return _zero_cotangent(plan), dm, dt

    f.defvjp(f_fwd, f_bwd)
    return f(plan, m_cat, t_rel)


_GEN_DENSE_BLOCK = 1 << 22     # elements of one (rows, n_src, H) block


def _gen_dense(a, m_src, t):
    """Softmax aggregation of one dense-tier relation from its (n_dst,
    n_src) weight block (non-zero = edge): a masked softmax over
    destination-row blocks, each block checkpointed so no (n_dst, n_src, H)
    tensor is kept."""
    n_dst, n_src = a.shape
    h = m_src.shape[1]
    rb = max(1, min(n_dst, _GEN_DENSE_BLOCK // max(n_src * h, 1)))
    n_pad = -(-n_dst // rb) * rb
    mask = jnp.pad(a != 0, ((0, n_pad - n_dst), (0, 0)))
    z = t * m_src

    @jax.checkpoint
    def block(mk):
        mk = mk[:, :, None]
        # softmax's own VJP centres the gradient, g·(m − a), as the kernel
        w = jax.nn.softmax(jnp.where(mk, z[None], _k.GEN_NEG), axis=1)
        out = jnp.sum(w * m_src[None], axis=1)
        return jnp.where(jnp.any(mk, axis=1), out, 0.0)

    out = jax.lax.map(block, mask.reshape(n_pad // rb, rb, n_src))
    return out.reshape(n_pad, h)[:n_dst]


def softmax_aggr_multi(plan: RelationPlan, x_by_type, t_by_relation, *,
                       backend: Backend = DEFAULT_BACKEND):
    """GENConv softmax aggregation of every relation of ``plan``.

    For relation r from type s to type d, with messages
    m_j = relu(x_s[j]) + eps:  a_ic = Σ_{j→i} softmax_j(t_r m_jc) m_jc,
    per channel, over i's in-edges; a destination with no in-edges gets 0.
    ``x_by_type`` maps each source node type to its (n_t, H) features and
    ``t_by_relation`` each relation to its scalar temperature.  Returns
    ``{etype: (n_dst, H)}``; gradients flow to the features and to every
    ``t``.  Edge weights in the plan only mark edges (non-zero); their
    values are not used.  Arena-tier relations run as one ``gen_aggr_fwd``
    and one ``gen_aggr_bwd`` per call on ``pallas_fused`` (f32, per-channel
    running max), and as the same arena walk in XLA on ``xla_fused``."""
    _check_backend(backend)
    m = {t: jax.nn.relu(x_by_type[t].astype(jnp.float32)) + GEN_EPS
         for t in plan.src_types}
    out = {}
    if plan.has_arena:
        m_cat = jnp.concatenate([m[t] for t in plan.src_types])
        t_rel = jnp.stack([jnp.asarray(t_by_relation[s.etype], jnp.float32)
                           for s in plan.arena_segments])
        y = _gen_arena(plan, m_cat, t_rel, backend)
        for s in plan.arena_segments:
            out[s.etype] = y[s.arena_out_off:s.arena_out_off + s.n_dst]
    for s in plan.dense_segments:
        o = plan.src_off[plan.src_types.index(s.src_type)]
        a = jnp.asarray(plan.dense_fwd)[s.dense_off:s.dense_off + s.n_dst,
                                        o:o + s.n_src]
        out[s.etype] = _gen_dense(a, m[s.src_type],
                                  jnp.asarray(t_by_relation[s.etype],
                                              jnp.float32))
    return out


# ---------------------------------------------------------------------------
# drspmm_multi_sharded — the giant-graph path (DESIGN.md §12): the
# super-arena partitioned by destination row-block over a ("shard",) mesh
# (sharding/plan_shard.py), executed under shard_map with ONE all-to-all
# halo exchange per direction.  Each device holds only its local arenas +
# owned operand slabs; the §1/§5 per-shard contraction is unchanged.
# ---------------------------------------------------------------------------

def _local_fused(tabs, n_dst: int, n_src: int, row_block: int,
                 chunk: int) -> FusedELL:
    """This device's arena from shard_map operand slices (leading shard
    axis of size 1) — traced leaves, static geometry."""
    nbr, w, blk, start, rows, gather = (t[0] for t in tabs)
    return FusedELL(nbr=nbr, w=w, block_of=blk, start=start, rows=rows,
                    gather=gather, n_dst=n_dst, n_src=n_src, nnz=-1,
                    row_block=row_block, chunk=chunk)


def _build_multi_sharded(splan, dim: int, backend: Backend, trace_key=None):
    """Custom-vjp callable over (vals_tuple, idxs_tuple), SPMD over the
    ("shard",) mesh.

    Forward: each device gathers the source rows its peers requested
    (``send_idx``), one ``all_to_all`` delivers every halo owner-major, the
    local slab ``[own | halo]`` feeds the unchanged fused contraction, and
    each device writes its contiguous output slab.  Backward reverses the
    exchange: the transposed local arena produces dx over the local slab;
    the halo segment travels back through the same ``all_to_all`` and is
    scatter-added into the owner shards' dx rows (two-coordinate backward,
    DESIGN.md §12).  Padded slots carry zero weights end to end — inert.
    """
    from repro.sharding.specs import shard_mesh
    from jax.sharding import PartitionSpec as P

    n, s_slab, t_slab, h = (splan.n_shards, splan.src_slab, splan.out_slab,
                            splan.halo_pad)
    local_src = splan.local_src
    mesh = shard_mesh(n)
    spec = P("shard")

    def probe():
        if trace_key is not None:
            _SHARDED_TRACES.append(trace_key)

    def fwd_inner(xv, xi, nbr, w, blk, start, rows, gather, send):
        # xv/xi: (S, k) owned slab; tables: (1, ...) shard slices
        send2 = send[0]                               # (n, H) rows peers want
        hv = jax.lax.all_to_all(jnp.take(xv, send2, axis=0), "shard", 0, 0)
        hi = jax.lax.all_to_all(jnp.take(xi, send2, axis=0), "shard", 0, 0)
        slab_v = jnp.concatenate([xv, hv.reshape(-1, xv.shape[1])])
        slab_i = jnp.concatenate([xi, hi.reshape(-1, xi.shape[1])])
        f = _local_fused((nbr, w, blk, start, rows, gather), t_slab,
                         local_src, splan.row_block, splan.fwd_chunk)
        if backend == "pallas_fused":
            ya = _k.drspmm_fwd_fused(f, slab_v, slab_i, dim)
            return jnp.take(ya, f.gather, axis=0).astype(xv.dtype)
        # densify-first, like the single-device hybrid: the slab is local
        # after the exchange, so the dense-operand walk is purely per-shard
        return _spmm_fused_xla(
            f, _densify_cbsr(slab_v, slab_i, dim)).astype(xv.dtype)

    def bwd_inner(gy, xi, nbr, w, blk, start, rows, gather, send):
        # gy: (T, D) owned output cotangent; xi: (S, k) owned indices
        send2 = send[0]
        hi = jax.lax.all_to_all(jnp.take(xi, send2, axis=0), "shard", 0, 0)
        slab_i = jnp.concatenate([xi, hi.reshape(-1, xi.shape[1])])
        ft = _local_fused((nbr, w, blk, start, rows, gather), local_src,
                          t_slab, splan.row_block, splan.bwd_chunk)
        if backend == "pallas_fused":
            xi_arena = jnp.take(slab_i, ft.rows, axis=0)
            ga = _k.drspmm_bwd_fused(ft, gy, xi_arena)
            dx_slab = jnp.take(ga, ft.gather, axis=0).astype(gy.dtype)
        else:
            dx_slab = _bwd_fused_xla(ft, gy, slab_i)  # (S + n·H, k)
        # reverse exchange: halo dx goes home, owners scatter-add it.  Both
        # padded send slots (local row 0) and the self segment add exact
        # zeros — unreferenced dx-slab rows gather from the sentinel block.
        back = jax.lax.all_to_all(
            dx_slab[s_slab:].reshape(n, h, -1), "shard", 0, 0)
        return dx_slab[:s_slab].at[send2.reshape(-1)].add(
            back.reshape(n * h, -1))

    sm = dict(mesh=mesh, check_vma=False)
    fwd_sm = jax.shard_map(fwd_inner, in_specs=(spec,) * 9,
                           out_specs=spec, **sm)
    bwd_sm = jax.shard_map(bwd_inner, in_specs=(spec,) * 9,
                           out_specs=spec, **sm)
    fwd_tabs = (splan.fwd_nbr, splan.fwd_w, splan.fwd_block_of,
                splan.fwd_start, splan.fwd_rows, splan.fwd_gather)
    bwd_tabs = (splan.bwd_nbr, splan.bwd_w, splan.bwd_block_of,
                splan.bwd_start, splan.bwd_rows, splan.bwd_gather)
    family = _family(backend)

    def _pad_rows(a, total):
        return jnp.pad(a, ((0, total - a.shape[0]), (0, 0)))

    @jax.custom_vjp
    def f(vals, idxs):
        probe()
        _record_dispatch(f"{family}:shard_fwd")
        xv, xi, _ = _multi_concat(splan, vals, idxs)
        y_full = fwd_sm(_pad_rows(xv, n * s_slab), _pad_rows(xi, n * s_slab),
                        *fwd_tabs, splan.send_idx)
        return _split_out(splan, y_full[:splan.n_out_total])

    def f_fwd(vals, idxs):
        return f(vals, idxs), idxs                # xi is the only residual

    def f_bwd(idxs, gys):
        _record_dispatch(f"{family}:shard_bwd")
        gy_cat = jnp.concatenate(list(gys))
        _, xi, _ = _multi_concat(splan, [jnp.zeros_like(i, jnp.float32)
                                         for i in idxs], idxs)
        dx_full = bwd_sm(_pad_rows(gy_cat, n * t_slab),
                         _pad_rows(xi, n * s_slab), *bwd_tabs,
                         splan.send_idx)
        dx = dx_full[:splan.n_src_total]          # already type-concat
        outs = tuple(dx[o:o + sz][:, :int(i.shape[1])]
                     for o, sz, i in zip(splan.src_off, splan.src_sizes,
                                         idxs))
        return (outs, tuple(np.zeros(np.shape(i), jax.dtypes.float0)
                            for i in idxs))

    f.defvjp(f_fwd, f_bwd)
    return f


_SHARDED_EXE: "OrderedDict[tuple, tuple]" = OrderedDict()
_SHARDED_EXE_MAX = 32
_SHARDED_TRACES: list = []


def _sharded_executable(splan, dim: int, backend: Backend):
    key = (id(splan), dim, backend)
    hit = _SHARDED_EXE.get(key)
    if hit is not None and hit[0] is splan:
        _SHARDED_EXE.move_to_end(key)
        return hit[1]
    exe = jax.jit(_build_multi_sharded(splan, dim, backend, trace_key=key))
    _SHARDED_EXE[key] = (splan, exe)
    _SHARDED_EXE.move_to_end(key)
    while len(_SHARDED_EXE) > _SHARDED_EXE_MAX:
        _SHARDED_EXE.popitem(last=False)
    return exe


def drspmm_multi_sharded(splan, cbsr, dim: int, *,
                         backend: Backend = DEFAULT_BACKEND):
    """Whole-direction-group DR-SpMM over a mesh-partitioned plan
    (:class:`~repro.sharding.plan_shard.ShardedRelationPlan`).

    Same contract as :func:`drspmm_multi` — ``cbsr`` maps source node types
    to CBSR pairs, returns ``{etype: y}``, gradients flow to every type's
    ``vals`` — but the execution is SPMD over the ``("shard",)`` mesh: one
    all-to-all halo exchange + one local fused contraction per direction,
    with each device holding only its arena slices (fwd/grad parity vs the
    single-device plan path: tests/test_sharded_parity.py).  Needs
    ``splan.n_shards`` visible devices (virtual CPU devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).  A concrete
    plan routes through the id-keyed LRU; a traced plan (e.g. a sharded
    trainer step taking the graph as a jit argument) traces inline and the
    outer jit owns the caching.
    """
    _check_backend(backend)
    vals = tuple(cbsr[t][0] for t in splan.src_types)
    idxs = tuple(cbsr[t][1] for t in splan.src_types)
    if isinstance(splan.fwd_nbr, jax.core.Tracer):
        ys = _build_multi_sharded(splan, dim, backend)(vals, idxs)
    else:
        ys = _sharded_executable(splan, dim, backend)(vals, idxs)
    return {s.etype: y for s, y in zip(splan.segments, ys)}
