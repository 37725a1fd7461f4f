"""Array-operation packers against their per-row / per-chunk loop forms.

``pack_ell`` fills every degree bucket's slab with one scatter after a
radix sort of the row ids, ``fuse_bucketed`` cuts each bucket into its
chunk grid with one reshape and a mask, and ``pick_chunk`` /
``pick_chunk_multi`` count every candidate's slots as arrays.  The loop
forms they replaced are kept below as oracles: every leaf must come out
bit-identical, dtype and shape included.
"""

import dataclasses

import numpy as np
import pytest

from repro.graphs.ell import (BucketedELL, CHUNK_CANDIDATES, DEFAULT_BOUNDS,
                              ELLBucket, FUSED_ROW_BLOCK, FusedELL, ROW_BLOCK,
                              _round_up, _stable_order, fuse_bucketed,
                              pack_eid_slabs, pack_ell, pad_fused_arena,
                              pick_chunk, pick_chunk_multi)


# ------------------------------ loop oracles ------------------------------

def loop_pack_ell(dst, src, w, n_dst, n_src, bounds=DEFAULT_BOUNDS,
                  row_block=ROW_BLOCK):
    dst = np.asarray(dst, np.int64)
    src = np.asarray(src, np.int64)
    if w is None:
        w = np.ones(dst.shape[0], np.float32)
    w = np.asarray(w, np.float32)
    order = np.argsort(dst, kind="stable")
    dst, src, w = dst[order], src[order], w[order]
    deg = np.bincount(dst, minlength=n_dst)
    rowptr = np.zeros(n_dst + 1, np.int64)
    np.cumsum(deg, out=rowptr[1:])
    nonempty = np.nonzero(deg > 0)[0]
    buckets, nnz, lo = [], 0, 1
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
        nbr = np.zeros((n_r, width), np.int32)
        wts = np.zeros((n_r, width), np.float32)
        rid = np.zeros(n_r, np.int32)
        rid[: rows.size] = rows
        for i, r in enumerate(rows):
            d = rowptr[r + 1] - rowptr[r]
            nbr[i, :d] = src[rowptr[r]:rowptr[r + 1]]
            wts[i, :d] = w[rowptr[r]:rowptr[r + 1]]
        nnz += int((wts != 0).sum())
        buckets.append(ELLBucket(rows=rid, nbr=nbr, w=wts))
    if not buckets:
        buckets = [ELLBucket(rows=np.zeros((row_block,), np.int32),
                             nbr=np.zeros((row_block, 1), np.int32),
                             w=np.zeros((row_block, 1), np.float32))]
    return BucketedELL(buckets=tuple(buckets), n_dst=n_dst, n_src=n_src,
                       nnz=nnz)


def _loop_effective_widths(w):
    nz = w != 0
    return np.where(nz.any(axis=1), w.shape[1] - np.argmax(nz[:, ::-1], 1), 0)


def loop_block_widths(adj, row_block):
    bws = []
    for b in adj.buckets:
        width_r = np.sort(_loop_effective_widths(np.asarray(b.w)))[::-1]
        rpad = _round_up(max(width_r.size, 1), row_block)
        width_r = np.concatenate(
            [width_r, np.zeros(rpad - width_r.size, np.int64)])
        for t in range(rpad // row_block):
            bws.append(int(width_r[t * row_block:(t + 1) * row_block]
                           .max(initial=0)))
    return bws


def loop_pick(bws, row_block, candidates=CHUNK_CANDIDATES):
    def slots(c):
        return sum(row_block * c * max(1, -(-bw // c)) for bw in bws)
    return min(candidates, key=lambda c: (slots(c), -c))


def loop_fuse_bucketed(adj, row_block=FUSED_ROW_BLOCK, chunk=None, *,
                       eids=False):
    if chunk is None:
        chunk = loop_pick(loop_block_widths(adj, row_block), row_block)
    nbr_chunks, w_chunks, block_of, start, rows_parts = [], [], [], [], []
    gather = np.full(adj.n_dst, -1, np.int64)
    blk = arena_off = 0
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
        nz = wt_p != 0
        width_r = np.where(nz.any(axis=1),
                           epad - np.argmax(nz[:, ::-1], axis=1), 0)
        order = np.argsort(-width_r, kind="stable")
        nb_p, wt_p, rid_p, width_r = (nb_p[order], wt_p[order],
                                      rid_p[order], width_r[order])
        real = width_r > 0
        gather[rid_p[real]] = arena_off + np.nonzero(real)[0]
        rows_parts.append(rid_p)
        arena_off += rpad
        for t in range(rpad // row_block):
            sl = slice(t * row_block, (t + 1) * row_block)
            bw = int(width_r[sl].max(initial=0))
            for ci in range(max(1, -(-bw // chunk))):
                cs = slice(ci * chunk, (ci + 1) * chunk)
                nbr_chunks.append(nb_p[sl, cs])
                w_chunks.append(wt_p[sl, cs])
                block_of.append(blk)
                start.append(1 if ci == 0 else 0)
            blk += 1
    nbr_chunks.append(np.zeros((row_block, chunk), np.int32))
    w_chunks.append(np.zeros((row_block, chunk), np.float32))
    block_of.append(blk)
    start.append(1)
    rows_parts.append(np.zeros(row_block, np.int32))
    gather[gather < 0] = arena_off
    nnz = adj.nnz if adj.nnz >= 0 else int(
        sum(int((np.asarray(b.w) != 0).sum()) for b in adj.buckets))
    w_arena = np.stack(w_chunks)
    eid_arena = None
    if eids:
        eid_arena = w_arena.astype(np.int32) - 1
        w_arena = (w_arena != 0).astype(np.float32)
    return FusedELL(
        nbr=np.stack(nbr_chunks), w=w_arena,
        block_of=np.asarray(block_of, np.int32),
        start=np.asarray(start, np.int32),
        rows=np.concatenate(rows_parts).astype(np.int32),
        gather=gather.astype(np.int32),
        n_dst=adj.n_dst, n_src=adj.n_src, nnz=nnz,
        row_block=row_block, chunk=chunk, eid=eid_arena)


# ------------------------------- comparison -------------------------------

def _assert_same(a, b, what):
    """Same class, same static fields, bit-identical array leaves."""
    assert type(a) is type(b), what
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if f.name == "buckets":
            assert len(x) == len(y), f"{what}: bucket count"
            for i, (bx, by) in enumerate(zip(x, y)):
                _assert_same(bx, by, f"{what}.buckets[{i}]")
        elif x is None or y is None:
            assert x is None and y is None, f"{what}.{f.name}"
        elif f.metadata.get("static"):
            assert x == y, f"{what}.{f.name}: {x} != {y}"
        else:
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype, f"{what}.{f.name} dtype"
            assert x.shape == y.shape, f"{what}.{f.name} shape"
            assert np.array_equal(x, y), f"{what}.{f.name} values"


# --------------------------------- cases ----------------------------------

def _heavy_tailed(rng):
    """Heavy-tailed rows (bulk ~20, evil rows to 300) with empty rows."""
    n_dst, n_src = 700, 500
    deg = np.clip(rng.lognormal(np.log(20), 0.8, n_dst), 0, 300).astype(int)
    deg[rng.random(n_dst) < 0.15] = 0
    dst = np.repeat(np.arange(n_dst), deg)
    rng.shuffle(dst)
    src = rng.integers(0, n_src, dst.size)
    w = rng.normal(size=dst.size).astype(np.float32)
    w[rng.random(dst.size) < 0.05] = 0.0       # zero weights are not nnz
    return dst, src, w, n_dst, n_src


def _at_bounds(rng):
    """One row at each bucket bound, one past it, and rows above the last
    bound; unit weights."""
    degs = []
    for b in DEFAULT_BOUNDS:
        degs += [b, b + 1]
    degs += [300, 257, 1]
    n_dst = len(degs) + 5                      # trailing empty rows
    dst = np.repeat(np.arange(len(degs)), degs)
    rng.shuffle(dst)
    return dst, rng.integers(0, 400, dst.size), None, n_dst, 400


def _all_empty(_rng):
    return (np.zeros(0, np.int64), np.zeros(0, np.int64),
            np.zeros(0, np.float32), 37, 19)


def _wide_ids(rng):
    """Row ids past 2**16, so the radix sort takes two digits."""
    n_dst = 70_001
    dst = np.concatenate([rng.integers(0, n_dst, 3000),
                          np.full(40, n_dst - 1), np.full(9, 65_536)])
    rng.shuffle(dst)
    return dst, rng.integers(0, 90, dst.size), None, n_dst, 90


CASES = {"heavy_tailed": _heavy_tailed, "at_bounds": _at_bounds,
         "all_empty": _all_empty, "wide_ids": _wide_ids}

# (case, chunk, eids, pad): every case at the picked width, the chunk
# widths 1/4/8/16 on the heavy-tailed rows, edge-id arenas, and a
# ``pad_fused_arena`` target on top of the fused arena.
PARAMS = ([(c, None, False, False) for c in CASES]
          + [("heavy_tailed", ck, False, False) for ck in (1, 4, 8, 16)]
          + [("heavy_tailed", 8, True, False), ("at_bounds", None, True, False),
             ("all_empty", 4, True, False)]
          + [("heavy_tailed", None, False, True), ("at_bounds", 16, True, True),
             ("all_empty", 8, False, True)])


@pytest.mark.parametrize("case,chunk,eids,pad", PARAMS,
                         ids=[f"{c}-ck{k}-eids{int(e)}-pad{int(p)}"
                              for c, k, e, p in PARAMS])
def test_packers_bit_identical_to_loops(case, chunk, eids, pad):
    rng = np.random.default_rng(sum(map(ord, case)))
    dst, src, w, n_dst, n_src = CASES[case](rng)
    if eids:
        fwd, bwd, order, nnz = pack_eid_slabs(dst, src, n_dst, n_src)
        assert np.array_equal(order, np.argsort(dst, kind="stable"))
        eid = np.empty(nnz, np.int64)
        eid[order] = np.arange(nnz)
        w_ref = eid.astype(np.float32) + 1.0
        pairs = [(fwd, loop_pack_ell(dst, src, w_ref, n_dst, n_src)),
                 (bwd, loop_pack_ell(src, dst, w_ref, n_src, n_dst))]
    else:
        pairs = [(pack_ell(dst, src, w, n_dst, n_src),
                  loop_pack_ell(dst, src, w, n_dst, n_src)),
                 (pack_ell(src, dst, w, n_src, n_dst),
                  loop_pack_ell(src, dst, w, n_src, n_dst))]
    for d, (got, ref) in zip(("fwd", "bwd"), pairs):
        _assert_same(got, ref, f"pack_ell {d}")
        f_got = fuse_bucketed(got, chunk=chunk, eids=eids)
        f_ref = loop_fuse_bucketed(ref, chunk=chunk, eids=eids)
        _assert_same(f_got, f_ref, f"fuse_bucketed {d}")
        if pad:
            c, br, _ = f_ref.nbr.shape
            target = (c + 5, f_ref.n_arena_rows + 3 * br)
            _assert_same(pad_fused_arena(f_got, *target),
                         pad_fused_arena(f_ref, *target), f"padded {d}")


@pytest.mark.parametrize("seed", range(6))
def test_pick_chunk_matches_candidate_loop(seed):
    """Same width as the loop over candidates, ties to the wider, alone
    and summed over several packings."""
    rng = np.random.default_rng(seed)
    packs = []
    for _ in range(3):
        n_dst = int(rng.integers(1, 300))
        hi = int(rng.choice([3, 6, 20, 80, 300]))
        deg = rng.integers(0, hi + 1, n_dst)
        dst = np.repeat(np.arange(n_dst), deg)
        packs.append(pack_ell(dst, rng.integers(0, 50, dst.size), None,
                              n_dst, 50))
    for rb in (FUSED_ROW_BLOCK, 1, 4):
        for cands in (CHUNK_CANDIDATES, (1, 2, 4, 8, 16), (8, 4)):
            bws = [loop_block_widths(p, rb) for p in packs]
            for p, bw in zip(packs, bws):
                assert pick_chunk(p, rb, cands) == loop_pick(bw, rb, cands)
            assert pick_chunk_multi(packs, rb, cands) == \
                loop_pick([b for bw in bws for b in bw], rb, cands)
    assert pick_chunk_multi([], FUSED_ROW_BLOCK) == \
        loop_pick([], FUSED_ROW_BLOCK)


def test_stable_order_is_the_stable_argsort():
    rng = np.random.default_rng(7)
    for n in (1, 300, 1 << 16, (1 << 16) + 1, 5_000_000, 1 << 40):
        keys = rng.integers(0, n, 20_000)
        assert np.array_equal(_stable_order(keys),
                              np.argsort(keys, kind="stable"))
