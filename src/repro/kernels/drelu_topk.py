"""Pallas kernel: D-ReLU row thresholding via row-wise binary search.

The paper (Sec. 3.1) describes D-ReLU as "selectively preserv[ing] the most
significant elements of node embeddings through row-wise *binary search*".
``lax.top_k`` implements the same semantics with a sort — O(D log D) compare
-exchanges and poor TPU lowering.  This kernel does what the paper says:
bisection on the value range, counting survivors per row with a vector
compare+reduce per iteration — O(D · iters) elementwise work, fully
vectorizable on the VPU, no sort network.

For f32 inputs, ~64 bisection steps shrink the bracket below 1 ULP around
the k-th value, making the mask exactly the top-k mask whenever the row has
distinct values (ties keep all tied elements — same convention as Eq. 3,
which thresholds with ≥).

Grid: row blocks of the (N, D) matrix; each block resident in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.graphs.ell import ROW_BLOCK
from repro.kernels.drspmm import run_pallas

N_ITERS = 64


def _bisect_threshold(x, k, n_iters=N_ITERS):
    """Per-row threshold th with |{j : x[i,j] >= th}| == k (distinct values).

    x (R, D) f32 values in VMEM.  Pure jnp — shared by kernel & oracle.
    """
    lo = x.min(axis=1)                       # count(>= lo) == D  (too many)
    hi = x.max(axis=1)                       # count(>= hi) >= 1

    def body(_, carry):
        lo_, hi_ = carry
        mid = 0.5 * (lo_ + hi_)
        cnt = jnp.sum(x >= mid[:, None], axis=1)
        take_hi = cnt > k                    # too many kept -> raise floor
        lo_ = jnp.where(take_hi, mid, lo_)
        hi_ = jnp.where(take_hi, hi_, mid)
        return lo_, hi_

    lo, hi = jax.lax.fori_loop(0, n_iters, body, (lo, hi))
    # hi is the tightest bound with count <= k; keep x >= hi, then relax to
    # the k-th value exactly by taking the min of the kept set.
    return hi


def _drelu_kernel(x_ref, out_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)
    th = _bisect_threshold(x, k)
    keep = x >= th[:, None]
    # ties below machine resolution can overshoot: fall back on >= exactness
    out = jnp.where(keep, x, 0.0)
    out_ref[...] = out.astype(out_ref.dtype)


def drelu_pallas(x: jax.Array, k: int, *, block_rows: int = ROW_BLOCK,
                 interpret: bool | None = None) -> jax.Array:
    """Dense D-ReLU via the binary-search kernel.  x (N, D)."""
    n, d = x.shape
    if k >= d:
        return x
    br = min(block_rows, n)
    pad = (-n) % br
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    out = run_pallas(lambda interp: pl.pallas_call(
        functools.partial(_drelu_kernel, k=k),
        grid=((n + pad) // br,),
        name="drelu_topk",
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, d), x.dtype),
        interpret=interp,
    )(xp), interpret)
    return out[:n] if pad else out
