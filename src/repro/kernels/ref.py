"""Pure-jnp oracles for the DR-SpMM ops.

Everything here is the mathematically transparent (dense) definition the
tests hold both executors of kernels/ops.py to (``pallas_fused``, bit-close
in interpret mode; ``xla_fused``).  No op dispatches here.  Gradients come
from autodiff: differentiating the dense scatter of the CBSR values gives
exactly the sampled backward of Alg. 2.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.cbsr import scatter_cbsr


def _dense(adj) -> jnp.ndarray:
    """(n_dst, n_src) matrix of a BucketedELL or FusedELL packing."""
    return jnp.asarray(adj.to_dense(), jnp.float32)


def drspmm_fwd_ref(adj, x_vals, x_idx, dim: int):
    """Y = A · dense(X_cbsr) via fully dense math."""
    x = scatter_cbsr(x_vals, x_idx, dim)
    return _dense(adj) @ x


def drspmm_bwd_ref(adj_t, gy, x_idx):
    """dX_vals = sample(Aᵀ · dY, x_idx)  — the SSpMM of Alg. 2."""
    gx_dense = _dense(adj_t) @ gy
    return jnp.take_along_axis(gx_dense, x_idx, axis=1)


def spmm_dense_ref(adj, x):
    """Plain SpMM with a dense operand (the cuSPARSE-analogue baseline)."""
    return _dense(adj) @ x


def drspmm_learnable_ref(dst, src, n_dst: int, n_src: int, w, x_vals,
                         x_idx, dim: int):
    """Y = A(w) · dense(X_cbsr) for the learnable op: edge e of the
    canonical (dst-stable-sorted) COO ``dst``/``src`` carries weight
    ``w[e]``; differentiable in ``w`` and ``x_vals``."""
    a = jnp.zeros((n_dst, n_src), jnp.float32).at[dst, src].add(w)
    return a @ scatter_cbsr(x_vals, x_idx, dim)
