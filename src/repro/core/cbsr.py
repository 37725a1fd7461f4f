"""CBSR — Compressed Balanced Sparse Row format.

The paper's D-ReLU produces *balanced* row sparsity: every row of a node
embedding matrix keeps exactly ``k`` non-zeros.  On GPU the paper stores the
survivors as per-row (values, indices) pairs; on TPU the balanced property is
the entire win — it means the compressed representation is a pair of *dense,
statically-shaped* arrays:

    values : (N, k) float   — surviving magnitudes, ordered by column index
    idx    : (N, k) int32   — column positions of the survivors

Static shapes make CBSR directly tileable into VMEM by a Pallas BlockSpec and
let the scatter back to dense be expressed as a one-hot matmul on the MXU.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CBSR:
    """A row-balanced sparse matrix: exactly ``k`` nnz per row.

    ``dim`` is the dense column count (static); ``values``/``idx`` are
    ``(N, k)``.  Rows are allowed to contain duplicate index ``0`` entries with
    zero value as padding (produced when a row has fewer than ``k`` finite
    survivors); all consumers accumulate, so zero-valued padding is inert.
    """

    values: jax.Array
    idx: jax.Array
    dim: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_rows(self) -> int:
        return self.values.shape[0]

    @property
    def k(self) -> int:
        return self.values.shape[1]

    def to_dense(self) -> jax.Array:
        """Scatter back to a dense (N, dim) matrix."""
        n, _ = self.values.shape
        rows = jnp.arange(n, dtype=jnp.int32)[:, None]
        out = jnp.zeros((n, self.dim), self.values.dtype)
        # ``add`` (not ``set``): tolerates zero-value padding duplicates.
        return out.at[rows, self.idx].add(self.values)


def cbsr_from_dense(x: jax.Array, k: int) -> CBSR:
    """Compress a dense matrix by keeping the top-``k`` entries of each row.

    Survivor columns are re-sorted ascending so gathers walk memory forward —
    the TPU analogue of the paper's CBSR index ordering.
    """
    n, d = x.shape
    k = min(k, d)
    with jax.named_scope("cbsr"):
        vals, idx = jax.lax.top_k(x, k)  # descending by value
        order = jnp.argsort(idx, axis=1)
        idx = jnp.take_along_axis(idx, order, axis=1).astype(jnp.int32)
        vals = jnp.take_along_axis(vals, order, axis=1)
    return CBSR(values=vals, idx=idx, dim=d)


def cbsr_mask(c: CBSR) -> jax.Array:
    """Dense 0/1 mask of surviving positions (used by the max-merge backward)."""
    n = c.n_rows
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    m = jnp.zeros((n, c.dim), jnp.bool_)
    return m.at[rows, c.idx].set(True)


def sample_dense(dense: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather ``dense`` at CBSR positions: out[i, j] = dense[i, idx[i, j]].

    This is the SSpMM sampling step of the backward pass (Alg. 2): gradients
    are only needed at positions D-ReLU let through.
    """
    return jnp.take_along_axis(dense, idx, axis=1)


def scatter_cbsr(values: jax.Array, idx: jax.Array, dim: int) -> jax.Array:
    """Dense (N, dim) from loose (values, idx) pairs."""
    return CBSR(values=values, idx=idx, dim=dim).to_dense()
