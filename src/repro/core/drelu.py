"""Dynamic ReLU (D-ReLU) — row-wise top-k thresholding activation.

Implements Eqs. (2)-(3) of the paper:

    th_i = min(top_k(X_i, k))
    f(X_id) = X_id  if X_id >= th_i  else 0

plus the CBSR encoding of the survivors.  Unlike plain ReLU (irregular
sparsity) or FATReLU (fixed threshold, irregular sparsity), D-ReLU yields
*exactly* k survivors per row, which is what makes the downstream SpMM
workload balanced.

The VJP is straight-through on survivors: dX = dY at kept positions, 0
elsewhere — identical to the subgradient of the piecewise-linear f.  The
threshold's dependence on X is ignored exactly like the kink of ReLU.

Heterogeneous usage: each node type phi_s gets its own k (k_cell, k_net), and
the per-edge-type K-value profile (Sec. 4.3) is handled by
:func:`profile_optimal_k`.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.cbsr import CBSR, cbsr_from_dense


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _drelu_dense(x: jax.Array, k: int) -> jax.Array:
    """Dense-in dense-out D-ReLU over the last axis (Eq. 3 semantics)."""
    th = _row_threshold(x, k)
    return jnp.where(x >= th[..., None], x, jnp.zeros_like(x))


def _row_threshold(x: jax.Array, k: int) -> jax.Array:
    vals, _ = jax.lax.top_k(x, min(k, x.shape[-1]))
    return vals[..., -1]


def _drelu_fwd(x, k):
    th = _row_threshold(x, k)
    keep = x >= th[..., None]
    return jnp.where(keep, x, jnp.zeros_like(x)), keep


def _drelu_bwd(k, res, g):
    keep = res
    return (jnp.where(keep, g, jnp.zeros_like(g)),)


_drelu_dense.defvjp(_drelu_fwd, _drelu_bwd)


def drelu(x: jax.Array, k: int) -> jax.Array:
    """Dense D-ReLU: keep the top-``k`` entries of each row, zero the rest."""
    if k >= x.shape[-1]:
        return x
    with jax.named_scope("drelu"):
        return _drelu_dense(x, k)


def drelu_grouped(x: jax.Array, k: int, groups: int) -> jax.Array:
    """Sharding-local D-ReLU: split the row into ``groups`` contiguous
    blocks and keep the top-(k/groups) of each block.

    Still exactly k survivors per row (the paper's balanced-sparsity
    invariant) but the threshold is per-block, so when the feature dim is
    tensor-sharded the top-k never crosses shard boundaries — a global-top-k
    on a model-sharded FFN hidden would all-gather the full activation
    (measured: 12.9 GB × 2/layer on qwen3-0.6b train_4k).  TPU adaptation
    recorded in DESIGN.md §2; ablation in tests/test_drelu.py.
    """
    f = x.shape[-1]
    if k >= f:
        return x
    if groups <= 1 or f % groups or k % groups:
        return _drelu_dense(x, k)
    lead = x.shape[:-1]
    xg = x.reshape(lead + (groups, f // groups))
    from repro.sharding.specs import constrain
    xg = constrain(xg, (("batch",) + (None,) * (len(lead) - 1)
                        + ("mlp", None)))
    out = _drelu_dense(xg, k // groups)
    return out.reshape(lead + (f,))


def drelu_cbsr(x: jax.Array, k: int) -> CBSR:
    """D-ReLU returning the CBSR encoding (values + indices) directly.

    This is the form consumed by DR-SpMM; indices are preserved for the
    backward pass (Alg. 1 stage 4 / Alg. 2 stage 1).
    """
    return cbsr_from_dense(x, k)


def drelu_cbsr_vjp(x: jax.Array, k: int) -> Tuple[CBSR, jax.Array]:
    """CBSR output plus the dense keep-mask (for building custom VJPs)."""
    c = cbsr_from_dense(x, k)
    th = _row_threshold(x, min(k, x.shape[-1]))
    keep = x >= th[:, None]
    return c, keep


# ---------------------------------------------------------------------------
# K-value profiling (Sec. 4.3): candidate K's are powers of two below the
# embedding dim; the optimal K per subgraph trades kernel speed against
# information kept.  On CPU we cannot wall-clock a TPU kernel, so the profiler
# scores candidates with the kernel's roofline byte model: bytes moved scale
# with k, and tail lag scales with the max-degree bucket's padded width.
# ---------------------------------------------------------------------------

def candidate_ks(dim: int) -> Tuple[int, ...]:
    ks = []
    k = 2
    while k <= dim:
        ks.append(k)
        k *= 2
    return tuple(ks)


def kernel_cost_model(n_rows: int, nnz: int, k: int, dim: int,
                      max_degree: int, mean_degree: float) -> float:
    """Roofline byte-model of one DR-SpMM call (lower is better).

    bytes ≈ gather traffic (nnz rows of (k values + k idx)) + output write
    + a tail-lag penalty proportional to the evil-row imbalance, which the
    degree-bucketed dispatch reduces by the paper's partition factor
    (larger k ⇒ fewer rows co-resident per block ⇒ worse tail absorption).
    """
    gather = float(nnz) * k * (4 + 4)
    out = float(n_rows) * dim * 4
    imbalance = max(max_degree / max(mean_degree, 1.0) - 1.0, 0.0)
    tail = imbalance * k * n_rows * 4.0 / 32.0
    return gather + out + tail


def profile_optimal_k(degrees, dim: int, quality_floor: int = 2) -> int:
    """Pick the cost-minimal candidate K for one subgraph (one edge type).

    ``degrees`` is the integer degree array of destination rows.  Mirrors the
    paper's preprocessing profiler: exhaustive over powers of two, one-time
    cost per dataset.
    """
    import numpy as np

    deg = np.asarray(degrees)
    nnz = int(deg.sum())
    n = int(deg.size)
    maxd = int(deg.max()) if n else 1
    meand = float(deg.mean()) if n else 1.0
    best_k, best_c = quality_floor, float("inf")
    for k in candidate_ks(dim):
        c = kernel_cost_model(n, nnz, k, dim, maxd, meand)
        if c < best_c:
            best_c, best_k = c, k
    return max(best_k, quality_floor)


def hetero_k_values(graph_stats: Dict[str, Dict], dim_by_ntype: Dict[str, int]
                    ) -> Dict[str, int]:
    """Per-edge-type K values from per-subgraph degree stats.

    ``graph_stats[etype] = {"degrees": np.ndarray, "src_type": str}``.
    """
    out = {}
    for etype, st in graph_stats.items():
        dim = dim_by_ntype[st["src_type"]]
        out[etype] = profile_optimal_k(st["degrees"], dim)
    return out
