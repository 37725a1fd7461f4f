"""Quickstart: D-ReLU + DR-SpMM on a toy heterogeneous circuit graph.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cbsr import cbsr_from_dense
from repro.core.drelu import drelu, profile_optimal_k
from repro.graphs.generator import generate_design
from repro.kernels import ops, ref

# 1. a synthetic CircuitNet-like partition (cell/net nodes; near/pin/pinned)
graph = generate_design(seed=0, size="small", scale=0.05)[0]
print(f"graph: {graph.n_cell} cells, {graph.n_net} nets, "
      f"edge types: {list(graph.edges)}")

# 2. D-ReLU: balanced row sparsification of the cell embeddings
rng = np.random.default_rng(0)
x_cell = jnp.asarray(rng.normal(size=(graph.n_cell, 64)).astype(np.float32))
k = 16
x_sparse = drelu(x_cell, k)
print(f"D-ReLU(k={k}): nnz per row =",
      np.unique(np.asarray((x_sparse != 0).sum(1))))

# 3. CBSR encoding (values + indices — the kernel operand)
c = cbsr_from_dense(x_sparse, k)
print("CBSR:", c.values.shape, c.idx.shape)

# 4. DR-SpMM over the 'near' adjacency (the Pallas arena kernels,
#    interpreted on CPU)
es = graph.edges["near"]
y = ops.drspmm(es.adj, es.adj_t, c.values, c.idx, 64, backend="pallas_fused")
y_ref = ref.drspmm_fwd_ref(es.adj, c.values, c.idx, 64)
print("DR-SpMM max|err| vs dense oracle:",
      float(jnp.abs(y - y_ref).max()))

# 5. gradient flows through the sampled backward (SSpMM)
g = jax.grad(lambda v: jnp.sum(ops.drspmm(es.adj, es.adj_t, v, c.idx,
                                          64) ** 2))(c.values)
print("SSpMM grad shape:", g.shape, "finite:", bool(jnp.isfinite(g).all()))

# 6. the profiler picks K per edge type (Sec. 4.3)
from repro.graphs.circuit import graph_degree_stats  # noqa: E402
deg = np.asarray((es.adj.to_dense() != 0).sum(1))
print("profiled optimal K for 'near':", profile_optimal_k(deg, 64))
