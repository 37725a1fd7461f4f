"""Work a DeepGEN training step requires, counted from exact shapes.

As in ``work.py``, counts come from the graph's exact edge counts (nnz per
relation), its row counts and the hidden width H, never from arena slots
or padding, so they read the same whatever implements the kernels.

Softmax aggregation (``gen_aggr``), one forward and one backward call per
layer over the three relations, per relation:

* forward: each edge gathers its source's H float32 messages; each
  destination row writes its H outputs and H log-normalisers.
  Operations per edge and channel: t·m, the running max, the shift, the
  exp, the sum and the weighted sum's multiply-add: 7.
* backward: each edge gathers its destination's cotangent, output and
  log-normaliser (3·H float32); each source row reads its H messages and
  writes its H gradients.  Operations per edge and channel: the shift
  (2), the exp, g·p, m − a, 1 + t(m − a) (2), its product, the sum, and
  the temperature term's two products and sum: 12.

Step FLOPs (for ``mfu``): the input projections, per layer and relation
the GENConv MLP (Linear(H, 2H) and Linear(2H, H) on the destination
rows), and the 3-layer head, forward and backward.  A matmul's backward
costs twice its forward, except the input projections', whose inputs need
no gradient.  Aggregation, LayerNorm and other elementwise work are not
counted.
"""

from __future__ import annotations

from typing import Dict

RELATIONS = {"near": ("cell", "cell"), "pin": ("cell", "net"),
             "pinned": ("net", "cell")}
FWD_OPS, BWD_OPS = 7, 12


def gen_aggr_calls(shape: dict, cfg: dict):
    """[(name, ops, bytes)] of the aggregation calls one step requires."""
    h = cfg["hidden"]
    n = {"cell": shape["n_cell"], "net": shape["n_net"]}
    f_ops = f_bytes = b_ops = b_bytes = 0
    for et, (s_t, d_t) in RELATIONS.items():
        nnz = shape["nnz"][et]
        f_ops += FWD_OPS * nnz * h
        f_bytes += nnz * h * 4 + n[d_t] * 2 * h * 4
        b_ops += BWD_OPS * nnz * h
        b_bytes += nnz * 3 * h * 4 + n[s_t] * 2 * h * 4
    calls = []
    for layer in range(cfg["n_layers"]):
        calls.append((f"fwd{layer}", f_ops, f_bytes))
        calls.append((f"bwd{layer}", b_ops, b_bytes))
    return calls


def gen_aggr_least_s(shape: dict, cfg: dict, peak: dict) -> Dict[str, float]:
    """Least seconds for a step's aggregation calls on a chip with
    ``peak``: per call the larger of operations over peak FLOP/s and bytes
    over peak HBM bandwidth; also which bound applied."""
    least = compute = memory = 0.0
    for _name, ops, by in gen_aggr_calls(shape, cfg):
        t_c, t_m = ops / peak["flops_bf16"], by / peak["hbm_bytes_per_s"]
        least += max(t_c, t_m)
        compute += t_c
        memory += t_m
    return dict(least_s=least, compute_s=compute, memory_s=memory,
                bound="memory" if memory >= compute else "compute")


def step_flops(shape: dict, cfg: dict) -> int:
    """Forward plus backward matmul FLOPs one training step requires."""
    h, h2 = cfg["hidden"], cfg["mlp_expansion"] * cfg["hidden"]
    n = {"cell": shape["n_cell"], "net": shape["n_net"]}
    proj = 2 * h * (n["cell"] * cfg["f_cell"] + n["net"] * cfg["f_net"])
    mlps = sum(2 * n[d_t] * (h * h2 + h2 * h)
               for _s, d_t in RELATIONS.values())
    head = 2 * n["cell"] * (h * h + h * h + h)
    fwd = proj + cfg["n_layers"] * mlps + head
    return fwd + proj + 2 * (cfg["n_layers"] * mlps + head)
