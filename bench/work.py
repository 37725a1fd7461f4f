"""Work a training step requires, counted from exact shapes.

Counts come from the graph's exact edge counts (nnz per relation), its row
counts, K and the hidden width, never from arena slots or padding, so they
read the same whatever implements the kernels.

DR-SpMM, one call per direction over the three relations of a layer:

* forward, per relation: each edge gathers its source row's k CBSR values
  and k column indices (4 B each), reads its source id and weight (4 B
  each); each destination row writes ``hidden`` float32 outputs.
  FLOPs: a multiply-add per kept value per edge, 2·nnz·k.
* sampled backward, per relation: each edge reads k sampled cotangent
  values of its destination (4 B each) and its id and weight; each source
  row reads its k indices and writes k gradient values.  FLOPs: 2·nnz·k.

A step runs one forward and one backward per layer.  Rematerialised
forwards are not required work and are not counted.

Step FLOPs (for ``mfu``): the input projections, per layer the five merge
matmuls and the DR-SpMM calls, and the head, forward and backward.  A
matmul's backward costs twice its forward (input and weight gradients),
except the input projections, whose inputs need no gradient.  Elementwise
work (D-ReLU, max, bias, sigmoid, AdamW) is not counted.
"""

from __future__ import annotations

from typing import Dict

RELATIONS = {"near": ("cell", "cell"), "pin": ("cell", "net"),
             "pinned": ("net", "cell")}


def shape_of(part: dict) -> dict:
    """The exact sizes of one partition that the counts need."""
    return dict(n_cell=int(part["n_cell"]), n_net=int(part["n_net"]),
                nnz={et: int(len(part["coo"][et][0])) for et in RELATIONS})


def drspmm_calls(shape: dict, cfg: dict):
    """[(name, flops, bytes)] of the DR-SpMM calls one step requires."""
    h = cfg["hidden"]
    k = {"cell": cfg["k_cell"], "net": cfg["k_net"]}
    n = {"cell": shape["n_cell"], "net": shape["n_net"]}
    calls = []
    for layer in range(cfg["n_layers"]):
        f_flops = f_bytes = b_flops = b_bytes = 0
        for et, (s_t, d_t) in RELATIONS.items():
            nnz, kk = shape["nnz"][et], k[s_t]
            f_flops += 2 * nnz * kk
            f_bytes += nnz * (2 * kk * 4 + 8) + n[d_t] * h * 4
            b_flops += 2 * nnz * kk
            b_bytes += nnz * (kk * 4 + 8) + n[s_t] * kk * 4 * 2
        calls.append((f"fwd{layer}", f_flops, f_bytes))
        calls.append((f"bwd{layer}", b_flops, b_bytes))
    return calls


def drspmm_least_s(shape: dict, cfg: dict, peak: dict) -> Dict[str, float]:
    """Least seconds for a step's DR-SpMM calls on a chip with ``peak``:
    per call the larger of FLOPs over peak FLOP/s and bytes over peak HBM
    bandwidth.  Also says which bound applied."""
    least = compute = memory = 0.0
    for _name, fl, by in drspmm_calls(shape, cfg):
        t_c, t_m = fl / peak["flops_bf16"], by / peak["hbm_bytes_per_s"]
        least += max(t_c, t_m)
        compute += t_c
        memory += t_m
    return dict(least_s=least, compute_s=compute, memory_s=memory,
                bound="memory" if memory >= compute else "compute")


def step_flops(shape: dict, cfg: dict) -> int:
    """Forward plus backward FLOPs one training step requires."""
    h = cfg["hidden"]
    nc, nn = shape["n_cell"], shape["n_net"]
    proj = 2 * nc * cfg["f_cell"] * h + 2 * nn * cfg["f_net"] * h
    merges = 2 * h * h * (4 * nc + nn)          # near, near_self, pinned,
    head = 2 * nc * h                            # pinned_self, pin
    spmm = sum(fl for name, fl, _ in drspmm_calls(shape, cfg)
               if name.startswith("fwd"))
    fwd = proj + cfg["n_layers"] * merges + spmm + head
    bwd = proj + 2 * (cfg["n_layers"] * merges + head) + spmm
    return fwd + bwd
