"""Plain jax.numpy reference of the heterogeneous circuit GNN and its
training step, written from the layer equations.

It imports nothing of the program and takes nothing the program made: it
builds its weights from the seed by the initialisation scheme that the
configuration file states, and its graphs from the traffic generator's COO
edges.  Message passing is a gather and a ``segment_sum`` over the edges,
D-ReLU is a dense top-k threshold, and every matmul runs at ``highest``
precision.

One hetero layer (DR-CircuitGNN, arXiv 2508.16769, Fig. 1):

    s_t       = D-ReLU_k(h_t)                      (top-k of each row kept)
    a_near    = A_near   s_cell                    (mean over in-neighbours)
    a_pinned  = A_pinned s_net
    a_pin     = A_pin    s_cell
    y_cell    = max(a_near W_near + h_cell W_near_self,
                    a_pinned W_pinned + h_cell W_pinned_self) + b_cell
    y_net     = a_pin W_pin + b_net
    h'        = D-ReLU_k(y)                        (the inter-layer activation)

The model is ``h_t = x_t W_in_t``, the layers, and ``sigmoid(h_cell W_head +
b_head)``; the loss is the mean squared error over cells, and AdamW updates
the weights.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

LAYER_KEYS = ("w_near", "w_near_self", "w_pinned", "w_pinned_self", "w_pin")


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Weights by the scheme the configuration states: one key per group,
    split in the order (in_cell, in_net, layer 0..L-1, head); each layer key
    split in five for its weight matrices; uniform(-1/sqrt(fan_in),
    1/sqrt(fan_in)) weights, zero biases."""
    h, n_l = cfg["hidden"], cfg["n_layers"]
    f_c, f_n = cfg["f_cell"], cfg["f_net"]
    ks = jax.random.split(jax.random.PRNGKey(seed), n_l + 3)

    def uni(k, shape, fan_in):
        s = 1.0 / jnp.sqrt(fan_in)
        return jax.random.uniform(k, shape, jnp.float32, -s, s)

    p = {"in_cell": uni(ks[0], (f_c, h), f_c),
         "in_net": uni(ks[1], (f_n, h), f_n)}
    for i in range(n_l):
        lk = jax.random.split(ks[2 + i], 5)
        for j, name in enumerate(LAYER_KEYS):
            p[f"layers.{i}.{name}"] = uni(lk[j], (h, h), h)
        p[f"layers.{i}.b_cell"] = jnp.zeros((h,), jnp.float32)
        p[f"layers.{i}.b_net"] = jnp.zeros((h,), jnp.float32)
    p["head_w"] = uni(ks[2 + n_l], (h, 1), h)
    p["head_b"] = jnp.zeros((1,), jnp.float32)
    return p


def pad_sizes(parts: List[dict]) -> dict:
    """One padded shape for a list of partitions, rounded up so that seeds
    of one traffic mix share it (one compile)."""
    n_c = _round_up(max(p["n_cell"] for p in parts), 1024)
    n_n = _round_up(max(p["n_net"] for p in parts), 1024)
    nnz = {et: _round_up(max(len(p["coo"][et][0]) for p in parts), 1 << 15)
           for et in ("near", "pin", "pinned")}
    return dict(n_cell=n_c, n_net=n_n, nnz=nnz)


def graph_arrays(part: dict, sizes: dict) -> dict:
    """Padded host arrays of one partition.  Edge weights are the mean
    normalisation 1/in-degree of the destination; padded edges have weight
    0 and padded cells a loss weight of 0."""
    n_of = {"cell": part["n_cell"], "net": part["n_net"]}
    dst_t = {"near": "cell", "pin": "net", "pinned": "cell"}
    g = {}
    for et, (dst, src) in part["coo"].items():
        deg = np.bincount(dst, minlength=n_of[dst_t[et]]).astype(np.float32)
        w = (1.0 / np.maximum(deg[dst], 1.0)).astype(np.float32)
        m = sizes["nnz"][et]
        pad = m - len(dst)
        g[f"{et}.dst"] = np.concatenate([dst, np.zeros(pad, np.int64)]).astype(np.int32)
        g[f"{et}.src"] = np.concatenate([src, np.zeros(pad, np.int64)]).astype(np.int32)
        g[f"{et}.w"] = np.concatenate([w, np.zeros(pad, np.float32)])
    nc, nn = part["n_cell"], part["n_net"]
    g["x_cell"] = np.pad(part["x_cell"], ((0, sizes["n_cell"] - nc), (0, 0)))
    g["x_net"] = np.pad(part["x_net"], ((0, sizes["n_net"] - nn), (0, 0)))
    g["y"] = np.pad(part["y"], (0, sizes["n_cell"] - nc))
    g["cell_w"] = np.pad(np.full(nc, 1.0 / nc, np.float32),
                         (0, sizes["n_cell"] - nc))
    return g


def drelu(x, k: int):
    """Keep each row's k largest entries, zero the rest; the gradient flows
    to the kept entries only."""
    if k >= x.shape[-1]:
        return x
    th = jax.lax.top_k(x, k)[0][:, -1:]
    return jnp.where(x >= th, x, jnp.zeros_like(x))


def _agg(g, et, s, n_dst: int):
    msg = s[g[f"{et}.src"]] * g[f"{et}.w"][:, None].astype(s.dtype)
    return jax.ops.segment_sum(msg, g[f"{et}.dst"], num_segments=n_dst)


def forward(p, g, cfg: dict, dtype=jnp.float32):
    """Per-cell prediction over the padded partition ``g``."""
    n_c, n_n = g["x_cell"].shape[0], g["x_net"].shape[0]
    kc, kn = cfg["k_cell"], cfg["k_net"]
    mm = jnp.matmul
    h_c = mm(g["x_cell"].astype(dtype), p["in_cell"])
    h_n = mm(g["x_net"].astype(dtype), p["in_net"])
    for i in range(cfg["n_layers"]):
        lp = lambda name: p[f"layers.{i}.{name}"]
        s_c, s_n = drelu(h_c, kc), drelu(h_n, kn)
        a_near = _agg(g, "near", s_c, n_c)
        a_pinned = _agg(g, "pinned", s_n, n_c)
        a_pin = _agg(g, "pin", s_c, n_n)
        y_c = jnp.maximum(
            mm(a_near, lp("w_near")) + mm(h_c, lp("w_near_self")),
            mm(a_pinned, lp("w_pinned")) + mm(h_c, lp("w_pinned_self"))
        ) + lp("b_cell")
        y_n = mm(a_pin, lp("w_pin")) + lp("b_net")
        h_c, h_n = drelu(y_c, kc), drelu(y_n, kn)
    return jax.nn.sigmoid(mm(h_c, p["head_w"]) + p["head_b"])[:, 0]


def loss(p, g, cfg: dict, dtype=jnp.float32, cell_w=None):
    pred = forward(p, g, cfg, dtype)
    w = g["cell_w"] if cell_w is None else cell_w
    return jnp.sum(w.astype(dtype) * (pred - g["y"].astype(dtype)) ** 2)


def adamw(p, grads, m, v, step, cfg: dict):
    """AdamW with the configuration's constants (float32); ``step`` counts
    from 1."""
    b1, b2, eps = cfg["adam_b1"], cfg["adam_b2"], cfg["adam_eps"]
    lr, wd = cfg["lr"], cfg["weight_decay"]
    bc1 = 1.0 - b1 ** step
    bc2 = 1.0 - b2 ** step
    new_p, new_m, new_v = {}, {}, {}
    for k in p:
        g = grads[k].astype(jnp.float32)
        new_m[k] = b1 * m[k] + (1 - b1) * g
        new_v[k] = b2 * v[k] + (1 - b2) * g * g
        delta = (new_m[k] / bc1) / (jnp.sqrt(new_v[k] / bc2) + eps) \
            + wd * p[k]
        new_p[k] = p[k] - lr * delta
    return new_p, new_m, new_v


def make_step(cfg: dict, precision: str = "float32", fault: Optional[str] = None):
    """Jitted (params, m, v, step, graph) -> (params, m, v, loss, grads).

    ``precision`` is ``"float32"`` (matmuls at ``highest``: the
    reference) or ``"bfloat16"``, the control one step below the float32
    the configuration states (weights cast from float32 masters, inputs and
    messages in bfloat16, matmuls at the default precision).  AdamW stays
    float32.
    ``fault="half_batch"`` takes the loss over the first half of the cells
    only, and ``fault="unchanged"`` returns the state it was given: planted
    faults, for reading what they do to the compared numbers."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32

    def loss_of(p, g):
        pc = {k: v.astype(dtype) for k, v in p.items()}
        cw = g["cell_w"]
        if fault == "half_batch":
            n = cw.shape[0]
            real = (cw > 0).astype(jnp.float32)
            n_real = jnp.sum(real)
            keep = real * (jnp.arange(n) < n_real // 2)
            cw = keep / jnp.sum(keep)
        return loss(pc, g, cfg, dtype, cw).astype(jnp.float32)

    def step(p, m, v, t, g):
        if precision == "bfloat16":
            lval, grads = jax.value_and_grad(loss_of)(p, g)
        else:
            with jax.default_matmul_precision("highest"):
                lval, grads = jax.value_and_grad(loss_of)(p, g)
        grads = {k: x.astype(jnp.float32) for k, x in grads.items()}
        if fault == "unchanged":
            return p, m, v, lval, grads
        p2, m2, v2 = adamw(p, grads, m, v, t, cfg)
        return p2, m2, v2, lval, grads

    return jax.jit(step)


def train_steps(cfg: dict, parts: List[dict], w_seed: int,
                precision: str = "float32",
                fault: Optional[str] = None) -> dict:
    """Run len(parts) steps, one per partition, from the seed's weights.

    Returns host arrays: ``losses`` (one per step), ``grad1`` (the first
    step's gradient per leaf, read back from the first moment as the
    program's is: m1 / (1 - b1)), ``delta`` (weights after the last step
    minus the initial weights, per leaf)."""
    sizes = pad_sizes(parts)
    p0 = init_params(cfg, w_seed)
    p = dict(p0)
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    step = make_step(cfg, precision, fault)
    losses, grad1 = [], None
    for t, part in enumerate(parts, start=1):
        g = {k: jnp.asarray(a) for k, a in graph_arrays(part, sizes).items()}
        p, m, v, lval, grads = step(p, m, v, jnp.float32(t), g)
        losses.append(float(lval))
        if grad1 is None:
            grad1 = {k: np.asarray(x) / (1.0 - cfg["adam_b1"])
                     for k, x in m.items()}
    delta = {k: np.asarray(p[k]) - np.asarray(p0[k]) for k in p}
    return dict(losses=losses, grad1=grad1, delta=delta)
