"""Plain jax.numpy reference of DeepGEN and its training step, written from
the layer equations (DeeperGCN res+ GENConv, arXiv 2006.07739, on the
cell/net graph).

It imports nothing of the program and takes nothing the program made: its
weights come from the seed by the initialisation the configuration file
states, its graphs from the traffic generator's COO edges.  Aggregation is
a gather and ``segment_max`` / ``segment_sum`` over the edges; every
matmul runs at ``highest`` precision; each layer is checkpointed, so only
one layer's per-edge tensors (E x H, about 250 MB at the large partitions)
are alive at a time.

For relation r from type s to type d, with u the layer's input:

    m_j    = relu(u_s[j]) + eps
    a_i    = sum_{j->i} softmax_j(t_r m_j) m_j        (per channel; 0 if
                                                      i has no in-edge)
    GEN_r  = W2 relu(LN(W1 (a_i + u_d[i]) + b1)) + b2
    y_cell = GEN_near + GEN_pinned,   y_net = GEN_pin

    h      = x_t W_in_t + b_in_t
    h      = GEN(h)                                   (layer 0)
    h      = h + GEN(relu(LN_t(h)))                   (layers 1..L-1)
    pred   = head(relu(LN_0(h_cell)))                 (3-layer MLP)

The loss is the mean squared error over cells; AdamW updates the weights.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference.model import adamw, pad_sizes

RELATIONS = {"near": ("cell", "cell"), "pinned": ("net", "cell"),
             "pin": ("cell", "net")}
CONV_KEYS = ("t", "w1", "b1", "ln_g", "ln_b", "w2", "b2")


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Weights by the scheme the configuration states (its ``init``)."""
    h, n_l = cfg["hidden"], cfg["n_layers"]
    h2 = cfg["mlp_expansion"] * h
    f_c, f_n = cfg["f_cell"], cfg["f_net"]

    def uni(k, shape, fan_in):
        s = 1.0 / jnp.sqrt(fan_in)
        return jax.random.uniform(k, shape, jnp.float32, -s, s)

    k_ic, k_in, k_layers, k_head = jax.random.split(
        jax.random.PRNGKey(seed), 4)
    z = lambda n: jnp.zeros((n,), jnp.float32)
    o = lambda n: jnp.ones((n,), jnp.float32)
    p = {"in_cell_w": uni(k_ic, (f_c, h), f_c), "in_cell_b": z(h),
         "in_net_w": uni(k_in, (f_n, h), f_n), "in_net_b": z(h)}
    for i, kl in enumerate(jax.random.split(k_layers, n_l)):
        for r, kr in zip(RELATIONS, jax.random.split(kl, 3)):
            k1, k2 = jax.random.split(kr)
            pre = f"layers.{i}.{r}."
            p.update({pre + "t": jnp.float32(cfg["t_init"]),
                      pre + "w1": uni(k1, (h, h2), h), pre + "b1": z(h2),
                      pre + "ln_g": o(h2), pre + "ln_b": z(h2),
                      pre + "w2": uni(k2, (h2, h), h2), pre + "b2": z(h)})
        for t in ("cell", "net"):
            p[f"layers.{i}.norm_{t}_g"] = o(h)
            p[f"layers.{i}.norm_{t}_b"] = z(h)
    kh = jax.random.split(k_head, 3)
    p.update({"head.w1": uni(kh[0], (h, h), h), "head.b1": z(h),
              "head.w2": uni(kh[1], (h, h), h), "head.b2": z(h),
              "head.w3": uni(kh[2], (h, 1), h), "head.b3": z(1)})
    return p


def graph_arrays(part: dict, sizes: dict) -> dict:
    """Padded host arrays of one partition.  Padded edges point at a spare
    destination row past the padded node count (dropped after the
    aggregation) and are masked; padded cells have a loss weight of 0."""
    g = {}
    n_pad = {"cell": sizes["n_cell"], "net": sizes["n_net"]}
    for et, (_src_t, dst_t) in RELATIONS.items():
        dst, src = part["coo"][et]
        pad = sizes["nnz"][et] - len(dst)
        g[f"{et}.dst"] = np.concatenate(
            [dst, np.full(pad, n_pad[dst_t])]).astype(np.int32)
        g[f"{et}.src"] = np.concatenate(
            [src, np.zeros(pad, np.int64)]).astype(np.int32)
        g[f"{et}.valid"] = np.concatenate(
            [np.ones(len(dst), bool), np.zeros(pad, bool)])
    nc, nn = part["n_cell"], part["n_net"]
    g["x_cell"] = np.pad(part["x_cell"], ((0, sizes["n_cell"] - nc), (0, 0)))
    g["x_net"] = np.pad(part["x_net"], ((0, sizes["n_net"] - nn), (0, 0)))
    g["y"] = np.pad(part["y"], (0, sizes["n_cell"] - nc))
    g["cell_w"] = np.pad(np.full(nc, 1.0 / nc, np.float32),
                         (0, sizes["n_cell"] - nc))
    return g


def aggregate(g, et: str, m, t, n_dst: int):
    """Softmax aggregation of relation ``et`` over its edges."""
    src, dst, valid = g[f"{et}.src"], g[f"{et}.dst"], g[f"{et}.valid"]
    x = m[src]
    z = t.astype(x.dtype) * x
    mx = jax.ops.segment_max(jnp.where(valid[:, None], z, -jnp.inf), dst,
                             num_segments=n_dst + 1)
    mx = jax.lax.stop_gradient(jnp.where(jnp.isfinite(mx), mx, 0.0))
    e = jnp.where(valid[:, None], jnp.exp(z - mx[dst]), 0.0)
    s = jax.ops.segment_sum(e, dst, num_segments=n_dst + 1)
    a = jax.ops.segment_sum(e * x, dst, num_segments=n_dst + 1)
    return jnp.where(s > 0, a / jnp.where(s > 0, s, 1.0), 0.0)[:n_dst]


def layer_norm(x, g, b, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def gen(lp: dict, g, u: dict, cfg: dict) -> dict:
    """One heterogeneous GENConv: {type: y} from the layer inputs ``u``."""
    eps = cfg["eps"]
    n = {t: v.shape[0] for t, v in u.items()}
    m = {t: jax.nn.relu(v) + eps for t, v in u.items()}
    y = {"cell": 0.0, "net": 0.0}
    for r, (s_t, d_t) in RELATIONS.items():
        x = aggregate(g, r, m[s_t], lp[f"{r}.t"], n[d_t]) + u[d_t]
        x = jnp.matmul(x, lp[f"{r}.w1"]) + lp[f"{r}.b1"]
        x = jax.nn.relu(layer_norm(x, lp[f"{r}.ln_g"], lp[f"{r}.ln_b"],
                                   cfg["layer_norm_eps"]))
        y[d_t] = y[d_t] + jnp.matmul(x, lp[f"{r}.w2"]) + lp[f"{r}.b2"]
    return y


def forward(p, g, cfg: dict, dtype=jnp.float32):
    """Per-cell prediction over the padded partition ``g``."""
    eps = cfg["layer_norm_eps"]
    lps = [{k[len(f"layers.{i}."):]: v for k, v in p.items()
            if k.startswith(f"layers.{i}.")} for i in range(cfg["n_layers"])]
    h = {t: jnp.matmul(g[f"x_{t}"].astype(dtype), p[f"in_{t}_w"])
         + p[f"in_{t}_b"] for t in ("cell", "net")}
    h = jax.checkpoint(lambda lp, h: gen(lp, g, h, cfg))(lps[0], h)

    @jax.checkpoint
    def block(lp, h):
        u = {t: jax.nn.relu(layer_norm(h[t], lp[f"norm_{t}_g"],
                                       lp[f"norm_{t}_b"], eps))
             for t in h}
        y = gen(lp, g, u, cfg)
        return {t: h[t] + y[t] for t in h}

    for lp in lps[1:]:
        h = block(lp, h)
    x = jax.nn.relu(layer_norm(h["cell"], lps[0]["norm_cell_g"],
                               lps[0]["norm_cell_b"], eps))
    x = jax.nn.relu(jnp.matmul(x, p["head.w1"]) + p["head.b1"])
    x = jax.nn.relu(jnp.matmul(x, p["head.w2"]) + p["head.b2"])
    return (jnp.matmul(x, p["head.w3"]) + p["head.b3"])[:, 0]


def loss(p, g, cfg: dict, dtype=jnp.float32, cell_w=None):
    pred = forward(p, g, cfg, dtype)
    w = g["cell_w"] if cell_w is None else cell_w
    return jnp.sum(w.astype(dtype) * (pred - g["y"].astype(dtype)) ** 2)


def make_step(cfg: dict, precision: str = "float32",
              fault: Optional[str] = None):
    """Jitted (params, m, v, step, graph) -> (params, m, v, loss, grads).

    ``precision`` is ``"float32"`` (matmuls at ``highest``: the reference)
    or ``"bfloat16"``, the control one step below the float32 the
    configuration states (weights cast from float32 masters, inputs,
    messages and aggregation in bfloat16, matmuls at the default
    precision); AdamW stays float32.  ``fault="half_batch"`` takes the
    loss over the first half of the cells only, ``fault="unchanged"``
    returns the state it was given: planted faults, as in
    ``reference/model.py``."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32

    def loss_of(p, g):
        pc = {k: v.astype(dtype) for k, v in p.items()}
        cw = g["cell_w"]
        if fault == "half_batch":
            n = cw.shape[0]
            real = (cw > 0).astype(jnp.float32)
            keep = real * (jnp.arange(n) < jnp.sum(real) // 2)
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
    """Run len(parts) steps, one per partition, from the seed's weights;
    returns ``losses``, ``grad1`` and ``delta`` as ``reference/model.py``
    does, keyed per layer (``layers.<i>.<relation>.<name>``)."""
    sizes = pad_sizes(parts)
    p0 = init_params(cfg, w_seed)
    p = dict(p0)
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    step = make_step(cfg, precision, fault)
    losses, grad1 = [], None
    for t, part in enumerate(parts, start=1):
        g = {k: jnp.asarray(a) for k, a in graph_arrays(part, sizes).items()}
        p, m, v, lval, _grads = step(p, m, v, jnp.float32(t), g)
        losses.append(float(lval))
        if grad1 is None:
            grad1 = {k: np.asarray(x) / (1.0 - cfg["adam_b1"])
                     for k, x in m.items()}
    delta = {k: np.asarray(p[k]) - np.asarray(p0[k]) for k in p}
    return dict(losses=losses, grad1=grad1, delta=delta)
