"""DeepGEN: DeeperGCN's ``res+`` GENConv stack on the heterogeneous
circuit graph (circuit-fewshot's ``DeepGENNet``; Li et al., "DeeperGCN:
All You Need to Train Deeper GCNs", arXiv 2006.07739).

One GENConv per relation r from type s to type d (PyG ``to_hetero``: one
conv per relation, summed per destination type):

    m_j   = relu(u_s[j]) + eps
    a_i   = Σ_{j→i} softmax_j(t_r · m_j) ⊙ m_j        (per channel)
    GEN_r = MLP_r(a_i + u_d[i])
    MLP   = Linear(H, 2H) → LayerNorm → ReLU → Linear(2H, H)

    y_cell = GEN_near + GEN_pinned,  y_net = GEN_pin

Stack (res+): per-type input Linear; layer 0 is the conv alone; layers
1..L-1 are ``h ← h + GEN(relu(LN_t(h)))``; then ``relu(LN_0(h))`` and a
3-layer per-cell MLP head.  The aggregation runs over the graph's
:class:`~repro.graphs.ell.RelationPlan` (``ops.softmax_aggr_multi``: one
``gen_aggr_fwd`` / ``gen_aggr_bwd`` per layer); layers 1..L-1 are ONE
``lax.scan`` over depth-stacked parameters, so the compiled program does
not grow with depth.  Dense matmuls run at ``Precision.HIGHEST``.
DESIGN.md §15.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.hetero_mp import HeteroMPConfig
from repro.graphs.circuit import CircuitGraph, relation_plan_of
from repro.kernels import ops
from repro.models.backbone import BackboneSpec

RELATIONS = ("near", "pinned", "pin")     # init order of a layer's convs
LN_EPS = 1e-5
_HI = jax.lax.Precision.HIGHEST


class GENConvParams(NamedTuple):
    """One relation's GENConv: softmax temperature and its MLP."""
    t: jax.Array          # ()
    w1: jax.Array         # (H, 2H)
    b1: jax.Array         # (2H,)
    ln_g: jax.Array       # (2H,)
    ln_b: jax.Array       # (2H,)
    w2: jax.Array         # (2H, H)
    b2: jax.Array         # (H,)


class GENLayerParams(NamedTuple):
    """One res+ block: a GENConv per relation and its per-type LayerNorm
    (layer 0's norm is the one after the stack)."""
    near: GENConvParams
    pinned: GENConvParams
    pin: GENConvParams
    norm_cell_g: jax.Array
    norm_cell_b: jax.Array
    norm_net_g: jax.Array
    norm_net_b: jax.Array


class HeadParams(NamedTuple):
    w1: jax.Array         # (H, H)
    b1: jax.Array
    w2: jax.Array         # (H, H)
    b2: jax.Array
    w3: jax.Array         # (H, 1)
    b3: jax.Array


class DeepGENParams(NamedTuple):
    in_cell_w: jax.Array  # (f_cell, H)
    in_cell_b: jax.Array
    in_net_w: jax.Array   # (f_net, H)
    in_net_b: jax.Array
    layers: GENLayerParams   # every leaf stacked over depth (leading L)
    head: HeadParams


def _uniform(key, shape, fan_in: int):
    s = 1.0 / jnp.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -s, s)


def init_deepgen(key, f_cell: int, f_net: int, hidden: int,
                 n_layers: int = 15) -> DeepGENParams:
    """Weights uniform(±1/sqrt(fan_in)), biases and LayerNorm shifts zero,
    LayerNorm scales and temperatures one.  ``key`` splits into (in_cell,
    in_net, layers, head); the layers key into one key per layer, each
    into one per relation (near, pinned, pin), each into (w1, w2); the
    head key into (w1, w2, w3)."""
    h, h2 = hidden, 2 * hidden
    k_ic, k_in, k_layers, k_head = jax.random.split(key, 4)
    zeros, ones = jnp.zeros, jnp.ones

    def conv(k):
        k1, k2 = jax.random.split(k)
        return GENConvParams(
            t=jnp.float32(1.0), w1=_uniform(k1, (h, h2), h), b1=zeros((h2,)),
            ln_g=ones((h2,)), ln_b=zeros((h2,)), w2=_uniform(k2, (h2, h), h2),
            b2=zeros((h,)))

    def layer(k):
        convs = dict(zip(RELATIONS, map(conv, jax.random.split(k, 3))))
        return GENLayerParams(**convs, norm_cell_g=ones((h,)),
                              norm_cell_b=zeros((h,)), norm_net_g=ones((h,)),
                              norm_net_b=zeros((h,)))

    layers = [layer(k) for k in jax.random.split(k_layers, n_layers)]
    kh = jax.random.split(k_head, 3)
    return DeepGENParams(
        in_cell_w=_uniform(k_ic, (f_cell, h), f_cell), in_cell_b=zeros((h,)),
        in_net_w=_uniform(k_in, (f_net, h), f_net), in_net_b=zeros((h,)),
        layers=jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
        head=HeadParams(w1=_uniform(kh[0], (h, h), h), b1=zeros((h,)),
                        w2=_uniform(kh[1], (h, h), h), b2=zeros((h,)),
                        w3=_uniform(kh[2], (h, 1), h), b3=zeros((1,))))


def _dense(x, w, b):
    return jnp.dot(x, w, precision=_HI) + b


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _mlp(p: GENConvParams, x):
    with jax.named_scope("gen_mlp"):
        y = jax.nn.relu(_layer_norm(_dense(x, p.w1, p.b1), p.ln_g, p.ln_b))
        return _dense(y, p.w2, p.b2)


def gen_conv(lp: GENLayerParams, plan, u_cell, u_net, cfg: HeteroMPConfig):
    """One heterogeneous GENConv: (y_cell, y_net) from the layer inputs."""
    aggs = ops.softmax_aggr_multi(
        plan, {"cell": u_cell, "net": u_net},
        {r: getattr(lp, r).t for r in RELATIONS}, backend=cfg.backend)
    y_cell = _mlp(lp.near, aggs["near"] + u_cell) \
        + _mlp(lp.pinned, aggs["pinned"] + u_cell)
    return y_cell, _mlp(lp.pin, aggs["pin"] + u_net)


def deepgen_forward(params: DeepGENParams, graph: CircuitGraph,
                    cfg: HeteroMPConfig,
                    spec: Optional[BackboneSpec] = None) -> jax.Array:
    """Per-cell prediction.  The graph's attached plan is used, or one is
    built for a concrete graph.  ``spec`` is the trainer's calling
    convention and is not read: depth comes from ``params``, the wiring is
    DeepGEN's own res+, and remat is off."""
    plan = graph.plan if graph.plan is not None else relation_plan_of(graph)
    layers = params.layers
    first = jax.tree.map(lambda x: x[0], layers)
    h_cell = _dense(graph.x_cell, params.in_cell_w, params.in_cell_b)
    h_net = _dense(graph.x_net, params.in_net_w, params.in_net_b)
    with jax.named_scope("gen_layer"):
        h_cell, h_net = gen_conv(first, plan, h_cell, h_net, cfg)

    def block(h, lp: GENLayerParams):
        with jax.named_scope("gen_layer"):
            u_cell = jax.nn.relu(_layer_norm(h[0], lp.norm_cell_g,
                                             lp.norm_cell_b))
            u_net = jax.nn.relu(_layer_norm(h[1], lp.norm_net_g,
                                            lp.norm_net_b))
            y_cell, y_net = gen_conv(lp, plan, u_cell, u_net, cfg)
            return (h[0] + y_cell, h[1] + y_net), None

    if layers.near.t.shape[0] > 1:
        (h_cell, _), _ = jax.lax.scan(block, (h_cell, h_net),
                                      jax.tree.map(lambda x: x[1:], layers))
    h = jax.nn.relu(_layer_norm(h_cell, first.norm_cell_g,
                                first.norm_cell_b))
    hd = params.head
    with jax.named_scope("head"):
        h = jax.nn.relu(_dense(h, hd.w1, hd.b1))
        h = jax.nn.relu(_dense(h, hd.w2, hd.b2))
        return _dense(h, hd.w3, hd.b3)[:, 0]


def loss_fn(params, graph, cfg, spec: Optional[BackboneSpec] = None):
    pred = deepgen_forward(params, graph, cfg, spec)
    with jax.named_scope("loss"):
        return jnp.mean((pred - graph.y_cell) ** 2)


def batched_loss_fn(params, graph, cell_weight, cfg,
                    spec: Optional[BackboneSpec] = None):
    """Loss over a block-diagonal collated batch, as
    ``models.hgnn.batched_loss_fn``."""
    pred = deepgen_forward(params, graph, cfg, spec)
    with jax.named_scope("loss"):
        return jnp.sum(cell_weight * (pred - graph.y_cell) ** 2)
