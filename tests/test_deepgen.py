"""DeepGEN (models/deepgen.py) and its softmax aggregation
(``ops.softmax_aggr_multi``) against plain oracles on the CPU.

* The op, on its XLA twin and on the Pallas kernels in interpret mode,
  against a segment-softmax oracle: forward, and gradients for the
  features and for every relation's temperature; with destinations that
  have no in-edge, heavy-tailed degrees up to 260 (rows spanning many
  chunks, so the kernel's running max and sums cross chunks), and t·m
  large enough that an unshifted exp would overflow.
* The whole model against the benchmark's plain reference
  (bench/reference/deepgen.py) on seeded weights: loss, every gradient
  leaf and one AdamW step.
* The trace-time dispatch counter, and that the trainer's default is still
  the DR model.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hetero_mp import HeteroMPConfig
from repro.graphs.circuit import with_plan
from repro.graphs.ell import build_relation_plan
from repro.graphs.generator import pack_graph_parallel
from repro.kernels import ops
from repro.models import deepgen as D
from repro.models import hgnn
from repro.obs.metrics import DEFAULT_REGISTRY
from repro.optim import adamw_init, adamw_update
from repro.train.circuit_trainer import CircuitTrainConfig, CircuitTrainer

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import generator as G                      # noqa: E402
from reference import deepgen as R         # noqa: E402

BACKENDS = ("xla_fused", "pallas_fused")   # pallas interprets on the CPU
SCHEMA = {"near": ("cell", "cell"), "pin": ("cell", "net"),
          "pinned": ("net", "cell")}


def _relation(rng, n_dst, n_src, deg):
    """Unique (dst, src) edges with the given in-degree per destination."""
    dst = np.repeat(np.arange(n_dst), deg)
    src = np.concatenate([rng.choice(n_src, d, replace=False) for d in deg])
    return dst, src


def _graph(case: str, seed: int = 0):
    rng = np.random.default_rng(seed)
    n = {"cell": 300, "net": 40}
    if case == "heavy_tail":      # lognormal bulk, a few rows at 260
        deg = np.clip(rng.lognormal(np.log(8), 0.8, n["cell"]), 0, 260)
        deg = deg.astype(int)
        deg[rng.choice(n["cell"], 4, replace=False)] = 260
    else:                         # a third of the rows, and a block, empty
        deg = rng.integers(0, 12, n["cell"])
        deg[rng.random(n["cell"]) < 0.33] = 0
        deg[16:32] = 0
    near = _relation(rng, n["cell"], n["cell"], deg)
    pin = _relation(rng, n["net"], n["cell"], rng.integers(0, 6, n["net"]))
    coo = {"near": near, "pin": pin, "pinned": (pin[1], pin[0])}
    scale, t = (40.0, (3.0, 2.5, 4.0)) if case == "overflow" \
        else (1.0, (1.3, 0.6, -0.4))
    x = {tp: jnp.asarray(rng.normal(size=(k, 8)) * scale, jnp.float32)
         for tp, k in n.items()}
    return coo, n, x, dict(zip(SCHEMA, map(jnp.float32, t)))


def _oracle(coo, n, x, t):
    out = {}
    for et, (s_t, d_t) in SCHEMA.items():
        dst, src = coo[et]
        m = jax.nn.relu(x[s_t]) + ops.GEN_EPS
        z = t[et] * m[src]
        mx = jax.lax.stop_gradient(jax.ops.segment_max(z, dst, n[d_t]))
        e = jnp.exp(z - jnp.where(jnp.isfinite(mx), mx, 0.0)[dst])
        s = jax.ops.segment_sum(e, dst, n[d_t])
        a = jax.ops.segment_sum(e * m[src], dst, n[d_t])
        out[et] = jnp.where(s > 0, a / jnp.where(s > 0, s, 1.0), 0.0)
    return out


def _probe(outs):
    """A scalar that weighs every output element differently."""
    return sum(jnp.sum(jnp.sin(outs[et] * (i + 1.0)))
               for i, et in enumerate(sorted(outs)))


@pytest.mark.parametrize("tier", ["arena", "mixed"])
@pytest.mark.parametrize("case", ["empty_rows", "heavy_tail", "overflow"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_softmax_aggr_matches_oracle(backend, case, tier):
    """``mixed`` puts the small relations in the plan's dense tier."""
    coo, n, x, t = _graph(case)
    tiers = {"arena": "arena", "mixed": "dense"}
    plan = build_relation_plan(
        [(et, s_t, d_t, coo[et][0], coo[et][1],
          np.ones(len(coo[et][0]), np.float32))
         for et, (s_t, d_t) in SCHEMA.items()], n,
        tiers={"near": "arena", "pin": tiers[tier], "pinned": tiers[tier]})
    assert plan.has_arena and plan.has_dense == (tier == "mixed")
    f = jax.jit(lambda x, t: ops.softmax_aggr_multi(plan, x, t,
                                                    backend=backend))
    got, want = f(x, t), _oracle(coo, n, x, t)
    deg0 = np.bincount(coo["near"][0], minlength=n["cell"]) == 0
    assert deg0.any() and np.all(np.asarray(got["near"])[deg0] == 0.0)
    for et in SCHEMA:
        assert np.all(np.isfinite(np.asarray(got[et])))
        np.testing.assert_allclose(got[et], want[et], rtol=2e-5,
                                   atol=2e-5 * float(jnp.max(want[et])))
    grad = lambda fn: jax.jit(jax.grad(lambda x, t: _probe(fn(x, t)),
                                       argnums=(0, 1)))(x, t)
    (gx, gt) = grad(lambda x, t: ops.softmax_aggr_multi(plan, x, t,
                                                        backend=backend))
    (wx, wt) = grad(lambda x, t: _oracle(coo, n, x, t))
    for tp in n:
        np.testing.assert_allclose(gx[tp], wx[tp], rtol=1e-3,
                                   atol=1e-4 * float(jnp.max(jnp.abs(
                                       wx[tp]))))
    for et in SCHEMA:
        # a sum over every edge and channel: compare on its own scale
        scale = float(jnp.sum(jnp.abs(_oracle(coo, n, x, t)[et]))) + 1.0
        assert abs(float(gt[et]) - float(wt[et])) <= 1e-5 * scale, \
            (et, float(gt[et]), float(wt[et]))


CFG = dict(hidden=16, n_layers=3, mlp_expansion=2, f_cell=16, f_net=16,
           eps=1e-7, t_init=1.0, layer_norm_eps=1e-5, lr=1e-3,
           weight_decay=0.0, adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8)


@pytest.fixture(scope="module")
def small_partition():
    part = G.generate_partition(np.random.default_rng(3), 160, 70)
    g = pack_graph_parallel(part["coo"], part["n_cell"], part["n_net"],
                            part["x_cell"], part["x_net"], part["y"])
    return part, with_plan(g)


def _per_layer(params):
    """The program's weights as the reference's keys."""
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = ".".join(p.name for p in path)
        if key.startswith("layers."):
            for i, v in enumerate(leaf):
                flat[f"layers.{i}.{key[len('layers.'):]}"] = np.asarray(v)
        else:
            flat[key] = np.asarray(leaf)
    return flat


@pytest.mark.parametrize("backend", BACKENDS)
def test_model_matches_reference(backend, small_partition):
    part, g = small_partition
    seed = 11
    params = D.init_deepgen(jax.random.PRNGKey(seed), 16, 16, 16, 3)
    p_ref = R.init_params(CFG, seed)
    flat = _per_layer(params)
    assert set(flat) == set(p_ref)
    for k in p_ref:                         # the same weights from the seed
        np.testing.assert_array_equal(flat[k], p_ref[k], err_msg=k)

    cfg = HeteroMPConfig(hidden=16, backend=backend)
    loss, grads = jax.jit(jax.value_and_grad(D.loss_fn), static_argnums=2)(
        params, g, cfg)
    rg = {k: jnp.asarray(v) for k, v in
          R.graph_arrays(part, R.pad_sizes([part])).items()}
    with jax.default_matmul_precision("highest"):
        r_loss, r_grads = jax.jit(jax.value_and_grad(
            lambda p, g: R.loss(p, g, CFG)))(p_ref, rg)
    np.testing.assert_allclose(float(loss), float(r_loss), rtol=1e-5)
    g_flat = _per_layer(grads)
    norm = np.median([np.linalg.norm(v) for v in r_grads.values()])
    for k, v in r_grads.items():
        np.testing.assert_allclose(g_flat[k], v, rtol=1e-3,
                                   atol=1e-5 * norm, err_msg=k)

    new_p, _ = adamw_update(params, grads, adamw_init(params), CFG["lr"],
                            weight_decay=CFG["weight_decay"])
    zeros = {k: jnp.zeros_like(v) for k, v in p_ref.items()}
    r_new, _, _ = R.adamw(p_ref, r_grads, zeros, zeros, 1, CFG)
    n_flat = _per_layer(new_p)
    for k, v in r_new.items():
        # a first AdamW step moves each weight by ~lr·sign(g): compare the
        # change, whose scale is lr, not the weight's
        np.testing.assert_allclose(n_flat[k] - flat[k],
                                   np.asarray(v) - np.asarray(p_ref[k]),
                                   atol=CFG["lr"] * 1e-2, err_msg=k)


@pytest.mark.parametrize("n_layers", [1, 4])
def test_one_fwd_and_one_bwd_dispatch_per_traced_layer(n_layers,
                                                       small_partition):
    """``mp.gen_aggr_dispatches`` counts kernel launches as they are traced:
    per traced layer body one ``gen_aggr_fwd`` over the whole
    direction-group in the forward and one ``gen_aggr_bwd`` in the
    backward.  Layers 1..L-1 are one scanned body, traced once whatever
    the depth: one body for 1 layer, two (layer 0, the scan's) deeper."""
    _part, g = small_partition
    params = D.init_deepgen(jax.random.PRNGKey(0), 16, 16, 16, n_layers)
    cfg = HeteroMPConfig(hidden=16, backend="pallas_fused")
    count = lambda d: DEFAULT_REGISTRY.value("mp.gen_aggr_dispatches", dir=d)
    bodies = 1 if n_layers == 1 else 2
    before = count("fwd"), count("bwd")
    jax.make_jaxpr(D.loss_fn, static_argnums=2)(params, g, cfg)
    assert (count("fwd") - before[0], count("bwd") - before[1]) == \
        (bodies, 0)
    before = count("bwd")
    jax.make_jaxpr(jax.grad(D.loss_fn), static_argnums=2)(params, g, cfg)
    assert count("bwd") - before == bodies


def test_trainer_default_is_the_dr_model():
    tr = CircuitTrainer(CircuitTrainConfig(), 16, 16)
    assert isinstance(tr.params, hgnn.DRCircuitGNNParams)
    want = hgnn.init_drcircuitgnn(jax.random.PRNGKey(0), 16, 16, 64, 2)
    for a, b in zip(jax.tree.leaves(tr.params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    assert tr._loss_fn is hgnn.loss_fn
    assert tr._batched_loss_fn is hgnn.batched_loss_fn


def test_trainer_trains_deepgen_on_the_plan(small_partition):
    _part, g = small_partition
    tr = CircuitTrainer(CircuitTrainConfig(model="deepgen", hidden=16,
                                           n_layers=3, lr=1e-3), 16, 16)
    assert isinstance(tr.params, D.DeepGENParams)
    losses = [tr.train_epoch([g]) for _ in range(4)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert tr.stats()["plan_uploads"] == 1
    with pytest.raises(ValueError, match="unknown model"):
        CircuitTrainer(CircuitTrainConfig(model="gat"), 16, 16)


def test_collated_batch_gradient_is_the_member_mean():
    """A collated batch (the collator's own plan, padded arenas) gives the
    mean of the members' gradients, as for the DR model."""
    from repro.graphs.collate import collate_graphs
    from repro.graphs.generator import generate_design
    graphs = generate_design(0, "small", scale=0.03)
    params = D.init_deepgen(jax.random.PRNGKey(2), 16, 16, 16, 3)
    cfg = HeteroMPConfig(hidden=16, backend="xla_fused")
    b = collate_graphs(graphs)
    got = jax.grad(D.batched_loss_fn)(params, b.graph, b.cell_weight, cfg)
    each = [jax.grad(D.loss_fn)(params, with_plan(g), cfg) for g in graphs]
    want = jax.tree.map(lambda *x: sum(x) / len(x), *each)
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-6)
