"""Deep-backbone machinery (DESIGN.md §13): spec/wiring/remat semantics,
remat-vs-not numeric parity, executor-cache hygiene under recompute, the
per-layer CBSR hoist, init RNG parity with the pre-backbone code, and the
serve engine's multi-tenant head registry."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hetero_mp import (HeteroMPConfig, _sparsify_types,
                                  init_hetero_layer)
from repro.graphs.collate import collate_graphs, graph_signature
from repro.graphs.generator import generate_design
from repro.kernels import ops
from repro.models.backbone import (BackboneSpec, apply_stack, init_stack,
                                   spec_for)
from repro.models.hgnn import (drcircuitgnn_forward, init_drcircuitgnn,
                               loss_fn)
from repro.serve.circuit_engine import CircuitServeEngine
from repro.train.circuit_trainer import CircuitTrainConfig, CircuitTrainer

CFG = HeteroMPConfig(hidden=32, k_cell=8, k_net=8)


@pytest.fixture(scope="module")
def graph():
    return generate_design(3, "small", scale=0.03)[0]


def _params(graph, depth, hidden=32, seed=0):
    return init_drcircuitgnn(jax.random.PRNGKey(seed),
                             graph.x_cell.shape[1], graph.x_net.shape[1],
                             hidden, n_layers=depth)


# ---------------------------------------------------------------- spec


def test_spec_validates_wiring():
    with pytest.raises(ValueError, match="wiring"):
        BackboneSpec(wiring="helix")


def test_apply_stack_depth_mismatch():
    spec = BackboneSpec(depth=3, hidden=4)
    with pytest.raises(ValueError, match="depth"):
        apply_stack((None,), 0.0, lambda lp, s, c: s, spec)


# ------------------------------------------------- remat numeric parity


def test_remat_parity_deep(graph):
    """Remat is a rematerialization schedule, not a different program:
    loss AND every grad leaf agree with the plain stack at depth 8."""
    depth = 8
    params = _params(graph, depth)
    outs = {}
    for remat in (False, True):
        spec = BackboneSpec(depth=depth, hidden=32, remat=remat)
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, graph, CFG, spec))(params)
        outs[remat] = (float(loss), grads)
    assert np.isclose(outs[True][0], outs[False][0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(outs[True][1]),
                    jax.tree.leaves(outs[False][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_remat_trained_params_parity(graph):
    """Two trainers differing ONLY in remat converge to allclose params
    (and both report the device's peak memory)."""
    trained, stats = {}, {}
    for remat in (False, True):
        cfg = CircuitTrainConfig(epochs=2, hidden=32, k_cell=8, k_net=8,
                                 n_layers=8, remat=remat)
        tr = CircuitTrainer(cfg, graph.x_cell.shape[1],
                            graph.x_net.shape[1])
        for _ in range(2):
            tr.train_epoch([graph])
        trained[remat] = tr.params
        stats[remat] = tr.stats()
    for a, b in zip(jax.tree.leaves(trained[True]),
                    jax.tree.leaves(trained[False])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    assert stats[True]["peak_memory_bytes"] > 0


SERIAL_CFGS = {
    "no_plan": dict(use_plan=False),
    "dense_baseline": dict(use_drelu=False),
    "k_at_hidden": dict(k_cell=32, k_net=32),
}


@pytest.mark.parametrize("serial", sorted(SERIAL_CFGS))
def test_remat_parity_serial_path(graph, serial):
    """The serial per-relation path under remat: the checkpoint traces the
    concrete graph, so the forward fuses its packings before the stack.
    Loss and grads agree with the plain stack on the same config."""
    cfg = dataclasses.replace(CFG, **SERIAL_CFGS[serial])
    params = _params(graph, 3)
    outs = {}
    for remat in (False, True):
        spec = BackboneSpec(depth=3, hidden=32, remat=remat)
        outs[remat] = jax.value_and_grad(
            lambda p: loss_fn(p, graph, cfg, spec))(params)
    assert np.isclose(float(outs[True][0]), float(outs[False][0]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(outs[True][1]),
                    jax.tree.leaves(outs[False][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("serial", ["no_plan", "dense_baseline"])
def test_remat_trainer_evaluate_serial_path(graph, serial):
    """``evaluate`` runs the un-jitted forward on a concrete graph; with
    remat on a serial-path config it must match the remat-free trainer."""
    metrics = {}
    for remat in (False, True):
        cfg = CircuitTrainConfig(hidden=32, k_cell=8, k_net=8, n_layers=2,
                                 remat=remat, **SERIAL_CFGS[serial])
        tr = CircuitTrainer(cfg, graph.x_cell.shape[1],
                            graph.x_net.shape[1])
        metrics[remat] = tr.evaluate([graph])
    assert metrics[True].keys() == metrics[False].keys()
    for k in metrics[False]:
        assert np.isclose(metrics[True][k], metrics[False][k],
                          rtol=1e-4, atol=1e-6), k


# ----------------------------------------------------------- wiring


def test_residual_depth1_degenerate(graph):
    """Skips start at the SECOND layer, so every wiring is bit-identical
    to plain at depth 1."""
    params = _params(graph, 1)
    ref = np.asarray(drcircuitgnn_forward(
        params, graph, CFG, BackboneSpec(depth=1, hidden=32)))
    for wiring in ("residual", "dense"):
        got = np.asarray(drcircuitgnn_forward(
            params, graph, CFG,
            BackboneSpec(depth=1, hidden=32, wiring=wiring)))
        np.testing.assert_array_equal(got, ref, err_msg=wiring)


def test_wiring_changes_deep_forward(graph):
    """At depth 3 the skip wirings are real different functions."""
    params = _params(graph, 3)
    preds = {w: np.asarray(drcircuitgnn_forward(
        params, graph, CFG, BackboneSpec(depth=3, hidden=32, wiring=w)))
        for w in ("plain", "residual", "dense")}
    assert np.abs(preds["residual"] - preds["plain"]).max() > 1e-6
    assert np.abs(preds["dense"] - preds["residual"]).max() > 1e-6


def test_residual_wiring_grads_flow(graph):
    """Residual stacks train: grads reach the FIRST layer and are not
    degenerate at depth 8 (the wiring's reason to exist)."""
    params = _params(graph, 8)
    spec = BackboneSpec(depth=8, hidden=32, wiring="residual", remat=True)
    grads = jax.grad(lambda p: loss_fn(p, graph, CFG, spec))(params)
    g0 = np.concatenate([np.asarray(x).ravel()
                         for x in jax.tree.leaves(grads.layers[0])])
    assert np.abs(g0).max() > 0


# ------------------------------------------------- executor-cache hygiene


def test_remat_no_retrace(graph):
    """Checkpoint bodies always trace, so remat must route around the
    id-keyed executor LRU (ops._MULTI_EXE) — recompute cannot thrash or
    grow it — and the jitted step compiles exactly once."""
    params = _params(graph, 4)
    drcircuitgnn_forward(params, graph, CFG)      # concrete warm-up entry
    n0 = len(ops._MULTI_EXE)
    assert n0 > 0
    spec = BackboneSpec(depth=4, hidden=32, remat=True)
    step = jax.jit(jax.grad(lambda p: loss_fn(p, graph, CFG, spec)))
    step(params)
    jax.block_until_ready(step(params))
    assert len(ops._MULTI_EXE) == n0
    if callable(getattr(step, "_cache_size", None)):
        assert step._cache_size() == 1


# ------------------------------------------------------ CBSR hoist


def _count_topk(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "top_k":
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_topk(sub)
    return n


def test_cbsr_shared_per_type_dispatch_count(graph):
    """The serial path sparsifies each node type ONCE per layer (near and
    pin both read the cell slab): total top_k work is depth × (one
    two-type sparsification) + one per inter-layer D-ReLU pair — not the
    3-per-layer of re-deriving CBSR per relation."""
    cfg = HeteroMPConfig(hidden=32, k_cell=8, k_net=8, use_plan=False)
    depth = 3
    params = _params(graph, depth)
    x_cell = jnp.zeros((graph.n_cell, 32))
    x_net = jnp.zeros((graph.n_net, 32))
    per_layer = _count_topk(jax.make_jaxpr(
        lambda a, b: _sparsify_types(a, b, cfg))(x_cell, x_net).jaxpr)
    act = _count_topk(jax.make_jaxpr(
        lambda a, b: (jax.tree.map(lambda v: v, a), b))(x_cell, x_net).jaxpr)
    assert act == 0 and per_layer > 0
    # the inter-layer activation is D-ReLU too: one more two-type pass
    from repro.core.drelu import drelu
    act_pair = _count_topk(jax.make_jaxpr(
        lambda a, b: (drelu(a, 8), drelu(b, 8)))(x_cell, x_net).jaxpr)
    spec = BackboneSpec(depth=depth, hidden=32)
    total = _count_topk(jax.make_jaxpr(
        lambda p: drcircuitgnn_forward(p, graph, cfg, spec))(params).jaxpr)
    assert total == depth * (per_layer + act_pair), \
        (total, depth, per_layer, act_pair)


# ------------------------------------------------------ init parity


def test_init_stack_rng_parity():
    """init_drcircuitgnn's RNG stream is pinned to the pre-backbone split
    pattern: split(key, L+3) with inputs at ks[0:2], layer i at ks[2+i],
    head at ks[-1]."""
    key, hidden, fc, fn, L = jax.random.PRNGKey(42), 16, 8, 12, 4
    p = init_drcircuitgnn(key, fc, fn, hidden, n_layers=L)
    ks = jax.random.split(key, L + 3)
    s_c = 1.0 / jnp.sqrt(fc)
    np.testing.assert_array_equal(
        np.asarray(p.in_cell),
        np.asarray(jax.random.uniform(ks[0], (fc, hidden), jnp.float32,
                                      -s_c, s_c)))
    for i in range(L):
        ref = init_hetero_layer(ks[2 + i], hidden)
        for a, b in zip(p.layers[i], ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s_h = 1.0 / jnp.sqrt(hidden)
    np.testing.assert_array_equal(
        np.asarray(p.head_w),
        np.asarray(jax.random.uniform(ks[-1], (hidden, 1), jnp.float32,
                                      -s_h, s_h)))


def test_init_stack_key_layout():
    pre, layers, post = init_stack(jax.random.PRNGKey(1), 3,
                                   lambda k, i: (i, k), n_pre=2, n_post=1)
    assert len(pre) == 2 and len(layers) == 3 and len(post) == 1
    assert [i for i, _ in layers] == [0, 1, 2]
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    np.testing.assert_array_equal(np.asarray(layers[0][1]),
                                  np.asarray(ks[2]))


# ------------------------------------------- signatures are data-only


def test_signature_depth_independent(graph):
    """Batch/bucket signatures depend on the DATA alone — depth, wiring,
    and remat never enter, so flipping the backbone can't invalidate
    collated layouts."""
    sigs = []
    for n_layers, remat, wiring in ((2, False, "plain"),
                                    (15, True, "dense")):
        cfg = CircuitTrainConfig(hidden=32, k_cell=8, k_net=8,
                                 n_layers=n_layers, remat=remat,
                                 wiring=wiring)
        tr = CircuitTrainer(cfg, graph.x_cell.shape[1],
                            graph.x_net.shape[1])
        sigs.append(graph_signature(tr._planned(graph)))
    assert sigs[0] == sigs[1]
    assert (collate_graphs([graph, graph]).signature
            == collate_graphs([graph, graph]).signature)


# -------------------------------------------------- head registry


def test_head_registry_shares_backbone_zero_compiles(graph):
    """Two named heads + the default share ONE backbone and ONE compiled
    executable per (signature, device): serving all three costs exactly
    one compile, selection is per request, and results match calling the
    forward with that head's weights directly."""
    params = _params(graph, 3)
    spec = BackboneSpec(depth=3, hidden=32, wiring="residual")
    eng = CircuitServeEngine(params, CFG, spec=spec, max_batch=2)
    hw_a = jax.random.uniform(jax.random.PRNGKey(7), params.head_w.shape,
                              jnp.float32, -0.2, 0.2)
    eng.register_head("taskA", hw_a)
    eng.register_head("taskB", -hw_a, params.head_b + 0.5)
    assert eng.heads == ("taskA", "taskB")

    rids = {h: eng.submit(graph, head=h) for h in (None, "taskA", "taskB")}
    eng.run()
    preds = {h: eng.result(r).pred for h, r in rids.items()}
    st = eng.stats()
    assert st["requests"] == 3
    assert st["compiles"] == 1, st["compiles"]   # heads share the compile

    # per-request selection really happened
    assert np.abs(preds["taskA"] - preds["taskB"]).max() > 1e-3
    assert np.abs(preds["taskA"] - preds[None]).max() > 1e-3
    ref = np.asarray(drcircuitgnn_forward(
        params._replace(head_w=hw_a), graph, CFG, spec))
    np.testing.assert_allclose(preds["taskA"], ref, atol=1e-5)

    # unknown heads bounce at the door; bad shapes bounce at registration
    with pytest.raises(KeyError, match="unknown head"):
        eng.submit(graph, head="nope")
    with pytest.raises(ValueError, match="shapes"):
        eng.register_head("bad", jnp.zeros((7, 1)))


def test_head_registry_survives_update_params(graph):
    """update_params swaps the backbone+default head but leaves registered
    heads (independent replicas) serving — still zero new compiles for a
    same-bucket stream."""
    params = _params(graph, 2, seed=0)
    eng = CircuitServeEngine(params, CFG, max_batch=1)
    hw = jax.random.uniform(jax.random.PRNGKey(9), params.head_w.shape,
                            jnp.float32, -0.3, 0.3)
    eng.register_head("fixed", hw)
    r0 = eng.submit(graph, head="fixed")
    eng.run()
    before = eng.result(r0).pred
    c0 = eng.stats()["compiles"]

    eng.update_params(_params(graph, 2, seed=1))
    assert eng.heads == ("fixed",)
    r1 = eng.submit(graph, head="fixed")
    r2 = eng.submit(graph)
    eng.run()
    after = eng.result(r1).pred
    default_after = eng.result(r2).pred
    assert eng.stats()["compiles"] == c0        # swap + heads: no compiles
    # new backbone under the same registered head -> different features
    assert np.abs(after - before).max() > 1e-6
    assert np.abs(after - default_after).max() > 1e-6
