"""The DeepGEN cell on the CPU at a small size: its files resolve by name,
its run is ``correct``, planted faults and the bfloat16 control are not,
a program without the model is refused at once, and ``work_deepgen``
counts exact work on a hand-checked graph."""

import json
import os
import sys

import numpy as np
import pytest

import cell
import control_deepgen
import work_deepgen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "deepgen15-large-resident"
# 3% of the partitions, 3 of the 15 layers, the XLA twin of the kernels
SMALL = {"traffic": {"scale": 0.03},
         "config": {"backend": "xla_fused", "n_layers": 3}}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_cell_files_resolve_by_name():
    wl = _load("workloads", f"{CELL}.json")
    cfg = _load("configs", f"{wl['config']}.json")
    traffic = _load("traffic", f"{wl['traffic']}.json")
    assert (cfg["hidden"], cfg["n_layers"], cfg["mlp_expansion"],
            cfg["proj_n_layers"], cfg["reduced"]) == (128, 15, 2, 3, [])
    # the keys the train_deepgen driver reads; no D-ReLU top-k here
    for key in ("mlp_expansion", "eps", "t_init", "layer_norm_eps", "init"):
        assert key in cfg
    assert (cfg["k_cell"], cfg["k_net"]) == (None, None)
    assert traffic["kind"] == "train_deepgen" and traffic["pack"] == "once"
    resident = _load("traffic", "large_resident.json")
    assert (traffic["pool"], traffic["structure_seed"]) == \
        (resident["pool"], resident["structure_seed"])
    assert os.path.isfile(os.path.join(BENCH, "drivers", "train_deepgen.py"))
    assert set(wl["limits"]) == {"loss_gap", "grad_gap", "change_gap"}


def test_small_run_is_correct():
    r = cell.run_cell(CELL, 2 ** 33 + 5, 0.3, False, require_chip=False,
                      overrides=SMALL)
    assert r["correct"] is True and r["attempted"] >= 1
    assert r["checks"]["loss_gap"]["value"] < 1e-5


def _unchanged(monkeypatch):
    monkeypatch.setattr("repro.train.circuit_trainer.adamw_update",
                        lambda params, grads, state, lr, **kw: (params, state))


def _half_batch(monkeypatch):
    import jax.numpy as jnp
    from repro.models.deepgen import deepgen_forward

    def half_loss(params, graph, cfg, spec=None):
        pred = deepgen_forward(params, graph, cfg, spec)
        n = pred.shape[0] // 2
        return jnp.mean((pred[:n] - graph.y_cell[:n]) ** 2)

    monkeypatch.setattr("repro.models.deepgen.loss_fn", half_loss)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = cell.run_cell(CELL, 37, 0.3, False, require_chip=False,
                      overrides=SMALL)
    assert r["correct"] is False


def test_control_and_faults_fail_a_limit():
    limits = _load("workloads", f"{CELL}.json")["limits"]
    out = control_deepgen.readings_for(11, SMALL)
    for name in ("control", "half_batch", "unchanged"):
        ok, _rows = cell.judge(out[name], limits)
        assert not ok, (name, out[name])


def test_program_without_the_model_is_refused(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.models.deepgen", None)
    driver = cell.load_driver("train_deepgen")
    with pytest.raises(SystemExit, match="no DeepGEN model"):
        driver.run(BENCH, {}, {}, 1, 1.0, None, 0.0, log=lambda _m: None)


CFG = dict(hidden=4, n_layers=1, mlp_expansion=2, f_cell=3, f_net=2)


@pytest.fixture
def shape():
    # near 4 edges (cell -> cell), pin 3 (cell -> net), pinned 3 (net -> cell)
    return dict(n_cell=3, n_net=2, nnz={"near": 4, "pin": 3, "pinned": 3})


def test_gen_aggr_calls_hand_counted(shape):
    calls = dict((n, (o, b)) for n, o, b in
                 work_deepgen.gen_aggr_calls(shape, CFG))
    # 10 edges; forward: 7 ops per edge and channel, a 4-float row per edge
    # in, 2 rows of 4 floats out per destination (near 3, pin 2, pinned 3)
    assert calls["fwd0"] == (7 * 10 * 4, 10 * 16 + 8 * 32)
    # backward: 12 ops, 3 rows per edge in, 1 row in and 1 out per source
    # (near 3 cells, pin 3 cells, pinned 2 nets)
    assert calls["bwd0"] == (12 * 10 * 4, 10 * 48 + 8 * 32)
    r = work_deepgen.gen_aggr_least_s(
        shape, dict(CFG, n_layers=2), dict(flops_bf16=1e3,
                                           hbm_bytes_per_s=1e3))
    assert r["least_s"] == pytest.approx(2 * (0.416 + 0.736))
    assert r["bound"] == "memory"


def test_step_flops_hand_counted(shape):
    proj = 2 * 4 * (3 * 3 + 2 * 2)
    mlps = 2 * (3 + 2 + 3) * (4 * 8 + 8 * 4)   # near, pin, pinned dsts
    head = 2 * 3 * (16 + 16 + 4)
    fwd = proj + mlps + head
    assert work_deepgen.step_flops(shape, CFG) == fwd + proj + 2 * (mlps
                                                                   + head)
