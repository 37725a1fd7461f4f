"""The work counters against hand counts on a tiny graph."""

import numpy as np
import pytest

import work

CFG = dict(hidden=8, n_layers=1, k_cell=2, k_net=2, f_cell=4, f_net=4)


@pytest.fixture
def shape():
    coo = {"near": (np.array([0, 0, 1, 2]), np.array([1, 2, 0, 1])),
           "pin": (np.array([0, 0, 1]), np.array([0, 1, 2])),
           "pinned": (np.array([0, 1, 2]), np.array([0, 0, 1]))}
    return work.shape_of(dict(coo=coo, n_cell=3, n_net=2))


def test_drspmm_calls_hand_counted(shape):
    calls = dict((n, (f, b)) for n, f, b in work.drspmm_calls(shape, CFG))
    # forward: 2·nnz·k = 2·10·2; per edge 2k·4 + 8 bytes, per output row
    # hidden·4 (near 3 cells, pin 2 nets, pinned 3 cells)
    assert calls["fwd0"] == (40, 10 * 24 + (3 + 2 + 3) * 32)
    # backward: per edge k·4 + 8, per source row k indices in and k grads
    # out (near 3 cells, pin 3 cells, pinned 2 nets)
    assert calls["bwd0"] == (40, 10 * 16 + (3 + 3 + 2) * 16)


def test_least_time_and_bound(shape):
    peak = dict(flops_bf16=1e3, hbm_bytes_per_s=1e3)
    r = work.drspmm_least_s(shape, CFG, peak)
    assert r["least_s"] == pytest.approx(0.496 + 0.288)
    assert r["bound"] == "memory"


def test_step_flops_hand_counted(shape):
    proj = 2 * 3 * 4 * 8 + 2 * 2 * 4 * 8
    merges = 2 * 8 * 8 * (4 * 3 + 2)
    head = 2 * 3 * 8
    spmm = 40
    fwd = proj + merges + spmm + head
    bwd = proj + 2 * (merges + head) + spmm
    assert work.step_flops(shape, CFG) == fwd + bwd == 6240


def test_counts_scale_with_layers(shape):
    two = dict(CFG, n_layers=2)
    assert len(work.drspmm_calls(shape, two)) == 4
    assert work.drspmm_least_s(shape, two, dict(
        flops_bf16=1e3, hbm_bytes_per_s=1e3))["least_s"] == \
        pytest.approx(2 * (0.496 + 0.288))
