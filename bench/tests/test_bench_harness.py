"""The harness on the CPU at a small size: no result without a chip; the
reference against itself and against the program; the control comes out
not correct."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import cell
import control
import generator as G
from reference import compare
from reference import model as R

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SMALL = {"traffic": {"scale": 0.03}, "config": {"backend": "xla_fused"}}
CELLS = ("drcgnn-large-resident", "drcgnn-table1-stream")


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "cell.py"), "--workload",
         "drcgnn-large-resident", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_reference_loss_falls():
    cfg = cell.load_json(BENCH, "configs", "drcgnn-h64-l2.json")
    cfg["lr"] = 1e-2
    part = G.make_pool(dict(G.load_traffic("large_resident"), scale=0.03),
                       5)[0]
    out = R.train_steps(cfg, [part] * 6, 5)
    assert all(np.isfinite(out["losses"]))
    assert out["losses"][-1] < out["losses"][0]
    again = R.train_steps(cfg, [part] * 6, 5)
    assert compare.readings(again, out)["loss_gap"] == 0.0


def test_sound_run_is_correct():
    r = cell.run_cell("drcgnn-large-resident", 2**31 + 77, 0.3, False,
                      require_chip=False, overrides=SMALL)
    assert r["correct"] is True
    assert list(r)[-1] == "checks"
    assert r["metrics"]["train_step_ms"]["value"] > 0
    json.dumps(r)


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    limits = cell.load_json(BENCH, "workloads", f"{workload}.json")["limits"]
    out = control.readings_for(workload, 41, {"traffic": {"scale": 0.03}})
    for name in ("control", "half_batch", "unchanged"):
        ok, _rows = cell.judge(out[name], limits)
        assert not ok, (name, out[name], limits)


@pytest.mark.parametrize("read, correct", [
    ({"a": 0.1, "b": 2.0}, True),
    ({"a": 0.3, "b": 2.0}, False),
    ({"a": 0.1}, False),
    ({"a": float("nan"), "b": 2.0}, False),
    ({"a": 0.2, "b": 3.0, "unlimited": 9.0}, True),
], ids=["under", "over", "missing", "nan", "at_limit"])
def test_judge_holds_any_reading_to_its_limit(read, correct):
    ok, rows = cell.judge(read, {"a": 0.2, "b": 3.0})
    assert ok is correct
    assert [name for name, _v, _lim in rows] == ["a", "b"]


def test_judge_without_limits_is_not_correct():
    assert cell.judge({"a": 0.0}, {}) == (False, [])
