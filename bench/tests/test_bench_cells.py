"""Every cell in BENCHMARK.json names files that exist, and the file keeps
to the shape BENCHMARK.json must have."""

import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"][1] == "bench/cell.py"
    assert 1 <= bench["run_seconds"] <= 51


def test_each_cell_names_existing_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["config"] in configs
        wl = _load("workloads", f"{w['name']}.json")
        assert (wl["config"], wl["traffic"], wl["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        assert wl["limits"] and all(
            isinstance(v, (int, float)) and v >= 0
            for v in wl["limits"].values())
        traffic = _load("traffic", f"{w['traffic']}.json")
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           f"{traffic['kind']}.py"))
        assert os.path.isfile(os.path.join(ROOT, configs[w["config"]]["file"]))


def test_every_config_is_used_and_complete(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        for key in ("hidden", "n_layers", "k_cell", "k_net", "lr",
                    "weight_decay", "adam_b1", "adam_b2", "adam_eps",
                    "backend", "assumed"):
            assert key in cfg


def test_every_metric_has_a_reader(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py"))
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        # every cell that reads the metric reports the metric it moves
        assert set(m["workloads"]) <= reports[m["moves"]]
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        n = w["name"]
        e2e = [m["name"] for m in bench["end_to_end"]
               if n in m.get("workloads", [n])]
        per = [m for m in bench["per_layer"] if n in m.get("workloads", [n])]
        assert "setup_s" in e2e and len(e2e) >= 2 and per
