"""Observability integration: the engine/trainer/collator/ops emitting
through one Recorder + MetricsRegistry (DESIGN.md §11).

Covers the ISSUE-7 acceptance points that live above the unit layer:
``stats()`` back-compat as a registry view, the no-op default's zero-cost
contract, pack-time arena gauges (the `near` slot saving), chaos
injections as trace annotations, and the 2-device online acceptance run
(per-slot dispatch tracks + healing ladder + deadline flush in one valid
trace)."""

import importlib.util
import json
import os
import threading

import numpy as np
import pytest

import jax

from _multidev import run_multidev
from repro.core.hetero_mp import HeteroMPConfig
from repro.fault.inject import FaultInjector, FaultRule
from repro.graphs.collate import collate_graphs
from repro.graphs.generator import generate_partition, pack_graph_parallel
from repro.models.hgnn import init_drcircuitgnn
from repro.obs import TraceRecorder
from repro.obs.metrics import DEFAULT_REGISTRY
from repro.serve import CircuitServeEngine

_spec = importlib.util.spec_from_file_location(
    "check_trace",
    os.path.join(os.path.dirname(__file__), "..", "tools", "check_trace.py"))
check_trace_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_trace_mod)
check_trace = check_trace_mod.check_trace


def _graph(n_cell, n_net, seed):
    coo, xc, xn, y = generate_partition(np.random.default_rng(seed),
                                        n_cell, n_net)
    return pack_graph_parallel(coo, n_cell, n_net, xc, xn, y)


def _engine(**kw):
    cfg = HeteroMPConfig(hidden=32, k_cell=8, k_net=8)
    params = init_drcircuitgnn(jax.random.PRNGKey(0), 16, 16, 32)
    return CircuitServeEngine(params, cfg, max_batch=2, **kw)


# --------------------------------------------------- stats() back-compat

GOLDEN_STATS_KEYS = {
    # every pre-PR-7 key (tests and benchmarks index these)...
    "requests", "batches", "compiles", "graphs_per_s", "p50_ms", "p95_ms",
    "wall_s", "cell_padding_ratio", "deadline_flushes", "failures",
    "retries", "bisects", "watchdog_timeouts", "nonfinite_outputs",
    "rejected_inputs", "admission_blocked", "admission_rejected",
    "admission_shed", "queued", "device_health", "quarantines", "probes",
    "readmissions", "devices", "dispatches_per_device", "live_buckets",
    "evictions", "live_compiles", "params_version", "jit_cache_size",
    # ...plus the one additive PR-7 key
    "p99_ms",
}


def test_stats_is_registry_view_with_backcompat_keys():
    eng = _engine()
    for s in range(4):
        eng.submit(_graph(50 + (s % 2), 25, s))
    eng.run()
    st = eng.stats()
    assert set(st) == GOLDEN_STATS_KEYS
    assert st["requests"] == 4
    assert isinstance(st["requests"], int)
    # the dict is a VIEW over the registry: same numbers both ways
    assert eng.metrics.value("serve.requests") == st["requests"]
    assert eng.metrics.value("serve.batches") == st["batches"]
    assert sum(int(c) for c in st["dispatches_per_device"]) == st["batches"]
    assert st["p99_ms"] >= st["p50_ms"] > 0.0


def test_stats_keys_identical_with_and_without_recorder():
    """Tracing on/off must not change the public surface."""
    eng_off = _engine()
    eng_on = _engine(recorder=TraceRecorder())
    for eng in (eng_off, eng_on):
        eng.submit(_graph(50, 25, 0))
        eng.run()
    assert set(eng_off.stats()) == set(eng_on.stats())


def test_noop_recorder_default_emits_nothing(tmp_path):
    eng = _engine()
    eng.submit(_graph(50, 25, 0))
    eng.run()
    assert eng.recorder.enabled is False
    assert eng.recorder.export() == {"traceEvents": []}
    p = tmp_path / "empty.json"
    eng.dump_trace(str(p))
    assert json.loads(p.read_text()) == {"traceEvents": []}


def test_metrics_exports():
    eng = _engine()
    eng.submit(_graph(50, 25, 0))
    eng.run()
    snap = eng.metrics_snapshot()
    assert snap["serve.requests"] == 1
    assert snap["serve.latency_ms"]["count"] == 1
    text = eng.metrics_text()
    assert "serve_requests 1" in text
    assert "# TYPE serve_latency_ms summary" in text
    json.loads(eng.metrics.snapshot_json())   # JSON-able end to end


# ------------------------------------------------- pack-time arena gauges

def test_collate_emits_arena_gauges_with_near_slot_saving():
    """The fused arena's double-bucketing pays off most on `near` (the
    high-variance cell–cell relation): packing 4 medium partitions must
    report the ~1.9x slot saving vs a single-slab layout in the pack-time
    gauge (ISSUE-7 satellite: the claim is visible in metrics, not just in
    a benchmark table)."""
    gs = [_graph(220, 110, s) for s in range(4)]
    collate_graphs(gs)
    saving = DEFAULT_REGISTRY.value("arena.slot_saving",
                                    etype="near", dir="fwd")
    assert saving >= 1.5, saving
    fill = DEFAULT_REGISTRY.value("arena.fill_ratio",
                                  etype="near", dir="fwd")
    assert 0.0 < fill <= 1.0
    slots = DEFAULT_REGISTRY.value("arena.slots", etype="near", dir="fwd")
    padded = DEFAULT_REGISTRY.value("arena.padded_slots",
                                    etype="near", dir="fwd")
    assert slots > 0 and 0 <= padded < slots
    assert fill == pytest.approx(1.0 - padded / slots)
    # the batch plan reports its own arena occupancy under etype __plan__
    assert DEFAULT_REGISTRY.value("arena.slots", etype="__plan__",
                                  dir="fwd") > 0


def test_pack_emits_tier_gauges():
    """§14 tier routing is visible in metrics at pack time: every edge-type
    direction reports which tier it landed in, the nnz that decided it, and
    the crossover threshold in force — so a mis-tiered relation shows up in
    a dashboard, not just in a kernel trace.  On this batch the crossover
    must split the relations: `near` (high-nnz cell–cell) stays on the
    arena tier while `pin` drops to the dense tier."""
    from repro.graphs.ell import DENSE_TIER_NNZ

    gs = [_graph(220, 110, s) for s in range(4)]
    collate_graphs(gs)
    for et in ("near", "pin", "pinned"):
        for d in ("fwd", "bwd"):
            tier = DEFAULT_REGISTRY.value("arena.tier", etype=et, dir=d)
            assert tier in (0.0, 1.0), (et, d, tier)
            nnz = DEFAULT_REGISTRY.value("arena.tier_nnz", etype=et, dir=d)
            assert nnz > 0, (et, d, nnz)
            thr = DEFAULT_REGISTRY.value("arena.tier_threshold",
                                         etype=et, dir=d)
            assert thr == DENSE_TIER_NNZ, (et, d, thr)
            # the gauge agrees with the rule it reports (modulo the area
            # guard and bucket pinning, which only force the arena tier)
            if nnz > thr:
                assert tier == 0.0, (et, d, nnz, tier)
    assert DEFAULT_REGISTRY.value("arena.tier", etype="near",
                                  dir="fwd") == 0.0     # arena
    assert DEFAULT_REGISTRY.value("arena.tier", etype="pin",
                                  dir="fwd") == 1.0     # dense


def test_ops_dispatch_counters_accumulate():
    def total():
        return sum(m.value for m in
                   DEFAULT_REGISTRY.series("ops.dispatch").values())

    before = total()
    eng = _engine()
    eng.submit(_graph(50, 25, 0))
    eng.run()
    assert total() > before
    # labeled by backend family and dispatch kind, mirroring the tags the
    # FUSED_DISPATCH_LOG deque records ("xla:fwd" -> {family,kind})
    labels = set(DEFAULT_REGISTRY.series("ops.dispatch"))
    assert all(dict(lab).keys() == {"family", "kind"} for lab in labels)


# ------------------------------------------------------- trainer metrics

def test_trainer_stats_and_step_histogram():
    from repro.train.circuit_trainer import (CircuitTrainConfig,
                                             CircuitTrainer)
    gs = [_graph(40, 20, 100 + s) for s in range(3)]
    f_cell, f_net = gs[0].x_cell.shape[1], gs[0].x_net.shape[1]
    tr = CircuitTrainer(CircuitTrainConfig(hidden=32, epochs=1),
                        f_cell, f_net)
    tr.train_epoch(gs)
    st = tr.stats()
    assert st["steps"] == 3
    assert st["nonfinite_grad_steps"] == 0
    assert st["step_p50_ms"] > 0.0
    assert tr.nonfinite_grad_steps == 0     # property over the counter
    assert tr.metrics.value("train.steps") == 3


# --------------------------------------- chaos as trace annotations (e2e)

def test_chaos_and_healing_ladder_annotated_in_trace(tmp_path):
    """Seeded dispatch faults on occurrences 0..2 exhaust the retry budget
    (max_retries=2) and force a bisect; every rung must appear in the
    trace — inject instants on the chaos track, retry/bisect instants on
    the healing track, and the final per-slot batch X events."""
    rec = TraceRecorder()
    chaos = FaultInjector([FaultRule("dispatch", at=(0, 1, 2))])
    eng = _engine(recorder=rec, chaos=chaos)
    for s in range(2):                      # one bucket, one batch of 2
        eng.submit(_graph(50, 25, s))
    out = eng.run()
    assert len(out) == 2
    st = eng.stats()
    assert st["retries"] >= 2 and st["bisects"] == 1 and st["failures"] == 0

    doc = rec.export()
    assert check_trace(doc, expect_device_tracks=1,
                       expect_events=("inject:dispatch", "retry", "bisect",
                                      "batch", "submit")) == []
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] != "M"]
    # one chaos annotation per scheduled fault, and the healing-track
    # instants agree exactly with the registry counters
    assert names.count("inject:dispatch") == 3
    assert names.count("retry") == st["retries"]
    assert names.count("bisect") == st["bisects"]
    p = tmp_path / "chaos_trace.json"
    eng.dump_trace(str(p))
    assert check_trace(json.loads(p.read_text())) == []


def test_online_deadline_flush_annotated_in_trace():
    rec = TraceRecorder()
    eng = _engine(recorder=rec, max_wait_ms=15.0)
    server = threading.Thread(target=eng.serve_forever)
    server.start()
    rid = eng.submit(_graph(50, 25, 0))     # lone request: must flush by
    eng.result(rid, timeout=600.0)          # deadline, not by size
    eng.stop()
    server.join()
    assert eng.stats()["deadline_flushes"] >= 1
    doc = rec.export()
    assert check_trace(doc, expect_events=("deadline_flush",)) == []


# ------------------------------------------- 2-device acceptance (slow)

ACCEPTANCE_SCRIPT = r"""
import sys, time, threading
import jax, numpy as np
from repro.core.hetero_mp import HeteroMPConfig
from repro.fault.inject import FaultInjector, FaultRule
from repro.graphs.generator import generate_partition, pack_graph_parallel
from repro.models.hgnn import init_drcircuitgnn
from repro.obs import TraceRecorder
from repro.serve import CircuitServeEngine

assert jax.device_count() == 2

def graph(n_cell, n_net, seed):
    coo, xc, xn, y = generate_partition(np.random.default_rng(seed),
                                        n_cell, n_net)
    return pack_graph_parallel(coo, n_cell, n_net, xc, xn, y)

cfg = HeteroMPConfig(hidden=32, k_cell=8, k_net=8)
params = init_drcircuitgnn(jax.random.PRNGKey(0), 16, 16, 32)
rec = TraceRecorder()
chaos = FaultInjector([FaultRule("dispatch", at=(0, 1, 2))], seed=11)
eng = CircuitServeEngine(params, cfg, max_batch=2, max_wait_ms=20.0,
                         recorder=rec, chaos=chaos)
t = threading.Thread(target=eng.serve_forever)
t.start()
# phase 1: ONE batch in flight — it eats all 3 scheduled dispatch faults
# (retry x2 exhausts the budget, then bisect); a wider burst would let a
# second concurrent batch share the fault schedule and dodge the bisect.
# Both graphs are built before either is submitted, so they arrive inside
# one max_wait_ms window and flush as that one batch.
burst = [graph(50, 25, s) for s in range(2)]
rids = [eng.submit(g) for g in burst]
for rid in rids:
    eng.result(rid, timeout=600.0)
# phase 2: paced singles — each waits out max_wait_ms => deadline flushes
for s in range(4, 10):
    rid = eng.submit(graph(50, 25, s))
    eng.result(rid, timeout=600.0)
eng.stop(); t.join()
st = eng.stats()
assert st["failures"] == 0, st
assert st["retries"] >= 2 and st["bisects"] >= 1, st
assert st["deadline_flushes"] >= 1, st
assert all(c > 0 for c in st["dispatches_per_device"]), st
eng.dump_trace(sys.argv[1])
print("ACCEPT_OK", st["retries"], st["bisects"], st["deadline_flushes"],
      st["dispatches_per_device"])
"""


@pytest.mark.slow
def test_two_device_chaos_trace_acceptance_subprocess(tmp_path):
    trace_path = str(tmp_path / "accept_trace.json")
    run_multidev(ACCEPTANCE_SCRIPT, n_devices=2, argv=[trace_path],
                 expect=("ACCEPT_OK",))
    with open(trace_path) as f:
        doc = json.load(f)
    assert check_trace(
        doc, expect_device_tracks=2,
        expect_events=("inject:dispatch", "retry", "bisect",
                       "deadline_flush", "batch", "collate",
                       "device_put")) == []
