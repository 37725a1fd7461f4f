"""Program spans on the profiler's clock (``repro.obs.span``, DESIGN.md
§11): the trainer's step phases and the packing stages land in a CPU
``jax.profiler`` trace, nested and in order, and the trainer counts one
plan upload per new graph."""

import glob
import os
import warnings

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData, TraceAnnotation

from repro.graphs.circuit import relation_plan_of
from repro.graphs.generator import generate_partition, pack_graph_parallel
from repro.obs import NULL_RECORDER, TraceRecorder, span
from repro.obs.metrics import DEFAULT_REGISTRY
from repro.train.circuit_trainer import CircuitTrainConfig, CircuitTrainer

PHASES = ["train.plan", "train.dispatch", "train.sync", "train.bookkeeping"]


def _parts(n_cell, n_net, seed):
    coo, xc, xn, y = generate_partition(np.random.default_rng(seed),
                                        n_cell, n_net)
    return coo, n_cell, n_net, xc, xn, y


def _trainer(f=16):
    return CircuitTrainer(CircuitTrainConfig(hidden=32, k_cell=8, k_net=8),
                          f, f)


def _events(trace_dir):
    """{thread line index: [(name, start, end, stats)]} of the host
    planes' program spans."""
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = {}
    # jaxlib's event-stats type warns once when first built
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for i, plane in enumerate(ProfileData.from_file(path).planes):
            if plane.name.startswith("/device:"):
                continue
            for j, line in enumerate(plane.lines):
                evs = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                       for e in line.events
                       if e.name == "train"
                       or e.name.startswith(("train.", "graph."))]
                if evs:
                    out[(i, j)] = sorted(evs, key=lambda e: (e[1], -e[2]))
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two single-graph steps on a freshly packed graph, under a CPU
    profiler session."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    tr = _trainer()
    before = {n: DEFAULT_REGISTRY.histogram("trace.span_ms", span=n).count
              for n in ("train.dispatch", "graph.pack_ell")}
    jax.profiler.start_trace(trace_dir)
    try:
        g = pack_graph_parallel(*_parts(60, 30, 3))
        relation_plan_of(g)
        tr.train_epoch([g])
        tr.train_epoch([g])
    finally:
        jax.profiler.stop_trace()
    after = {n: DEFAULT_REGISTRY.histogram("trace.span_ms", span=n).count
             for n in before}
    return tr, g, _events(trace_dir), before, after


def test_step_phases_nested_and_in_order(traced):
    _, _, events, _, _ = traced
    main = [evs for evs in events.values()
            if any(e[0] == "train" for e in evs)]
    assert len(main) == 1
    evs = main[0]
    steps = [e for e in evs if e[0] == "train"]
    assert [int(e[3]["step_num"]) for e in steps] == [0, 1]
    for k, step in enumerate(steps):
        inner = [e for e in evs if e[0] != "train" and _inside(e, step)
                 and e[0].startswith("train.")]
        phases = [e for e in inner if e[0] in PHASES]
        assert [e[0] for e in phases] == PHASES
        assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
        uploads = [e for e in inner if e[0] == "train.plan_upload"]
        # the plan is uploaded on the first step only, inside train.plan
        assert len(uploads) == (1 if k == 0 else 0)
        if uploads:
            assert _inside(uploads[0], phases[0])


def test_packing_spans(traced):
    _, _, events, _, _ = traced
    flat = [(key, e) for key, evs in events.items() for e in evs]
    pack = [(key, e) for key, e in flat if e[0] == "graph.pack_ell"]
    assert len(pack) == 1
    pack_line, pack_ev = pack[0]
    rels = [(key, e) for key, e in flat if e[0] == "graph.pack_relation"]
    # one per edge type, on the pool's threads, inside the pool's span
    assert sorted(e[3]["etype"] for _, e in rels) == ["near", "pin",
                                                     "pinned"]
    assert all(key != pack_line and _inside(e, pack_ev) for key, e in rels)
    plans = [(key, e) for key, e in flat if e[0] == "graph.relation_plan"]
    # built once, outside any step: the trainer's lookup hits the memo
    assert len(plans) == 1 and plans[0][0] == pack_line
    assert plans[0][1][1] >= pack_ev[2]


def test_span_times_summed_while_profiling(traced):
    _, _, _, before, after = traced
    assert after["train.dispatch"] - before["train.dispatch"] == 2
    assert after["graph.pack_ell"] - before["graph.pack_ell"] == 1


def test_plan_uploads_count_one_miss_per_new_graph(traced):
    tr, g, _, _, _ = traced
    assert tr.stats()["plan_uploads"] == 1
    assert tr.metrics.value("train.plan_uploads") == 1
    tr.train_epoch([g])                          # cached: no upload
    assert tr.stats()["plan_uploads"] == 1
    tr.train_epoch([pack_graph_parallel(*_parts(60, 30, 4))])
    assert tr.stats()["plan_uploads"] == 2


def test_batched_steps_span_collate_and_count_uploads():
    rec = TraceRecorder()
    gs = [pack_graph_parallel(*_parts(40, 20, s)) for s in (5, 6)]
    tr = CircuitTrainer(CircuitTrainConfig(hidden=32, k_cell=8, k_net=8),
                        16, 16, recorder=rec)
    tr.train_epoch(gs, batch_size=2)
    tr.train_epoch(gs, batch_size=2)             # same batch: cache hit
    assert tr.stats()["plan_uploads"] == 1
    names = [(e["ph"], e["name"]) for e in rec.export()["traceEvents"]
             if e["ph"] in "BE" and e["cat"] == "train"]
    one = [("B", "train"), ("B", "train.plan"), ("B", "train.plan_upload"),
           ("E", "train.plan_upload"), ("E", "train.plan"),
           ("B", "train.dispatch"), ("E", "train.dispatch"),
           ("B", "train.sync"), ("E", "train.sync"),
           ("B", "train.bookkeeping"), ("E", "train.bookkeeping"),
           ("E", "train")]
    hit = [n for n in one if n[1] != "train.plan_upload"]
    assert names == one + hit


def test_tracing_off_is_a_bare_trace_annotation():
    assert not TraceAnnotation.is_enabled()
    s = span("train.sync", NULL_RECORDER)
    assert type(s) is TraceAnnotation
    with s:
        pass
    assert type(span("train", step=3)).__name__ == "StepTraceAnnotation"
