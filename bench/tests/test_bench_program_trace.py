"""The program's spans in a traced window (program_trace.py) and the
readers of the named kernels and program spans, on small synthetic
traces."""

import sys

import pytest

import program_trace
import readers
import trace_reduce
from test_bench_trace_reduce import TRACE as OLD_TRACE

# device ops (ns): [1000, 3000), [3500, 7500); window [0, 10000).  The
# window's thread holds one step, train [500, 9500), and in it plan [600,
# 1500) (upload [700, 1200) inside), dispatch [1500, 2500), sync [2500,
# 8000), bookkeeping [8000, 9000).  A worker thread packs a relation over
# the whole window.
DEVICE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%drspmm_arena_fwd.3 = f32[8,64]{1,0} custom-call(s32[8]{0} %a), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%drspmm_arena_bwd.1 = f32[8,16]{1,0} custom-call(s32[8]{0} %a), custom_call_target=\\"tpu_custom_call\\"" } }
}
"""
# a second device, busy over the whole window
BUSY_DEVICE = """
planes {
  id: 3
  name: "/device:TPU:1"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion()" } }
}
"""
HOST = """
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 9000000 }
    events { metadata_id: 3 offset_ps: 600000 duration_ps: 900000 }
    events { metadata_id: 4 offset_ps: 700000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 2500000 duration_ps: 5500000 }
    events { metadata_id: 7 offset_ps: 8000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 8 offset_ps: 0 duration_ps: 12000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "train" } }
  event_metadata { key: 3 value { id: 3 name: "train.plan" } }
  event_metadata { key: 4 value { id: 4 name: "train.plan_upload" } }
  event_metadata { key: 5 value { id: 5 name: "train.dispatch" } }
  event_metadata { key: 6 value { id: 6 name: "train.sync" } }
  event_metadata { key: 7 value { id: 7 name: "train.bookkeeping" } }
  event_metadata { key: 8 value { id: 8 name: "graph.pack_relation" } }
}
"""
TRACE = DEVICE + HOST

OLD_KEYS = {"window_s", "busy_s", "n_devices", "n_events", "ops",
            "idle_gaps"}


def _pd(text):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def program():
    return program_trace.reduce(_pd(TRACE))


def test_gaps_split_piecewise_by_innermost_span(program):
    gaps = program["program_gaps"]
    # idle [0, 1000): outside 500, train 100, plan 100, upload 300;
    # [3000, 3500): sync; [7500, 10000): sync 500, bookkeeping 1000,
    # train 500, outside 500.  The midpoint rule would give the whole
    # last stretch to bookkeeping.
    assert gaps == pytest.approx({
        "outside": 1000e-9, "train": 600e-9, "train.plan": 100e-9,
        "train.plan_upload": 300e-9, "train.sync": 1000e-9,
        "train.bookkeeping": 1000e-9})
    assert "train.dispatch" not in gaps


def test_gaps_sum_to_the_idle_time(program):
    old = trace_reduce.reduce(_pd(TRACE))
    idle = old["window_s"] - old["busy_s"]
    assert sum(program["program_gaps"].values()) == pytest.approx(idle)
    # the existing fixture has no program spans: all its idle is outside
    legacy = program_trace.reduce(_pd(OLD_TRACE))
    assert legacy["program_spans"] == {}
    assert legacy["program_gaps"] == pytest.approx({"outside": 5e-6})


def test_worker_spans_count_time_and_attribute_nothing(program):
    spans = program["program_spans"]
    # clipped to the window; the worker's span is timed but owns no gap
    assert spans["graph.pack_relation"] == [pytest.approx(10e-6), 1]
    assert spans["train"] == [pytest.approx(9e-6), 1]
    assert spans["train.sync"] == [pytest.approx(5.5e-6), 1]
    assert "graph.pack_relation" not in program["program_gaps"]


def test_existing_reduction_keeps_its_keys():
    for text in (OLD_TRACE, TRACE):
        assert set(trace_reduce.reduce(_pd(text))) == OLD_KEYS
    b = trace_reduce.breakdown(trace_reduce.reduce(_pd(OLD_TRACE)))
    assert set(b) == {"device_ops", "idle_gaps"}


def test_two_device_planes_are_averaged(program):
    two = program_trace.reduce(_pd(DEVICE + BUSY_DEVICE + HOST))
    assert two["program_gaps"] == pytest.approx(
        {k: v / 2 for k, v in program["program_gaps"].items()})


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        program_trace.reduce(_pd(TRACE.replace('"bench.window"', '"x"')))


# ----------------------------------------------------------------- readers

KERNEL_TRACE = {
    "ops": {
        '%drspmm_arena_fwd.3 = f32[8,64]{1,0} custom-call(s32[8]{0} %a), '
        'custom_call_target="tpu_custom_call"': 0.030,
        '%drspmm_dense_fwd = f32[8,64]{1,0} custom-call(f32[8,8]{1,0} %d), '
        'custom_call_target="tpu_custom_call"': 0.002,
        '%drspmm_arena_bwd.1 = f32[8,16]{1,0} custom-call(s32[8]{0} %a), '
        'custom_call_target="tpu_custom_call"': 0.020,
        '%drspmm_dense_bwd.2 = f32[8,16]{1,0} custom-call(f32[8,8]{1,0} '
        '%d), custom_call_target="tpu_custom_call"': 0.001,
        # consumers of a kernel's output name it, and are not the kernel
        "%fusion.9 = f32[8,64]{1,0} fusion(f32[8,64]{1,0} "
        "%drspmm_arena_fwd.3)": 0.5,
        "%sort.1 = (f32[9,64]{1,0}, s32[9,64]{1,0}) sort(f32[9,64]{1,0} "
        "%drspmm_arena_bwd.1)": 0.5,
    },
}
UNNAMED_TRACE = {"ops": {
    '%branch_0_fun.4 = f32[8,64]{1,0} custom-call(s32[8]{0} %a), '
    'custom_call_target="tpu_custom_call"': 0.030}}


@pytest.mark.parametrize("name,ms", [("kernels.drspmm_fwd_ms", 3.2),
                                     ("kernels.drspmm_bwd_ms", 2.1)])
def test_kernel_readers(name, ms):
    read = readers.load(name).read
    assert read({"trace": None, "steps": 10}) is None
    assert read({"trace": UNNAMED_TRACE, "steps": 10}) is None
    assert read({"trace": KERNEL_TRACE, "steps": 10}) == pytest.approx(ms)


def test_named_kernels_add_up_to_all_kernels():
    ctx = {"trace": KERNEL_TRACE, "steps": 10}
    total = readers.load("kernels.drspmm_ms").read(ctx)
    assert (readers.load("kernels.drspmm_fwd_ms").read(ctx)
            + readers.load("kernels.drspmm_bwd_ms").read(ctx)) == \
        pytest.approx(total)


@pytest.fixture
def registry(monkeypatch):
    from repro.obs import metrics
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "DEFAULT_REGISTRY", reg)
    return reg


@pytest.mark.parametrize("name,span", [
    ("host.pack_ell_ms", "graph.pack_ell"),
    ("host.relation_plan_ms", "graph.relation_plan"),
    ("train.plan_upload_ms", "train.plan_upload"),
])
def test_span_readers(registry, name, span):
    read = readers.load(name).read
    ctx = {"trace": KERNEL_TRACE, "steps": 4}
    assert read(ctx) is None                  # no such span recorded
    for ms in (10.0, 30.0):
        registry.observe("trace.span_ms", ms, span=span)
    registry.observe("trace.span_ms", 99.0, span="graph.other")
    assert read({"trace": None, "steps": 4}) is None
    assert read(ctx) == pytest.approx(10.0)


def test_span_readers_without_the_program(monkeypatch):
    monkeypatch.delitem(sys.modules, "repro.obs.metrics", raising=False)
    ctx = {"trace": KERNEL_TRACE, "steps": 4}
    assert readers.load("host.pack_ell_ms").read(ctx) is None
