"""The trace reduction on a small synthetic trace: busy union, idle share,
kernel matching and idle gaps named by the host span open at the time."""

import pytest

import trace_reduce

# device ops (ns): a [1000, 3000), b [2000, 5000) overlapping, c [7000,
# 8000); window [0, 10000); host spans: pack [0, 1000), step [1000, 9000)
TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 11000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
  event_metadata { key: 2 value { id: 2 name: "_arena_fwd_kernel" } }
  event_metadata { key: 3 value { id: 3 name: "topk.2" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 8000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.pack" } }
  event_metadata { key: 3 value { id: 3 name: "bench.step" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return trace_reduce.reduce(ProfileData.from_text_proto(TRACE))


def test_busy_union_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(10e-6)
    # union of [1000, 5000) and [7000, 8000): 5000 ns; the op after the
    # window and the module line are not counted
    assert reduced["busy_s"] == pytest.approx(5e-6)
    assert reduced["n_events"] == 3


def test_kernel_matching(reduced):
    assert trace_reduce.op_time(reduced, r"arena_fwd_kernel") == \
        pytest.approx(3e-6)
    assert trace_reduce.op_time(reduced, r"(?i)top-?k") == pytest.approx(1e-6)
    assert trace_reduce.op_time(reduced, r"no_such_kernel") == 0.0


def test_idle_gaps_named_by_host_span(reduced):
    gaps = reduced["idle_gaps"]
    # [0, 1000) under pack; [5000, 7000) under step; [8000, 10000) has
    # its midpoint after the step span closed, so no inner span
    assert gaps["pack"] == pytest.approx(1e-6)
    assert gaps["step"] == pytest.approx(2e-6)
    assert gaps["other"] == pytest.approx(2e-6)
    b = trace_reduce.breakdown(reduced)
    assert b["device_ops"][0] == ["_arena_fwd_kernel", pytest.approx(3e-6)]
    assert [g[0] for g in b["idle_gaps"]][-1] == "pack"


def test_no_window_span_is_an_error():
    from jax.profiler import ProfileData
    bare = TRACE.replace('"bench.window"', '"something.else"')
    with pytest.raises(ValueError):
        trace_reduce.reduce(ProfileData.from_text_proto(bare))


@pytest.mark.parametrize("full,short", [
    ('%branch_0_fun.4 = f32[23896,64]{1,0:T(8,128)S(1)} custom-call('
     's32[16935]{0:T(1024)S(1)} %copy-done.135), '
     'custom_call_target="tpu_custom_call", operand_layout_constraints={}',
     "branch_0_fun.4 custom-call:tpu_custom_call f32[23896,64]"),
    ("%sort.47 = (f32[9856,64]{1,0:T(8,128)}, s32[9856,64]{1,0}) "
     "sort(f32[9856,64]{1,0} %x), dimensions={1}",
     "sort.47 sort (f32[9856,64], s32[9856,64])"),
    ("%fusion.22", "fusion.22"),
])
def test_short_names_of_tpu_ops(full, short):
    assert trace_reduce.short_name(full) == short


def test_metric_patterns_match_tpu_op_text():
    import importlib.util
    import os
    import re

    def pattern(name):
        path = os.path.join(os.path.dirname(trace_reduce.__file__),
                            "metrics", f"{name}.py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.PATTERN

    kernel = ('%branch_0_fun.4 = f32[23896,64]{1,0} custom-call(s32[16935]{0} '
              '%a), custom_call_target="tpu_custom_call"')
    sort = "%sort.1 = (f32[9,64]{1,0}, s32[9,64]{1,0}) sort(f32[9,64]{1,0} %x)"
    fusion = "%fusion.3 = f32[9,64]{1,0} fusion(f32[9,64]{1,0} %sort.1)"
    assert re.search(pattern("kernels.drspmm_ms"), kernel)
    assert not re.search(pattern("kernels.drspmm_ms"), sort)
    assert re.search(pattern("mp.topk_ms"), sort)
    assert not re.search(pattern("mp.topk_ms"), fusion)
    assert not re.search(pattern("mp.topk_ms"), kernel)
