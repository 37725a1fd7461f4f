"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the per-layer
metrics read.

* Device planes are those named ``/device:<PLATFORM>:<n>``; their ``XLA
  Ops`` line holds one event per operation that ran on the device.
* The traced window is the benchmark's own host span ``bench.window``
  (``jax.profiler.TraceAnnotation``), on the same clock as the device
  events.  Device events are clipped to it.
* ``busy_s`` is the union of the device op intervals inside the window,
  averaged over the device planes; the idle share is 1 - busy/window.
* ``ops`` sums device time per op name.  On the TPU an op's name is its
  whole HLO text (``%fusion.3 = f32[...] fusion(...), ...``), so a reader
  can match a kernel by what the op is as well as by its name.
* ``idle_gaps`` names every idle stretch of the device by the benchmark
  span (``bench.<name>``) open on the host at its midpoint, and sums them
  per name.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def host_spans(pd) -> List[Tuple[str, float, float]]:
    """[(name, start_ns, end_ns)] of the benchmark's host spans."""
    spans = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name[len(SPAN_PREFIX):],
                                  ev.start_ns, ev.end_ns))
    return spans


def reduce(pd) -> dict:
    """The reduced trace of one traced window."""
    spans = host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    w0, w1 = windows[0]
    inner = [(n, s, e) for n, s, e in spans if n != "window"]

    planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    ops: Dict[str, float] = {}
    busy_total = 0.0
    gaps: Dict[str, float] = {}
    n_events = 0
    for plane in planes:
        ivs = []
        for line in plane.lines:
            if line.name != OP_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e <= s:
                    continue
                n_events += 1
                ivs.append((s, e))
                ops[ev.name] = ops.get(ev.name, 0.0) + (e - s) * 1e-9
        merged = _union(ivs)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        prev = w0
        for s, e in merged + [(w1, w1)]:
            if s > prev:
                mid = 0.5 * (s + prev)
                name, opened = "other", None
                for n, hs, he in inner:
                    if hs <= mid < he and (opened is None or hs > opened):
                        name, opened = n, hs      # the innermost span
                gaps[name] = gaps.get(name, 0.0) + (s - prev) * 1e-9
            prev = max(prev, e)
    n_dev = max(len(planes), 1)
    return dict(window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n_dev,
                n_devices=len(planes), n_events=n_events,
                ops={k: v / n_dev for k, v in ops.items()},
                idle_gaps={k: v / n_dev for k, v in gaps.items()})


def op_time(reduced: dict, pattern: str) -> float:
    """Device seconds of the ops whose name matches ``pattern`` (a regular
    expression)."""
    rx = re.compile(pattern)
    return sum(secs for name, secs in reduced["ops"].items()
               if rx.search(name))


def short_name(full: str) -> str:
    """``%name = type op(...)`` as the trace names a TPU op, cut to
    ``name op[:custom-call target] type``."""
    lhs, _, rhs = full.partition(" = ")
    lhs = lhs.lstrip("%")
    if not rhs:
        return lhs
    m = re.search(r"(?:^|[\s)])([a-z][\w.-]*)\(", rhs)
    op = m.group(1) if m else ""
    tgt = re.search(r'custom_call_target="([^"]+)"', rhs)
    if tgt:
        op += ":" + tgt.group(1)
    typ = re.sub(r"\{[^}]*\}", "", rhs[:m.start(1)] if m else rhs).strip()
    return f"{lhs} {op} {typ}".strip()


def breakdown(reduced: dict, n: int = 10) -> dict:
    """The device ops that took most time and the idle time by host span."""
    top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:n]
    gaps = sorted(reduced["idle_gaps"].items(), key=lambda kv: -kv[1])[:n]
    return dict(device_ops=[[short_name(k), v] for k, v in top],
                idle_gaps=[[k, v] for k, v in gaps])
