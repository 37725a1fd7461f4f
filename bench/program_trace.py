"""The program's own spans (``repro.obs.span``) in a traced window.

    python3 bench/program_trace.py <trace dir> [--steps N]

reads the ``.xplane.pb`` that ``bench/cell.py --trace 1 --trace-dir <dir>``
keeps and prints one JSON object:

* ``program_spans``: ``{name: [host_seconds, count]}`` of the spans named
  ``train``, ``train.*`` and ``graph.*`` on any host thread, clipped to the
  ``bench.window`` span;
* ``program_gaps``: ``{name: seconds}``, every device-idle stretch of the
  window split piecewise by the innermost program span open on the thread
  that holds ``bench.window`` (``"outside"`` where none is), averaged over
  the device planes as ``trace_reduce.idle_gaps`` is.  Its entries sum to
  the window's idle time.  Spans on other threads (the packing workers)
  attribute nothing.

With ``--steps`` both are also given in milliseconds per step.

The metric readers cannot see the raw trace (``cell.py`` reduces it with
``trace_reduce.reduce`` and deletes it), so the readers of host span times
read the program's ``trace.span_ms`` histogram instead (:func:`span_ms`),
which ``repro.obs.span`` fills while a profiler session runs.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Dict, List, Optional, Tuple

import trace_reduce

PROGRAM_SPAN = re.compile(r"^(?:train$|train\.|graph\.)")
OUTSIDE = "outside"


def _innermost(spans: List[Tuple[str, float, float]], w0: float,
               w1: float) -> List[Tuple[float, float, str]]:
    """[(start, end, name)] covering [w0, w1): the innermost of the nested
    ``spans`` open in each stretch, ``OUTSIDE`` where none is."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []
    t = w0

    def emit(until: float) -> None:
        nonlocal t
        if until > t:
            out.append((t, until, stack[-1][0] if stack else OUTSIDE))
            t = until

    def close() -> None:
        emit(stack[-1][1])
        stack.pop()

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            close()
        emit(s)
        stack.append((name, e))
    while stack:
        close()
    emit(w1)
    return out


def _split(idle: List[Tuple[float, float]],
           segments: List[Tuple[float, float, str]],
           into: Dict[str, float]) -> None:
    """Add each idle interval's overlap with each named segment (both lists
    sorted and disjoint) to ``into``, in seconds."""
    j = 0
    for a, b in idle:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            lo, hi = max(a, s), min(b, e)
            if hi > lo:
                into[name] = into.get(name, 0.0) + (hi - lo) * 1e-9
            k += 1


def reduce(pd) -> dict:
    """``program_spans`` and ``program_gaps`` of one traced window."""
    window, w_line, spans = None, None, []
    for i, plane in enumerate(pd.planes):
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for j, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name == "bench.window" and window is None:
                    window, w_line = (ev.start_ns, ev.end_ns), (i, j)
                elif PROGRAM_SPAN.match(ev.name):
                    spans.append(((i, j), ev.name, ev.start_ns, ev.end_ns))
    if window is None:
        raise ValueError("trace holds no bench.window span")
    w0, w1 = window

    program_spans: Dict[str, List[float]] = {}
    own = []
    for line, name, s, e in spans:
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        tot = program_spans.setdefault(name, [0.0, 0])
        tot[0] += (e - s) * 1e-9
        tot[1] += 1
        if line == w_line:
            own.append((name, s, e))
    segments = _innermost(own, w0, w1)

    planes = [p for p in pd.planes if trace_reduce.DEVICE_PLANE.match(p.name)]
    gaps: Dict[str, float] = {}
    for plane in planes:
        ivs = [(max(ev.start_ns, w0), min(ev.end_ns, w1))
               for line in plane.lines if line.name == trace_reduce.OP_LINE
               for ev in line.events]
        busy = trace_reduce._union([(s, e) for s, e in ivs if e > s])
        idle, prev = [], w0
        for s, e in busy + [(w1, w1)]:
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
        _split(idle, segments, gaps)
    n_dev = max(len(planes), 1)
    return dict(program_spans=program_spans,
                program_gaps={k: v / n_dev for k, v in gaps.items()})


def span_ms(ctx: dict, name: str) -> Optional[float]:
    """Host milliseconds per step of the program span ``name`` in the
    window, from the program's ``trace.span_ms{span=name}`` histogram
    (filled only while a profiler session runs, so only the traced window
    counts).  None without a trace, or where the program has no such span
    or no such histogram."""
    if ctx.get("trace") is None or not ctx.get("steps"):
        return None
    metrics = sys.modules.get("repro.obs.metrics")
    if metrics is None:
        return None
    h = metrics.DEFAULT_REGISTRY.series("trace.span_ms").get(
        (("span", name),))
    if h is None or not h.count:
        return None
    return h.sum / ctx["steps"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--steps", type=int, default=0,
                    help="optimizer steps in the window: adds ms per step")
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    out = reduce(ProfileData.from_file(
        trace_reduce.find_xplane(args.trace_dir)))
    if args.steps:
        out["program_spans_ms_per_step"] = {
            k: v[0] * 1e3 / args.steps
            for k, v in out["program_spans"].items()}
        out["program_gaps_ms_per_step"] = {
            k: v * 1e3 / args.steps for k, v in out["program_gaps"].items()}
    print(json.dumps(out, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
