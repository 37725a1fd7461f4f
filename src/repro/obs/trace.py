"""Structured request tracing with Chrome trace-event export, and the
program's spans on the profiler's clock.

:func:`span` is the one entry point for a named stretch of program work
(``train.dispatch``, ``graph.pack_ell``, ...).  It always opens a
``jax.profiler.TraceAnnotation`` (a TraceMe): inactive it records nothing;
while a profiler session runs the span lands in the same ``.xplane.pb``
as the device ops, on their clock.  With an enabled recorder the same
span also goes to it (B/E pair, own clock), and while a profiler session
runs its host milliseconds are summed into the ``trace.span_ms{span=}``
histogram of :data:`~repro.obs.metrics.DEFAULT_REGISTRY`.  With neither,
the cost is the TraceMe's inactive check.

One :class:`Recorder` interface, two implementations:

* :data:`NULL_RECORDER` — the default.  Every method is a no-op and
  ``enabled`` is ``False``; emitters guard with ``if rec.enabled:`` so the
  happy path allocates nothing (the same zero-overhead contract the chaos
  harness keeps with ``if self.chaos is not None``).
* :class:`TraceRecorder` — a bounded in-memory event buffer that exports
  the Chrome trace-event JSON format (``{"traceEvents": [...]}``),
  loadable in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.

Trace model (DESIGN.md §11)
---------------------------

Tracks are named lanes (``tid`` rows under one ``pid``).  The engine uses:

* ``device/<i>`` — one track per ring slot.  Batch work on a slot is
  emitted as **X (complete) events** carrying ``dur``: healing attempts
  (retry/bisect re-dispatches on daemon threads) can overlap the pipeline's
  next batch on the same slot, and X events nest/overlap cleanly where
  B/E pairs would cross.
* ``worker/<i>`` — one track per host-pool prep worker.  Collate +
  device_put spans are **B/E pairs**: a track maps 1:1 onto a thread, so
  pairs are strictly nested per track (tests assert this).
* ``intake`` — submit/admit/shed/deadline-flush **instant** events.
* ``healing`` — retry/bisect/watchdog/quarantine ladder instants.
* ``chaos`` — fault-injection annotations (one instant per injected
  fault, args carrying point/occurrence/device).
* ``layout`` — compile / eviction / recompile instants from the
  LayoutTable and the engine's jit-cache.

Timestamps are ``time.perf_counter()`` microseconds relative to recorder
creation — monotonic, so exported ``ts`` never goes backwards.  The buffer
is bounded (default 2^16 events); past the cap new events are counted in
``dropped`` rather than grown without bound — same discipline as
``FUSED_DISPATCH_LOG``.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.obs.metrics import DEFAULT_REGISTRY

DEFAULT_MAX_EVENTS = 65536

_PID = 1  # single-process tracing; one pid, tracks are tids


class _NullSpan:
    """Reusable no-op context manager — one shared instance, zero alloc."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Recorder:
    """No-op base recorder.  All emitters hold one of these; the real
    :class:`TraceRecorder` subclasses it.  Guard emission sites with
    ``if rec.enabled:`` — with the base class that branch is the entire
    cost of tracing-off."""

    enabled: bool = False

    def begin(self, track: str, name: str, **args) -> None: ...

    def end(self, track: str, name: str, **args) -> None: ...

    def instant(self, track: str, name: str, **args) -> None: ...

    def complete(self, track: str, name: str, ts_us: float,
                 dur_us: float, **args) -> None: ...

    def span(self, track: str, name: str, **args):
        return _NULL_SPAN

    def now(self) -> float:
        return 0.0

    def export(self) -> Dict[str, object]:
        return {"traceEvents": []}

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.export(), f)


NULL_RECORDER = Recorder()
# Shared reusable null context for `with (rec.span(...) if rec.enabled
# else NULL_SPAN):` guards — zero allocation on the tracing-off path.
NULL_SPAN = _NULL_SPAN


class _Span:
    __slots__ = ("_rec", "_track", "_name", "_args")

    def __init__(self, rec: "TraceRecorder", track: str, name: str, args):
        self._rec, self._track, self._name, self._args = rec, track, name, args

    def __enter__(self):
        self._rec.begin(self._track, self._name, **self._args)
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self._rec.end(self._track, self._name)
        else:
            self._rec.end(self._track, self._name, error=exc_type.__name__)
        return False


class TraceRecorder(Recorder):
    """Bounded in-memory trace-event collector.

    Thread-safe: every emit takes one short lock append.  Emitters never
    re-enter the recorder while holding its lock (the recorder calls
    nothing back), so it is safe to call from under engine/injector locks.
    """

    enabled = True

    def __init__(self, max_events: int = DEFAULT_MAX_EVENTS):
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._max_events = int(max_events)
        self._tids: Dict[str, int] = {}
        self._t0 = time.perf_counter()
        self.dropped = 0

    # ------------------------------------------------------------- clock

    def now(self) -> float:
        """Microseconds since recorder creation (monotonic)."""
        return (time.perf_counter() - self._t0) * 1e6

    # ------------------------------------------------------------- emits

    def _tid(self, track: str) -> int:
        # caller holds self._lock
        tid = self._tids.get(track)
        if tid is None:
            tid = self._tids[track] = len(self._tids) + 1
        return tid

    def _emit(self, track: str, ev: dict) -> None:
        ts = self.now()
        with self._lock:
            if len(self._events) >= self._max_events:
                self.dropped += 1
                return
            ev["pid"] = _PID
            ev["tid"] = self._tid(track)
            ev.setdefault("ts", ts)
            self._events.append(ev)

    def begin(self, track: str, name: str, **args) -> None:
        ev = {"ph": "B", "name": name, "cat": track}
        if args:
            ev["args"] = args
        self._emit(track, ev)

    def end(self, track: str, name: str, **args) -> None:
        ev = {"ph": "E", "name": name, "cat": track}
        if args:
            ev["args"] = args
        self._emit(track, ev)

    def instant(self, track: str, name: str, **args) -> None:
        ev = {"ph": "i", "s": "t", "name": name, "cat": track}
        if args:
            ev["args"] = args
        self._emit(track, ev)

    def complete(self, track: str, name: str, ts_us: float,
                 dur_us: float, **args) -> None:
        """X event with explicit start/duration — for slot-track work whose
        start time the caller measured (dispatch attempts may overlap on
        one track, which B/E pairs cannot express)."""
        ev = {"ph": "X", "name": name, "cat": track,
              "ts": float(ts_us), "dur": max(0.0, float(dur_us))}
        if args:
            ev["args"] = args
        self._emit(track, ev)

    def span(self, track: str, name: str, **args):
        """``with rec.span("worker/0", "collate", bucket=sig): ...`` —
        emits a B at entry and an E at exit (annotated on exception)."""
        return _Span(self, track, name, args)

    # ------------------------------------------------------------ export

    def export(self) -> Dict[str, object]:
        """Chrome trace-event JSON: metadata (process/thread names) first,
        then all events sorted by ``ts``."""
        with self._lock:
            events = [dict(e) for e in self._events]
            tids = dict(self._tids)
            dropped = self.dropped
        meta: List[dict] = [{
            "ph": "M", "name": "process_name", "pid": _PID, "tid": 0,
            "args": {"name": "repro-circuit-serve"},
        }]
        for track, tid in sorted(tids.items(), key=lambda kv: kv[1]):
            meta.append({"ph": "M", "name": "thread_name",
                         "pid": _PID, "tid": tid, "args": {"name": track}})
        events.sort(key=lambda e: (e["ts"], 0 if e["ph"] == "B" else 1))
        out: Dict[str, object] = {"traceEvents": meta + events,
                                  "displayTimeUnit": "ms"}
        if dropped:
            out["otherData"] = {"dropped_events": dropped}
        return out

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.export(), f)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


class _ProgramSpan:
    """A TraceMe, the recorder's span, and the host time summed into
    ``trace.span_ms`` while a profiler session runs."""

    __slots__ = ("_ann", "_rspan", "_name", "_t0")

    def __init__(self, ann, rspan, name: str):
        self._ann, self._rspan, self._name = ann, rspan, name

    def __enter__(self):
        self._ann.__enter__()
        self._rspan.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        ms = (time.perf_counter() - self._t0) * 1e3
        if TraceAnnotation.is_enabled():
            DEFAULT_REGISTRY.histogram("trace.span_ms",
                                       span=self._name).observe(ms)
        self._rspan.__exit__(*exc)
        return self._ann.__exit__(*exc)


def span(name: str, rec: Recorder = NULL_RECORDER, *,
         step: Optional[int] = None, **args):
    """``with obs.span("train.sync", rec): ...`` — the program's span.

    ``step`` makes it the profiler's step marker
    (``StepTraceAnnotation(name, step_num=step)``: the trace's ``Steps``
    line).  On the recorder the span's track is the name's first dotted
    part (``train``, ``graph``); ``args`` go to both."""
    if step is None:
        ann = TraceAnnotation(name, **args)
    else:
        ann = StepTraceAnnotation(name, step_num=step, **args)
    if not (rec.enabled or TraceAnnotation.is_enabled()):
        return ann
    return _ProgramSpan(ann, rec.span(name.partition(".")[0], name, **args),
                        name)
