"""Observability: request tracing, metrics registry, program spans on the
profiler's clock (:func:`span`).

No dependency beyond ``jax.profiler``.  See DESIGN.md §11 for the trace
model and metric naming scheme.  Quickstart::

    from repro.obs import TraceRecorder, MetricsRegistry

    eng = CircuitServeEngine(model, params, recorder=TraceRecorder())
    ... serve ...
    eng.dump_trace("trace.json")        # open in https://ui.perfetto.dev
    print(eng.metrics_text())           # Prometheus text exposition
"""

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    DEFAULT_REGISTRY,
    default_registry,
)
from repro.obs.trace import (
    Recorder,
    TraceRecorder,
    NULL_RECORDER,
    span,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "DEFAULT_REGISTRY",
    "default_registry",
    "Recorder",
    "TraceRecorder",
    "NULL_RECORDER",
    "span",
]
