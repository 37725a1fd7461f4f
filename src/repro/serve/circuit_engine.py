"""Circuit serve engine: online multi-device batched HGNN congestion
inference.

The LM engine (serve/engine.py) batches *tokens* into fixed slots; circuit
graphs have no such fixed shape, so this engine batches *graphs* via
block-diagonal collation (graphs/collate.py) instead:

* **continuous intake** — ``submit()`` is thread-safe and legal while
  ``serve_forever()`` is running: producers append to the live queue under
  the engine lock and wake the serving loop;
* **deadline-aware micro-batcher** — requests group into shape buckets
  (quantized node counts + feature widths), FIFO within a bucket.  The
  first bucket to reach ``max_batch`` compatible requests dispatches as a
  full batch; a partial bucket closes when its oldest request has waited
  ``max_wait_ms`` (filler-padding — inert replicas of the last member —
  happens only at that deadline, so full batches never pay padding and
  partial batches never starve);
* **bucket eviction** — per-bucket compiled-layout state (the
  :class:`~repro.graphs.collate.BucketLayout` that pins arena chunk widths
  and floors chunk counts, plus the bucket's own jitted forward and its
  compiled executables) lives in an LRU :class:`LayoutTable` bounded by
  ``max_live_buckets``: a long tail of one-off shapes evicts cold buckets
  instead of growing host+device memory without bound.  An evicted bucket
  that returns recompiles at most once;
* **multi-device routing** — bucket-compatible micro-batches are routed
  round-robin onto the replica devices of the active mesh (or every local
  device) via :class:`~repro.sharding.specs.DeviceRing`: independent
  collated batches are embarrassingly parallel, so N devices give N
  concurrent dispatches, each compiled once per (bucket, device);
* **executor cache** — each bucket owns a jitted forward taking the
  collated graph as a *traced argument*; a mixed-size stream compiles once
  per (bucket, device), not once per graph (the HOGA-motivated property).
  ``compiles`` counts first-dispatches of (signature, device) pairs,
  cumulative across evictions, and ``stats()`` cross-checks the live count
  against jit's own caches when available;
* **packing pool** — host threads collate/pad/``device_put`` upcoming
  batches while devices execute the current ones, one batch in flight per
  device (``core.parallel.prefetch`` in drain mode; an equivalent explicit
  pipeline in the online loop) — the paper's CPU-thread + stream overlap
  (Sec. 3.4) at batch granularity;
* **params hot-swap** — ``update_params()`` commits fresh per-device
  replicas between batches (in-flight batches finish on the old weights);
  every request records the ``params_version`` that served it — the
  train-then-serve loop without a restart or a recompile;
* **multi-tenant head registry** — ``register_head(name, head_w, head_b)``
  installs named per-task output heads sharing the ONE backbone:
  ``submit(graph, head="congestion_v2")`` selects a head per request.  The
  bucket's jitted forward takes the head weights as *traced arguments*
  (same shapes for every head), so N heads share each bucket's single
  compiled executable — head registration and selection cost ZERO extra
  compiles (tests/test_backbone.py pins it).  Batches group by
  (shape bucket, head) — a batch is head-homogeneous — while layouts,
  compile caches, and the ``compiles`` counter stay keyed by shape bucket
  alone.  ``head=None`` (default) serves the committed params' own head,
  so ``update_params()`` interacts unchanged;
* **self-healing containment ladder** (DESIGN.md §10) — a failed batch is
  retried with exponential backoff on a freshly-routed device
  (``max_retries``); a batch that keeps failing is *bisected* so only the
  poison member errors while every healthy member is re-served with
  results bit-identical to a fault-free run; device-attributable failures
  feed the :class:`~repro.sharding.specs.DeviceRing` health state
  (K-consecutive-failure quarantine, periodic probe re-admission); an
  optional ``watchdog_s`` bounds per-attempt wall-clock so a wedged
  dispatch becomes a timed-out request instead of a hung ``result()``;
* **admission control** — ``max_queue`` bounds the intake queue with a
  pluggable ``admission`` policy: ``"block"`` (backpressure the producer),
  ``"reject"`` (raise :class:`QueueFullError` promptly), or
  ``"shed_oldest"`` (evict the FIFO head with :class:`LoadShedError`);
  ``validate_inputs`` rejects NaN/Inf-feature graphs at ``submit()`` and a
  non-finite output guard fails poisoned predictions with diagnostics;
* **chaos hook** — pass ``chaos=FaultInjector(...)``
  (fault/inject.py) to exercise every injection point under a
  deterministic seed; ``chaos=None`` (default) executes no injection code;
* **observability** (DESIGN.md §11) — every counter/latency stat lives in a
  per-engine :class:`~repro.obs.metrics.MetricsRegistry` (``stats()`` is a
  back-compat view; ``metrics_text()`` is Prometheus exposition), and
  passing ``recorder=TraceRecorder()`` traces every request through
  submit → admit/shed → bucket → collate → device_put → dispatch → commit
  — healing-ladder steps and chaos injections included — exportable as
  Chrome trace-event JSON (``dump_trace(path)``, perfetto-loadable).  The
  default no-op recorder keeps the happy path allocation-free.

Collated batches also carry a :class:`~repro.graphs.ell.RelationPlan`
(``collate_graphs(with_plan=True)``, the default), so each hetero layer of
the batched forward runs as ONE dispatch per direction-group instead of one
per edge type (DESIGN.md §9); plan layouts are pinned per bucket in the
same ``BucketLayout`` as the per-edge-type arenas.

Two serving modes share the pipeline:

* ``run()`` — drain a snapshot of the queue (partial batches flush
  immediately), the PR-2 batch interface;
* ``serve_forever()`` — block the calling thread serving submits as they
  arrive until ``stop()`` (which drains) or, with ``stop_when_idle=True``,
  until the queue and pipeline are empty.  Typical online use::

      eng = CircuitServeEngine(params, cfg, max_wait_ms=20.0,
                               max_live_buckets=32)
      t = threading.Thread(target=eng.serve_forever)
      t.start()
      rid = eng.submit(graph)               # any thread, any time
      pred = eng.result(rid, timeout=5.0).pred
      eng.stop(); t.join()

Throughput/latency stats (graphs/s, p50/p95 ms, compiles, evictions,
per-device dispatch counts) are kept per run for
benchmarks/bench_serve_circuit.py.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Deque, Dict, List, Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.hetero_mp import HeteroMPConfig
from repro.core.parallel import prefetch
from repro.fault.inject import FaultInjector, InjectedFault
from repro.graphs.circuit import CircuitGraph
from repro.graphs.collate import (ARENA_GRID_BITS, LayoutTable,
                                  collate_graphs, quantize_up)
from repro.models.backbone import BackboneSpec
from repro.models.hgnn import drcircuitgnn_forward
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_RECORDER, NULL_SPAN, Recorder
from repro.sharding.specs import DeviceRing
# Back-compat re-export: percentile lived here through PR 2; it is now a
# train.metrics helper so benchmarks don't import the engine for stats.
from repro.train.metrics import percentile  # noqa: F401


class QueueFullError(RuntimeError):
    """submit() under ``admission="reject"`` with the queue at capacity."""


class LoadShedError(RuntimeError):
    """Request evicted by ``admission="shed_oldest"`` to admit a newer one;
    its ``result()`` re-raises with this cause."""


class WatchdogTimeoutError(RuntimeError):
    """Batch attempt exceeded ``watchdog_s``; its requests are failed so
    ``result()`` returns instead of hanging on a wedged dispatch."""


class NonFiniteInputError(ValueError):
    """submit() rejected a graph whose features contain NaN/Inf."""


class NonFiniteOutputError(RuntimeError):
    """The output guard found NaN/Inf in a member's prediction (poisoned
    input that slipped validation, or an unhealthy kernel/device)."""


@dataclasses.dataclass
class CircuitRequest:
    rid: int
    graph: CircuitGraph
    t_submit: float
    t_done: float = 0.0
    pred: Optional[np.ndarray] = None     # (n_cell,) congestion in [0, 1]
    key: Optional[tuple] = None           # shape bucket, stamped by submit()
    # which registered head serves this request; None = the committed
    # params' own head.  Batching is head-homogeneous (the grouping key is
    # (key, head)) but compilation is not: every head shares the bucket's
    # one executable.
    head: Optional[str] = None
    error: Optional[BaseException] = None  # set when the batch failed
    # which params generation served this request (update_params bumps it);
    # stamped at dispatch, so callers can tell pre- from post-swap results
    params_version: int = 0
    # finalized: result committed (pred or error).  The containment ladder
    # may abandon a wedged attempt whose orphaned thread finishes later —
    # the flag makes the first commit win and every later one a no-op.
    final: bool = False

    @property
    def latency_ms(self) -> float:
        return (self.t_done - self.t_submit) * 1e3


@dataclasses.dataclass
class _BucketState:
    """Engine-side per-bucket derived state, dropped as ONE unit by the
    eviction hook (new per-bucket fields belong here, not in a sibling
    dict, so they cannot leak past max_live_buckets)."""
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    fwd: Optional[object] = None          # the bucket's jitted forward
    sigs: set = dataclasses.field(default_factory=set)  # live (sig, dev)


# sentinel boxed through run()'s prefetch pipeline so a failed prepare
# reaches the containment ladder instead of killing the iterator
_PREP_FAILED = object()

# Per-thread trace track names for the host-side packing spans: a track
# maps 1:1 onto a thread, so B/E prepare spans never interleave within a
# track (the trace validator asserts matched pairs per track).  Pool
# workers and healer threads alike get "worker/<k>" on first emission.
_track_local = threading.local()
_track_counter = itertools.count()


def _worker_track() -> str:
    name = getattr(_track_local, "name", None)
    if name is None:
        name = _track_local.name = f"worker/{next(_track_counter)}"
    return name


class CircuitServeEngine:
    """Micro-batching congestion-prediction server over a fixed model."""

    # Serving wants FEW shape buckets more than tight padding: one mantissa
    # bit (grid {2^e, 3·2^(e-1)}) collapses a size class with ±10% jitter
    # into one bucket at ≤50% worst-case node padding.  Training keeps the
    # finer NODE_GRID_BITS default — its batch membership is fixed, so
    # signatures are stable regardless.
    SERVE_NODE_BITS = 1

    def __init__(self, params, mp_cfg: HeteroMPConfig, *,
                 spec: Optional[BackboneSpec] = None,
                 max_batch: int = 8,
                 n_pack_threads: int = 3,
                 node_bits: int = SERVE_NODE_BITS,
                 arena_bits: int = ARENA_GRID_BITS,
                 chunk: Union[None, int, Dict[str, int]] = None,
                 pad_to_full: bool = True,
                 max_wait_ms: float = 50.0,
                 max_live_buckets: Optional[int] = None,
                 max_finished: Optional[int] = None,
                 devices: Optional[Sequence] = None,
                 # --- self-healing / admission (DESIGN.md §10) ---
                 max_retries: int = 2,
                 retry_backoff_s: float = 0.02,
                 watchdog_s: Optional[float] = None,
                 max_queue: Optional[int] = None,
                 admission: str = "block",
                 validate_inputs: bool = True,
                 quarantine_after: int = 3,
                 probe_interval_s: float = 1.0,
                 chaos: Optional[FaultInjector] = None,
                 # --- observability (DESIGN.md §11) ---
                 recorder: Optional[Recorder] = None,
                 registry: Optional[MetricsRegistry] = None):
        if admission not in ("block", "reject", "shed_oldest"):
            raise ValueError(f"unknown admission policy {admission!r}")
        self.mp_cfg = mp_cfg
        # backbone spec (wiring/remat/depth — DESIGN.md §13); None keeps
        # the vanilla plain stack derived from the params themselves
        self.spec = spec
        self.b = max_batch
        self.n_pack_threads = n_pack_threads
        self.node_bits = node_bits
        self.arena_bits = arena_bits
        self.chunk = chunk
        self.pad_to_full = pad_to_full
        self.max_wait_ms = max_wait_ms
        # Bound on retained results: a long-lived loop whose clients never
        # collect would otherwise pin every request's graph + prediction
        # forever.  None keeps everything (the run()-and-read-back pattern);
        # online clients should either set it or result(..., pop=True).
        self.max_finished = max_finished
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.watchdog_s = watchdog_s
        self.max_queue = max_queue
        self.admission = admission
        self.validate_inputs = validate_inputs
        self.chaos = chaos
        self.ring = DeviceRing(devices, quarantine_after=quarantine_after,
                               probe_interval_s=probe_interval_s)
        self.params = params
        # one committed replica per ring device: a dispatch's placement
        # follows its (committed) arguments, so batch routing is just
        # "device_put the batch to slot i, call with replica i"
        self._params_of = tuple(jax.device_put(params, d)
                                for d in self.ring.devices)
        self._params_version = 0
        # head registry: name -> per-ring-slot (head_w, head_b) replicas,
        # committed like _params_of so a dispatch just indexes its slot
        self._heads: Dict[str, tuple] = {}
        self.queue: Deque[CircuitRequest] = deque()
        self.finished: Dict[int, CircuitRequest] = {}
        self._rid = itertools.count()
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)   # submit/prep/stop
        self._done = threading.Condition(self._lock)   # result() waiters
        self._stop = False
        self._serving = False
        # --- observability: per-engine metrics registry + trace recorder.
        # The recorder defaults to the shared no-op (enabled=False), so the
        # happy path's entire tracing cost is dead `if rec.enabled` checks;
        # pass obs.TraceRecorder() to capture a Chrome trace (dump_trace).
        self.metrics = registry if registry is not None else MetricsRegistry()
        self._rec = recorder if recorder is not None else NULL_RECORDER
        if self.chaos is not None and self._rec.enabled:
            # injected faults become annotated instants on the chaos track
            self.chaos.recorder = self._rec
        # Counter handles cached once (get-or-create is lock-free only on
        # the hit path; the hot path should be a plain .inc()).  These
        # replace the PR-2..6 ad-hoc `_counters` dict; stats() rebuilds the
        # same keys from the registry.
        m = self.metrics
        self._c = {name: m.counter("serve." + name) for name in (
            "batches", "requests", "real_cells", "padded_cells", "wall_s",
            "deadline_flushes", "failures", "retries", "bisects",
            "watchdog_timeouts", "nonfinite_outputs", "rejected_inputs",
            "admission_blocked", "admission_rejected", "admission_shed")}
        self._disp = [m.counter("serve.dispatches", device=i)
                      for i in range(len(self.ring))]
        # latency stats live in their own bounded reservoir so trimming
        # `finished` (max_finished / result(pop=True)) can't skew them
        self._lat = m.histogram("serve.latency_ms")
        # Per-bucket state, all evicted together by the LayoutTable LRU:
        # the arena layout (the table's value) plus the engine-side
        # _BucketState — pack lock, the bucket's jitted forward (owning its
        # compile cache; dropping it is what releases the executables), and
        # its live (signature, device) set.
        self._layouts = LayoutTable(max_live=max_live_buckets,
                                    on_evict=self._evict_bucket,
                                    metrics=m, recorder=self._rec)
        self._buckets: Dict[tuple, _BucketState] = {}
        self._n_compiles = 0        # cumulative, incl. eviction recompiles
        self._healing = 0           # containment-ladder batches in flight

    def _make_fwd(self):
        """The bucket's jitted forward.  The head weights ride as TRACED
        arguments (not baked into the closure): every registered head has
        the shapes of ``params.head_w``/``head_b``, so selecting a head
        changes only argument *values* — the (signature, device) executable
        is shared by all heads and by the default, and head selection can
        never trigger a compile."""
        cfg, spec = self.mp_cfg, self.spec
        return jax.jit(lambda p, hw, hb, g: drcircuitgnn_forward(
            p._replace(head_w=hw, head_b=hb), g, cfg, spec))

    # ------------------------------------------------------------- intake

    def submit(self, graph: CircuitGraph,
               timeout: Optional[float] = None, *,
               head: Optional[str] = None) -> int:
        """Enqueue one request; thread-safe, legal while serve_forever()
        runs (the serving loop is woken immediately).

        ``head`` selects a registered per-task output head by name
        (:meth:`register_head`); ``None`` serves the committed params' own
        head.  An unregistered name raises ``KeyError`` here, at the door.

        With ``max_queue`` set, admission is policy-dependent when the
        queue is full: ``"block"`` waits for capacity (up to ``timeout``,
        raising :class:`TimeoutError`) — backpressure on the producer;
        ``"reject"`` raises :class:`QueueFullError` promptly;
        ``"shed_oldest"`` evicts the FIFO head (its ``result()`` re-raises
        :class:`LoadShedError`) and admits the newcomer.  With
        ``validate_inputs`` (default), NaN/Inf-feature graphs raise
        :class:`NonFiniteInputError` here instead of poisoning a batch."""
        if head is not None and head not in self._heads:
            raise KeyError(f"unknown head {head!r}; registered heads: "
                           f"{sorted(self._heads)}")
        if self.validate_inputs:
            self._validate(graph)
        rid = next(self._rid)
        # bucket key stamped once here, so the batcher's queue scans don't
        # recompute it under the engine lock on every wake
        req = CircuitRequest(rid=rid, graph=graph,
                             t_submit=time.perf_counter(),
                             key=self._group_key(graph), head=head)
        deadline = None if timeout is None else time.perf_counter() + timeout
        with self._work:
            if self.max_queue is not None and \
                    len(self.queue) >= self.max_queue:
                if self.admission == "reject":
                    self._c["admission_rejected"].inc()
                    if self._rec.enabled:
                        self._rec.instant("intake", "admission_reject",
                                          rid=rid)
                    raise QueueFullError(
                        f"queue at capacity ({self.max_queue}); request "
                        f"rejected (admission='reject')")
                if self.admission == "shed_oldest":
                    while len(self.queue) >= self.max_queue:
                        head = self.queue.popleft()
                        self._c["admission_shed"].inc()
                        if self._rec.enabled:
                            self._rec.instant("intake", "admission_shed",
                                              rid=head.rid, admitted=rid)
                        self._finalize_failed_locked(
                            [head], LoadShedError(
                                f"request {head.rid} shed (FIFO head) to "
                                f"admit request {rid} under "
                                f"admission='shed_oldest'"))
                else:                       # "block": producer backpressure
                    waited = False
                    while len(self.queue) >= self.max_queue:
                        if not waited:
                            self._c["admission_blocked"].inc()
                            if self._rec.enabled:
                                self._rec.instant(
                                    "intake", "admission_block", rid=rid)
                            waited = True
                        rem = None if deadline is None \
                            else deadline - time.perf_counter()
                        if rem is not None and rem <= 0:
                            raise TimeoutError(
                                f"submit blocked on full queue "
                                f"({self.max_queue}) for {timeout}s")
                        self._work.wait(rem)
            self.queue.append(req)
            self._work.notify_all()
        if self._rec.enabled:
            self._rec.instant("intake", "submit", rid=rid,
                              bucket=str(req.key))
        return rid

    def _validate(self, g: CircuitGraph) -> None:
        """Per-request input guard: NaN/Inf features are rejected at the
        door — a poisoned member would otherwise fail (or silently corrupt)
        the whole collated batch it lands in."""
        for name in ("x_cell", "x_net"):
            x = np.asarray(getattr(g, name))
            if not np.isfinite(x).all():
                bad = int(np.size(x) - np.count_nonzero(np.isfinite(x)))
                self._c["rejected_inputs"].inc()
                if self._rec.enabled:
                    self._rec.instant("intake", "input_rejected", field=name)
                raise NonFiniteInputError(
                    f"graph.{name} contains {bad} non-finite value(s) "
                    f"of {x.size}; rejected at submit")

    def result(self, rid: int, timeout: Optional[float] = None,
               pop: bool = False) -> CircuitRequest:
        """Block until request ``rid`` finishes (serve_forever must be
        running on another thread, or run() called later).  ``pop=True``
        releases the engine's reference to the finished request — the
        collect-your-results pattern that keeps a long-lived loop's memory
        flat even without ``max_finished``."""
        deadline = None if timeout is None else time.perf_counter() + timeout
        with self._done:
            while rid not in self.finished:
                rem = None if deadline is None \
                    else deadline - time.perf_counter()
                if rem is not None and rem <= 0:
                    raise TimeoutError(f"request {rid} not finished "
                                       f"within {timeout}s")
                self._done.wait(rem)
            req = self.finished.pop(rid) if pop else self.finished[rid]
        if req.error is not None:
            raise RuntimeError(f"request {rid} failed in serving"
                               ) from req.error
        return req

    def _group_key(self, g: CircuitGraph) -> tuple:
        """Per-request shape bucket: requests sharing it collate into one
        signature-stable batch."""
        return (quantize_up(g.n_cell, self.node_bits),
                quantize_up(g.n_net, self.node_bits),
                g.x_cell.shape[1], g.x_net.shape[1])

    # ----------------------------------------------------------- batcher

    def _take_due_batch(self, max_wait_s: float
                        ) -> Optional[List[CircuitRequest]]:
        """Deadline-aware micro-batcher (caller holds the lock).

        Requests group by (shape bucket, head) — a batch is always
        head-homogeneous, so its one dispatch reads one head's weights —
        while layouts and compile caches stay keyed by shape bucket alone.
        Buckets form in FIFO order of first appearance; the first bucket
        with ``max_batch`` compatible requests dispatches full.  With none
        full, the head bucket dispatches partial once its oldest request
        (the queue head — the globally oldest) has waited ``max_wait_s``;
        ``max_wait_s <= 0`` flushes partials immediately (drain mode).
        Returns None when nothing is due.  Taken requests leave the queue;
        the rest keep their relative order."""
        if not self.queue:
            return None
        groups: Dict[tuple, List[CircuitRequest]] = {}
        order: List[tuple] = []
        for r in self.queue:
            k = (r.key, r.head)
            g = groups.get(k)
            if g is None:
                groups[k] = g = []
                order.append(k)
            if len(g) < self.b:
                g.append(r)
        pick = next((k for k in order if len(groups[k]) >= self.b), None)
        if pick is None:
            head = order[0]
            age = time.perf_counter() - groups[head][0].t_submit
            if max_wait_s <= 0 or age >= max_wait_s:
                pick = head
                if max_wait_s > 0 and len(groups[head]) < self.b:
                    self._c["deadline_flushes"].inc()
                    if self._rec.enabled:
                        self._rec.instant("intake", "deadline_flush",
                                          bucket=str(head),
                                          size=len(groups[head]),
                                          waited_ms=age * 1e3)
        if pick is None:
            return None
        chosen = {id(r) for r in groups[pick]}
        # Rotate the deque in place (never rebind self.queue): non-matching
        # requests keep their relative order.
        for _ in range(len(self.queue)):
            r = self.queue.popleft()
            if id(r) not in chosen:
                self.queue.append(r)
        # the queue shrank: wake producers blocked on admission backpressure
        self._work.notify_all()
        if self._rec.enabled:
            self._rec.instant("intake", "batch_formed", bucket=str(pick),
                              size=len(groups[pick]),
                              rids=[r.rid for r in groups[pick]])
        return groups[pick]

    def _next_deadline_s(self, max_wait_s: float) -> Optional[float]:
        """Seconds until the queue head's deadline (lock held); None when
        the queue is empty (wait for a submit)."""
        if not self.queue or max_wait_s <= 0:
            return None if not self.queue else 0.0
        rem = self.queue[0].t_submit + max_wait_s - time.perf_counter()
        return max(rem, 0.0)

    # ---------------------------------------------------------- pipeline

    def _prepare(self, reqs: List[CircuitRequest], dev_idx: int):
        """Host side (runs on the packing pool): collate, pad, transfer to
        ring slot ``dev_idx``.  Collation errors are the batch's fault;
        transfer errors are the device's (ring health records them).

        Traced as B/E spans on the calling thread's ``worker/<k>`` track
        (collate and device_put separately) — a track maps 1:1 onto a
        thread, so the pairs per track are strictly nested."""
        rec = self._rec
        track = _worker_track() if rec.enabled else None
        try:
            with (rec.span(track, "collate", batch=len(reqs),
                           bucket=str(reqs[0].key), device=dev_idx)
                  if rec.enabled else NULL_SPAN):
                if self.chaos is not None:
                    self.chaos.stall("straggler")
                    self.chaos.raise_if("collate")
                graphs = [r.graph for r in reqs]
                n_real = len(graphs)
                if self.pad_to_full and n_real < self.b:
                    # replicate the last member as filler so partial batches
                    # keep the full-batch signature (outputs dropped, loss
                    # weight zero)
                    graphs = graphs + [graphs[-1]] * (self.b - n_real)
                key = reqs[0].key
                # The bucket layout pins chunk widths and floors chunk
                # counts so same-bucket batches share a signature.  Locking
                # is per bucket: prepares of different buckets (the common
                # in-flight set for an interleaved stream) pack
                # concurrently; only the rare same-bucket pair serializes
                # on its layout.
                with self._lock:
                    layout = self._layouts.get(key)  # LRU touch; may evict
                    lock = self._buckets.setdefault(key, _BucketState()).lock
                with lock:
                    batch = collate_graphs(graphs, quantize=True,
                                           node_bits=self.node_bits,
                                           arena_bits=self.arena_bits,
                                           chunk=self.chunk, layout=layout,
                                           n_real=n_real)
        except Exception:
            # host-side failure before the device was touched: the routed
            # slot must not be blamed — but a probe handout must not stay
            # in probing limbo either (it would never be re-probed)
            self.ring.release(dev_idx)
            raise
        try:
            with (rec.span(track, "device_put", device=dev_idx)
                  if rec.enabled else NULL_SPAN):
                if self.chaos is not None:
                    self.chaos.raise_if("device_put", device=dev_idx)
                graph = self.ring.put(batch.graph, dev_idx)
        except Exception:
            self.ring.record_failure(dev_idx)
            raise
        return reqs, batch, graph, key, dev_idx

    def _dispatch(self, prepared):
        reqs, batch, graph, key, dev_idx = prepared
        sig = batch.signature
        rec = self._rec
        t_disp = rec.now() if rec.enabled else 0.0
        compile_new = False
        with self._lock:
            st = self._buckets.setdefault(key, _BucketState())
            if st.fwd is None:
                # first dispatch of the bucket, or its return after an
                # eviction dropped the old jit — either way a fresh compile
                # cache (so "recompiles at most once on return" is exact)
                st.fwd = self._make_fwd()
            fwd = st.fwd
            if (sig, dev_idx) not in st.sigs:
                st.sigs.add((sig, dev_idx))
                self._n_compiles += 1
                compile_new = True
            self._disp[dev_idx].inc()
            # snapshot replicas + version under the lock so a concurrent
            # update_params() can't hand this batch replica A and stamp it
            # version B.  The head replica snapshots under the SAME lock:
            # batches are head-homogeneous (the batcher groups by
            # (key, head)), so reqs[0] speaks for the batch.
            params_d = self._params_of[dev_idx]
            version = self._params_version
            head = reqs[0].head
            hw, hb = (params_d.head_w, params_d.head_b) if head is None \
                else self._heads[head][dev_idx]
        if compile_new:
            self.metrics.inc("serve.compiles")
            if rec.enabled:
                rec.instant(f"device/{dev_idx}", "compile", bucket=str(key))
        try:
            if self.chaos is not None:
                self.chaos.raise_if("dispatch", device=dev_idx)
            out = fwd(params_d, hw, hb, graph)        # async dispatch
        except Exception:
            self.ring.record_failure(dev_idx)
            raise
        # t_disp rides at the END of the tuple: downstream consumers
        # (serve_forever) index dev_idx as entry[4], so never insert before
        return reqs, batch, out, version, dev_idx, t_disp

    def _complete(self, inflight):
        self._commit(inflight, *self._await(inflight))

    def _await(self, inflight):
        """Device barrier and output guard of a dispatched batch; returns
        its predictions and the recorder time they arrived.  Raises for the
        containment ladder; commits nothing (:meth:`_commit`)."""
        reqs, batch, out, version, dev_idx, t_disp = inflight
        try:
            preds = np.asarray(out)                   # device barrier
        except Exception:
            self.ring.record_failure(dev_idx)
            raise
        self.ring.record_success(dev_idx)
        if self.chaos is not None:
            preds = self.chaos.poison(preds)
        # Output guard: a non-finite member prediction must surface as a
        # diagnosed failure, never as a served result.  Raising for the
        # whole batch hands it to the containment ladder — a transient
        # (poisoned output) heals on retry, a poisoned member bisects down
        # to a single diagnosed request.
        bad = [(r, m) for r, m in zip(reqs, batch.members)
               if not np.isfinite(preds[m.cell_off:m.cell_off + m.n_cell]
                                  ).all()]
        if bad:
            self._c["nonfinite_outputs"].inc()
            if self._rec.enabled:
                self._rec.instant("healing", "nonfinite_output",
                                  device=dev_idx,
                                  rids=[r.rid for r, _ in bad])
            rids = [r.rid for r, _ in bad]
            counts = [int((~np.isfinite(
                preds[m.cell_off:m.cell_off + m.n_cell])).sum())
                for _, m in bad]
            raise NonFiniteOutputError(
                f"non-finite predictions for request(s) {rids} "
                f"({counts} bad cells of "
                f"{[m.n_cell for _, m in bad]}) on ring slot {dev_idx}")
        return preds, (self._rec.now() if self._rec.enabled else 0.0)

    def _commit(self, inflight, preds, t_ready: float) -> None:
        """Publish a completed batch's member results (``t_done`` stamped
        here, so commits made in dispatch order finish in it)."""
        reqs, batch, out, version, dev_idx, t_disp = inflight
        now = time.perf_counter()
        with self._done:
            committed = []
            for r, m in zip(reqs, batch.members):
                if r.final:
                    continue          # an abandoned attempt raced us; the
                    #                   first committed result stands
                r.final = True
                # copy: a view would pin the whole padded batch array, so
                # max_finished / result(pop=True) would bound nothing
                r.pred = preds[m.cell_off:m.cell_off + m.n_cell].copy()
                r.t_done = now
                r.params_version = version
                self.finished[r.rid] = r
                self._lat.observe(r.latency_ms)
                committed.append(m)
            if self.max_finished is not None:
                while len(self.finished) > self.max_finished:
                    # dict preserves insertion order: drop the oldest
                    self.finished.pop(next(iter(self.finished)))
            if committed:
                self._c["batches"].inc()
                self._c["requests"].inc(len(committed))
                self._c["real_cells"].inc(sum(m.n_cell for m in committed))
                self._c["padded_cells"].inc(batch.graph.n_cell)
            self._done.notify_all()
        if self._rec.enabled:
            # one X (complete) event per committed batch attempt on the
            # slot's track: attempts may overlap on a slot (pipeline batch
            # vs healing re-dispatch), which B/E pairs cannot express
            self._rec.complete(
                f"device/{dev_idx}", "batch", t_disp,
                t_ready - t_disp, requests=len(committed),
                batch=len(reqs), params_version=version)

    def _evict_bucket(self, key: tuple, layout) -> None:
        """LayoutTable eviction hook (fires under self._lock, from the
        pool thread inside _prepare).  Dropping the bucket's _BucketState
        releases its jit's compiled executables; its signatures stop being
        live, so a future return of the bucket counts as a fresh compile."""
        self._buckets.pop(key, None)

    def _fail(self, reqs: List[CircuitRequest], exc: BaseException) -> None:
        """Contain a batch failure: mark its requests failed (result()
        re-raises for them) and keep serving — one malformed request must
        not strand the rest of the stream."""
        with self._done:
            self._finalize_failed_locked(reqs, exc)

    def _finalize_failed_locked(self, reqs: List[CircuitRequest],
                                exc: BaseException) -> None:
        """Commit failures (engine lock held).  Already-finalized requests
        are skipped — an abandoned watchdog attempt may have raced us."""
        now = time.perf_counter()
        failed = 0
        for r in reqs:
            if r.final:
                continue
            r.final = True
            r.error = exc
            r.t_done = now
            self.finished[r.rid] = r
            failed += 1
        if self.max_finished is not None:
            while len(self.finished) > self.max_finished:
                self.finished.pop(next(iter(self.finished)))
        if failed:
            self._c["failures"].inc(failed)
            if self._rec.enabled:
                self._rec.instant("healing", "fail", count=failed,
                                  error=type(exc).__name__,
                                  rids=[r.rid for r in reqs])
        self._done.notify_all()

    # ------------------------------------------- containment ladder (§10)

    def _attempt(self, reqs: List[CircuitRequest]) -> None:
        """One full serve attempt of ``reqs`` on a freshly-routed device
        (quarantined slots are skipped by the ring; a due probe may be
        handed out here — a healing retry doubling as the health probe)."""
        dev_idx = self.ring.next_index()
        self._complete(self._dispatch(self._prepare(reqs, dev_idx)))

    def _timed_attempt(self, reqs: List[CircuitRequest]) -> None:
        """``_attempt`` bounded by ``watchdog_s``: the attempt runs on a
        disposable daemon thread; on expiry the thread is abandoned (its
        eventual late commit is voided by the requests' ``final`` flags)
        and :class:`WatchdogTimeoutError` raises instead of hanging."""
        if self.watchdog_s is None:
            return self._attempt(reqs)
        box: Dict[str, BaseException] = {}

        def attempt():
            try:
                self._attempt(reqs)
            except BaseException as e:
                box["exc"] = e

        th = threading.Thread(target=attempt, daemon=True)
        th.start()
        th.join(self.watchdog_s)
        if th.is_alive():
            self._c["watchdog_timeouts"].inc()
            if self._rec.enabled:
                self._rec.instant("healing", "watchdog_timeout",
                                  batch=len(reqs), where="healing_attempt")
            raise WatchdogTimeoutError(
                f"healing attempt for batch of {len(reqs)} exceeded "
                f"watchdog {self.watchdog_s}s")
        if "exc" in box:
            raise box["exc"]

    def _heal(self, reqs: List[CircuitRequest], exc: BaseException,
              depth: int = 0) -> None:
        """The containment ladder, run off the serve loop after a batch's
        pipeline attempt failed with ``exc``:

        1. **retry** — up to ``max_retries`` full re-serves with
           exponential backoff, each on a freshly-routed (healthy) device;
        2. **bisect** — a batch that keeps failing splits in half and each
           half re-enters the ladder, so a single poison member is isolated
           in O(log B) rounds and ONLY it ultimately fails;
        3. **fail** — a singleton that keeps failing is marked failed with
           the last error (``result()`` re-raises it).

        Healthy members re-served here are bit-identical to a fault-free
        run: collation is block-diagonal and the bucket layout pins the
        padded shapes, so a member's output rows do not depend on which
        companions shared its batch."""
        for attempt in range(self.max_retries):
            time.sleep(self.retry_backoff_s * (2 ** attempt))
            self._c["retries"].inc()
            if self._rec.enabled:
                self._rec.instant("healing", "retry", attempt=attempt,
                                  depth=depth, batch=len(reqs),
                                  error=type(exc).__name__)
            try:
                self._timed_attempt(reqs)
                return
            except Exception as e:
                exc = e
        if len(reqs) > 1:
            self._c["bisects"].inc()
            if self._rec.enabled:
                self._rec.instant("healing", "bisect", depth=depth,
                                  batch=len(reqs),
                                  error=type(exc).__name__)
            mid = len(reqs) // 2
            self._heal(reqs[:mid], exc, depth + 1)
            self._heal(reqs[mid:], exc, depth + 1)
        else:
            self._fail(reqs, exc)

    def _on_watchdog(self, reqs: List[CircuitRequest],
                     dev_idx: Optional[int] = None) -> None:
        """An in-flight pipeline batch outlived ``watchdog_s``: fail its
        requests now (result() returns a timed-out error instead of
        hanging) and blame the device — a wedge IS a device fault."""
        self._c["watchdog_timeouts"].inc()
        if self._rec.enabled:
            self._rec.instant("healing", "watchdog_timeout",
                              batch=len(reqs), device=dev_idx,
                              where="pipeline")
        if dev_idx is not None:
            self.ring.record_failure(dev_idx)
        self._fail(reqs, WatchdogTimeoutError(
            f"batch of {len(reqs)} in flight past the "
            f"{self.watchdog_s}s watchdog"))

    # ------------------------------------------------------------- modes

    def run(self) -> Dict[int, CircuitRequest]:
        """Drain a snapshot of the queue: partial batches flush immediately
        (no deadline wait), batches round-robin over the device ring, and
        the packing pool keeps one batch in flight per device — the pool
        packs batches i+1..i+D while the D devices run batches i-D+1..i.
        Failed batches enter the containment ladder synchronously (drain
        mode has no serve loop to hand off to)."""
        batches = []
        with self._lock:
            if self._serving:
                raise RuntimeError("run() while serve_forever() is active; "
                                   "use submit()/result() instead")
            while self.queue:
                reqs = self._take_due_batch(0.0)
                batches.append((reqs, self.ring.next_index()))
        t0 = time.perf_counter()
        inflight: Deque = deque()
        n_dev = len(self.ring)

        def prep_safe(reqs, dev_idx):
            # prefetch's iterator re-raises worker exceptions, which would
            # strand every later batch — box the failure instead
            try:
                return self._prepare(reqs, dev_idx)
            except Exception as e:
                return _PREP_FAILED, reqs, e

        def retire(entry):
            try:
                self._complete(entry)
            except Exception as e:
                self._heal(entry[0], e)

        for prepared in prefetch(batches, lambda ba: prep_safe(*ba),
                                 depth=n_dev,
                                 n_threads=max(self.n_pack_threads, n_dev)):
            if prepared[0] is _PREP_FAILED:
                self._heal(prepared[1], prepared[2])
                continue
            try:
                inflight.append(self._dispatch(prepared))
            except Exception as e:
                self._heal(prepared[0], e)
                continue
            if len(inflight) > n_dev:
                retire(inflight.popleft())
        while inflight:
            retire(inflight.popleft())
        self._c["wall_s"].inc(time.perf_counter() - t0)
        return self.finished

    def serve_forever(self, *, stop_when_idle: bool = False
                      ) -> Dict[int, CircuitRequest]:
        """Long-lived online loop: serve submits as they arrive until
        ``stop()`` (which drains the queue and pipeline first) or, with
        ``stop_when_idle``, until queue and pipeline are both empty.

        Blocks the calling thread — run it on a dedicated thread and feed
        it with ``submit()`` from any other.  The pipeline is the drain-mode
        one made incremental: pool threads prepare due batches (one in
        flight per device, plus the pool's own lookahead), the loop
        dispatches them in order, and completed batches are retired eagerly
        whenever no batch is due — so results surface during lulls instead
        of waiting for the next submit.

        Batch failures are contained by the self-healing ladder: a
        prepare/dispatch/complete exception hands the batch to a healer
        thread (retry with backoff → bisect → fail only the poison member;
        ``stats()`` counts ``retries``/``bisects``/``failures``) and the
        loop keeps serving the rest of the stream.  With ``watchdog_s``
        set, a batch wedged in flight past the bound is failed with
        :class:`WatchdogTimeoutError` — ``result()`` never hangs on it."""
        max_wait_s = self.max_wait_ms * 1e-3
        n_dev = len(self.ring)
        prep: Deque = deque()       # (Future of _prepare, reqs, t0, dev)
        inflight: Deque = deque()   # (Future of _await, reqs, t0, dev,
        #                             dispatched entry)

        def overdue(t_start: float) -> bool:
            return (self.watchdog_s is not None
                    and time.perf_counter() - t_start > self.watchdog_s)

        def heal_async(reqs_h, exc):
            # containment off the serve thread: backoff sleeps and bisect
            # rounds must not stall the happy path.  _healing keeps the
            # drain honest (stop() waits for outstanding heals).
            with self._lock:
                self._healing += 1

            def heal():
                try:
                    self._heal(reqs_h, exc)
                finally:
                    with self._work:
                        self._healing -= 1
                        self._work.notify_all()

            threading.Thread(target=heal, daemon=True).start()

        def dispatch_head():
            fut, reqs_p, t_start, _dev = prep.popleft()
            try:
                entry = self._dispatch(fut.result())
            except Exception as e:
                heal_async(reqs_p, e)
                return
            # the pool thread only awaits the device; this thread commits
            # in dispatch order (reap_head), so a later batch whose await
            # returns first cannot finish before an earlier one
            cfut = pool.submit(self._await, entry)
            cfut.add_done_callback(self._notify_work)
            inflight.append((cfut, reqs_p, t_start, entry[4], entry))

        def reap_head():
            cfut, reqs_c, t_start, dev_idx, entry = inflight.popleft()
            if cfut.done():
                exc = cfut.exception()
                if exc is not None:
                    heal_async(reqs_c, exc)
                else:
                    self._commit(entry, *cfut.result())
            else:
                # overdue and still running: abandon the attempt (its
                # result is never committed) and time it out
                self._on_watchdog(reqs_c, dev_idx)

        with self._lock:
            if self._serving:
                raise RuntimeError("serve_forever() is already running")
            self._serving = True
            # do NOT clear _stop here: a stop() that raced ahead of this
            # thread's start must still win (the loop then just drains the
            # already-queued requests and returns).  _stop resets on exit,
            # so a later serve_forever() starts fresh.
        t0 = time.perf_counter()
        # +2 workers: a wedged _await occupying a worker past its
        # watchdog must not starve the packing lookahead
        pool = ThreadPoolExecutor(
            max_workers=max(self.n_pack_threads, n_dev) + 2)
        try:
            while True:
                while prep and prep[0][0].done():
                    dispatch_head()
                while inflight and (inflight[0][0].done()
                                    or overdue(inflight[0][2])):
                    reap_head()
                if prep and overdue(prep[0][2]):
                    # wedged prepare (e.g. a stalled host thread): the
                    # whole batch times out, the orphaned future's result
                    # is never dispatched; the routed slot takes the blame
                    # (which also resolves a probe handout)
                    fut, reqs_p, _, dev_p = prep.popleft()
                    fut.cancel()
                    self._on_watchdog(reqs_p, dev_p)
                reqs = dev_idx = None
                with self._work:
                    # stopping flushes partials immediately — no deadline;
                    # one batch in flight per device bounds device queueing
                    if len(inflight) <= n_dev:
                        reqs = self._take_due_batch(
                            0.0 if self._stop else max_wait_s)
                    if reqs is not None:
                        dev_idx = self.ring.next_index()
                    elif prep or inflight or self._healing:
                        # pipeline busy: sleep until a future lands, a
                        # submit arrives, or the next watchdog/queue
                        # deadline — unless a head is already actionable
                        if not ((prep and prep[0][0].done()) or
                                (inflight and inflight[0][0].done())):
                            self._work.wait(
                                self._tick_s(prep, inflight, max_wait_s))
                        continue
                    elif self._stop or (stop_when_idle and not self.queue):
                        break       # queue empty, pipeline dry, heals done
                    else:
                        # nothing due and nothing in flight: sleep until
                        # the head's deadline / a submit / stop()
                        self._work.wait(self._next_deadline_s(max_wait_s))
                        continue
                fut = pool.submit(self._prepare, reqs, dev_idx)
                fut.add_done_callback(self._notify_work)
                prep.append((fut, reqs, time.perf_counter(), dev_idx))
        finally:
            pool.shutdown(wait=False)
            with self._lock:
                self._serving = False
                self._stop = False
            self._c["wall_s"].inc(time.perf_counter() - t0)
        return self.finished

    def _tick_s(self, prep, inflight, max_wait_s: float) -> Optional[float]:
        """Bounded sleep for the serve loop while the pipeline is busy:
        the soonest of the queue-head deadline and the heads' watchdog
        deadlines (None blocks until a notify)."""
        cands = []
        q = self._next_deadline_s(max_wait_s)
        if q is not None:
            cands.append(q)
        if self.watchdog_s is not None:
            now = time.perf_counter()
            if prep:
                cands.append(max(prep[0][2] + self.watchdog_s - now, 0.0))
            if inflight:
                cands.append(max(inflight[0][2] + self.watchdog_s - now,
                                 0.0))
        return min(cands) if cands else None

    def stop(self) -> None:
        """Ask serve_forever() to drain (queue + in-flight batches) and
        return; thread-safe, and it wins even when it races ahead of the
        serving thread's start (the flag is sticky until a serve loop
        consumes it on exit).  Requests submitted after stop() may still be
        served by the drain or by a later run()/serve_forever()."""
        with self._work:
            self._stop = True
            self._work.notify_all()

    def _notify_work(self, _fut) -> None:
        with self._work:
            self._work.notify_all()

    # --------------------------------------------------------- hot swap

    def update_params(self, params) -> int:
        """Swap the served model without stopping the loop (the
        train-then-serve pattern, ROADMAP): new per-device replicas are
        committed via the same ``_params_of`` isolation every dispatch
        reads, so batches dispatched after the swap use the new weights
        while in-flight batches finish on the old ones — no torn batch ever
        mixes generations (replica + version are snapshotted together under
        the engine lock at dispatch).  Returns the new version; every
        request records the version that served it
        (``result(rid).params_version``).  Params must keep their pytree
        shapes — the per-bucket jits re-run the existing executables, so a
        swap costs zero recompiles.  Registered heads (:meth:`register_head`)
        are independent replicas and survive the swap unchanged; only the
        default ``head=None`` path follows the new params' own head."""
        replicas = tuple(jax.device_put(params, d)
                         for d in self.ring.devices)
        with self._lock:
            self.params = params
            self._params_of = replicas
            self._params_version += 1
            return self._params_version

    @property
    def params_version(self) -> int:
        return self._params_version

    # ------------------------------------------------- multi-tenant heads

    def register_head(self, name: str, head_w, head_b=None) -> None:
        """Install (or replace) a named per-task output head sharing the
        engine's one backbone.  ``head_w``/``head_b`` must match the
        committed params' head shapes — that is what guarantees selection
        is argument-only and costs zero compiles (a different shape would
        be a different model, not a head).  ``head_b=None`` uses a zero
        bias.  Replicas are committed per ring slot exactly like
        ``update_params`` replicas; re-registering a name hot-swaps that
        head between batches.  Requests then opt in per call:
        ``submit(graph, head=name)``."""
        ref_w, ref_b = self.params.head_w, self.params.head_b
        head_w = jnp.asarray(head_w, ref_w.dtype)
        head_b = jnp.zeros_like(ref_b) if head_b is None \
            else jnp.asarray(head_b, ref_b.dtype)
        if head_w.shape != ref_w.shape or head_b.shape != ref_b.shape:
            raise ValueError(
                f"head {name!r} shapes {head_w.shape}/{head_b.shape} do "
                f"not match the backbone's head {ref_w.shape}/{ref_b.shape}"
                f"; a registered head swaps argument values only")
        replicas = tuple(
            (jax.device_put(head_w, d), jax.device_put(head_b, d))
            for d in self.ring.devices)
        with self._lock:
            self._heads[name] = replicas

    @property
    def heads(self) -> tuple:
        """Registered head names, sorted."""
        return tuple(sorted(self._heads))

    # ------------------------------------------------------------- stats

    @property
    def compiles(self) -> int:
        """Cumulative first-dispatches of (padded-shape signature, device)
        pairs — each is one jit compile.  Evicting a bucket drops its live
        signatures, so a bucket that returns after eviction counts its
        recompile here too (cross-checked in stats() against the live
        buckets' own jit caches)."""
        return self._n_compiles

    @property
    def live_buckets(self) -> int:
        return len(self._layouts)

    @property
    def evictions(self) -> int:
        return self._layouts.evictions

    def stats(self) -> Dict[str, float]:
        """Back-compat stats dict, now a VIEW over the metrics registry:
        every pre-PR-7 key is preserved (tests pin the key set), counters
        are integer-valued where they were, and p99_ms rides along from the
        latency histogram.  ``metrics_snapshot()``/``metrics_text()`` expose
        the full registry."""
        with self._lock:
            fwds = [s.fwd for s in self._buckets.values()
                    if s.fwd is not None]
            live = sum(len(s.sigs) for s in self._buckets.values())
        health = self.ring.health()
        ci = {name: int(cnt.value) for name, cnt in self._c.items()}
        wall_s = self._c["wall_s"].value
        p50, p95, p99 = self._lat.percentiles((0.50, 0.95, 0.99))
        out = dict(requests=ci["requests"], batches=ci["batches"],
                   compiles=self.compiles,
                   graphs_per_s=ci["requests"] / max(wall_s, 1e-9),
                   p50_ms=p50, p95_ms=p95, p99_ms=p99,
                   wall_s=wall_s,
                   cell_padding_ratio=(ci["padded_cells"]
                                       / max(ci["real_cells"], 1)),
                   deadline_flushes=ci["deadline_flushes"],
                   failures=ci["failures"],
                   retries=ci["retries"],
                   bisects=ci["bisects"],
                   watchdog_timeouts=ci["watchdog_timeouts"],
                   nonfinite_outputs=ci["nonfinite_outputs"],
                   rejected_inputs=ci["rejected_inputs"],
                   admission_blocked=ci["admission_blocked"],
                   admission_rejected=ci["admission_rejected"],
                   admission_shed=ci["admission_shed"],
                   queued=len(self.queue),
                   device_health=health["states"],
                   quarantines=health["quarantines"],
                   probes=health["probes"],
                   readmissions=health["readmissions"],
                   devices=len(self.ring),
                   dispatches_per_device=[int(c.value) for c in self._disp],
                   live_buckets=self.live_buckets,
                   evictions=self.evictions,
                   live_compiles=live,
                   params_version=self._params_version)
        sizes = [f._cache_size() for f in fwds
                 if callable(getattr(f, "_cache_size", None))]
        if len(sizes) == len(fwds):
            # sum over live per-bucket jits == live (sig, device) pairs;
            # with no evictions this equals the cumulative `compiles`
            out["jit_cache_size"] = sum(sizes)
        return out

    # ----------------------------------------------------- obs exports

    @property
    def recorder(self) -> Recorder:
        return self._rec

    def dump_trace(self, path: str) -> None:
        """Write the engine's Chrome trace-event JSON to ``path`` (open in
        https://ui.perfetto.dev or chrome://tracing).  With the default
        no-op recorder this writes an empty-but-valid trace."""
        self._rec.dump(path)

    def metrics_snapshot(self) -> Dict[str, object]:
        """JSON-able registry snapshot (counters/gauges as numbers,
        histograms as count/sum/min/max/percentile summaries)."""
        return self.metrics.snapshot()

    def metrics_text(self) -> str:
        """Prometheus text exposition of the engine's registry."""
        return self.metrics.to_prometheus()
