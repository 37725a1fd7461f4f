"""Driver of the training cells: the program's ``CircuitTrainer`` stepped
over a pool of partitions for a measured window.

Set-up builds ONE trainer and, where the traffic says ``pack: once``, one
packed graph per pool entry (plans cached on the device by the trainer, as
in a multi-epoch job).  With ``pack: every_step`` each step packs a fresh
graph object from its pool entry inside the benchmark's ``pack`` span
(``pack_graph_parallel`` and ``relation_plan_of``), so no cache is hit.

The first pass over the pool runs in set-up.  Its first three steps go
through the window's own call (``train_epoch([g])``) on three different
partitions and are what the reference follows; the whole pass compiles
every shape the window uses.  The window then keeps cycling the pool in
the same seeded order for ``seconds``.
"""

from __future__ import annotations

import contextlib
import gc
import os
import sys
import time
from typing import Callable, Dict, Optional

import numpy as np

N_CHECKED = 3


class CompileCounter:
    """Counts lowerings and backend compiles while ``armed``, and the
    persistent cache's hits and misses over the whole run."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, _duration, **_kw):
        if self.armed and event in self.EVENTS:
            self.count += 1

    def _on_event(self, event, **_kw):
        for k in self.cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                self.cache[k] += 1


def _flat_params(tree) -> Dict[str, np.ndarray]:
    """Program weights as {dotted path: host array}, e.g. layers.0.w_near."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = [getattr(p, "name", getattr(p, "idx", p)) for p in path]
        out[".".join(map(str, parts))] = np.asarray(leaf)
    return out


class _Spans:
    """The benchmark's host spans: timed always, and written into the
    profiler trace when tracing."""

    def __init__(self, tracing: bool):
        self.tracing = tracing
        self.total: Dict[str, float] = {}

    def __call__(self, name: str, fn: Callable, *a):
        import jax
        t0 = time.perf_counter()
        if self.tracing:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                out = fn(*a)
        else:
            out = fn(*a)
        self.total[name] = self.total.get(name, 0.0) + time.perf_counter() - t0
        return out


def run(bench_root: str, cfg: dict, traffic: dict, seed: int, seconds: float,
        trace_dir: Optional[str], t_start: float, log=print) -> dict:
    """One run of a training cell.  Returns the context the metric readers
    take and, under ``readings``, the numbers that cell.py judges against
    the cell's limits (reference/compare.py: the training comparison)."""
    import jax
    sys.path.insert(0, os.path.join(os.path.dirname(bench_root), "src"))
    sys.path.insert(0, bench_root)
    import generator as G
    import work
    from repro.graphs.circuit import relation_plan_of
    from repro.graphs.generator import pack_graph_parallel
    from repro.train.circuit_trainer import CircuitTrainConfig, CircuitTrainer

    counter = CompileCounter()

    def phase(what: str) -> None:
        log(f"[set-up {time.perf_counter() - t_start:7.2f} s] {what}")

    phase("imports done")
    pool = G.make_pool(traffic, seed)
    order = G.visit_order(len(pool), seed)
    shapes = [work.shape_of(p) for p in pool]
    w_seed = G.weight_seed(seed)
    log(f"pool: {len(pool)} partitions, order {order}, sizes "
        f"{[(s['n_cell'], s['n_net'], s['nnz']['near']) for s in shapes]}")

    def pack(part):
        g = pack_graph_parallel(part["coo"], part["n_cell"], part["n_net"],
                                part["x_cell"], part["x_net"], part["y"])
        relation_plan_of(g)
        return g

    phase("pool generated")
    every_step = traffic["pack"] == "every_step"
    packed = None if every_step else [pack(p) for p in pool]
    phase("pool packed" if packed else "packing left to each step")

    tc = CircuitTrainConfig(
        hidden=cfg["hidden"], n_layers=cfg["n_layers"], k_cell=cfg["k_cell"],
        k_net=cfg["k_net"], lr=cfg["lr"], weight_decay=cfg["weight_decay"],
        backend=cfg["backend"], use_plan=cfg["use_plan"], seed=w_seed)
    trainer = CircuitTrainer(tc, cfg["f_cell"], cfg["f_net"])
    phase("trainer built")

    spans = _Spans(tracing=False)

    def one_step(i: int) -> float:
        j = order[i % len(pool)]
        g = spans("pack", pack, pool[j]) if every_step else packed[j]
        return spans("step", trainer.train_epoch, [g])

    # --- set-up: the checked steps, then the rest of one pass ------------
    p0 = _flat_params(trainer.params)
    losses, m1 = [], None
    for i in range(N_CHECKED):
        losses.append(one_step(i))
        if i == 0:
            m1 = _flat_params(trainer.opt_state.m)
        phase(f"step {i + 1} (checked)")
    p3 = _flat_params(trainer.params)
    for i in range(N_CHECKED, len(pool)):
        one_step(i)
        phase(f"step {i + 1}")
    phase(f"persistent cache so far: {counter.cache}")
    b1 = cfg["adam_b1"]
    prog = dict(losses=losses,
                grad1={k: v / (1.0 - b1) for k, v in m1.items()},
                delta={k: p3[k] - p0[k] for k in p0})
    steps_before = trainer.stats()["steps"]
    skipped_before = trainer.nonfinite_grad_steps

    # --- the measured window ----------------------------------------------
    if trace_dir:
        # no Python function tracer (it slows the host's packing by half)
        # and no HLO protos in the trace; TraceMe spans stay on
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    spans.total.clear()
    spans.tracing = bool(trace_dir)
    setup_s = time.perf_counter() - t_start
    counter.armed = True
    i = len(pool)
    window_shapes = []
    with (jax.profiler.TraceAnnotation("bench.window") if trace_dir
          else contextlib.nullcontext()):
        t0 = time.perf_counter()
        while True:
            one_step(i)
            window_shapes.append(shapes[order[i % len(pool)]])
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    counter.armed = False
    if trace_dir:
        jax.profiler.stop_trace()
    steps = trainer.stats()["steps"] - steps_before
    skipped = trainer.nonfinite_grad_steps - skipped_before
    log(f"window: {steps} steps in {window_s:.3f} s; compiles in window: "
        f"{counter.count}; pack spans {spans.total.get('pack', 0.0):.3f} s")

    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))

    # --- free the program's state, then run the reference ------------------
    del trainer, packed
    gc.collect()
    from reference import compare
    from reference import model as R
    checked = [pool[order[i]] for i in range(N_CHECKED)]
    t_ref = time.perf_counter()
    ref = R.train_steps(cfg, checked, w_seed)
    log(f"reference: {N_CHECKED} steps in {time.perf_counter() - t_ref:.2f} s")

    return dict(setup_s=setup_s, window_s=window_s, steps=steps,
                attempted=len(window_shapes), failed=skipped,
                window_shapes=window_shapes, memory_peak_bytes=peak,
                spans=dict(spans.total), compiles_in_window=counter.count,
                readings=compare.readings(prog, ref))
