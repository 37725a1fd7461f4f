"""Driver of the DeepGEN training cell: the program's ``CircuitTrainer``
with ``model="deepgen"`` stepped over a pool of partitions for a measured
window, as ``drivers/train.py`` steps the DR model (packed once, plans
cached on the device, the first pass in set-up, the first three steps
checked against the plain reference, ``reference/deepgen.py``).

A program without the DeepGEN model is refused before any long work: the
run exits non-zero within seconds.
"""

from __future__ import annotations

import contextlib
import gc
import os
import sys
import time
from typing import Dict, Optional

import numpy as np

N_CHECKED = 3


def per_layer(flat: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """``layers.<name>`` leaves stacked over depth, split into
    ``layers.<i>.<name>``: the reference's keys, so each layer's leaf is
    compared on its own."""
    out = {}
    for k, v in flat.items():
        if k.startswith("layers."):
            for i, vi in enumerate(v):
                out[f"layers.{i}.{k[len('layers.'):]}"] = vi
        else:
            out[k] = v
    return out


def run(bench_root: str, cfg: dict, traffic: dict, seed: int, seconds: float,
        trace_dir: Optional[str], t_start: float, log=print) -> dict:
    """One run of the DeepGEN training cell; returns the context the metric
    readers take and, under ``readings``, the compared numbers."""
    sys.path.insert(0, os.path.join(os.path.dirname(bench_root), "src"))
    sys.path.insert(0, bench_root)
    try:
        import repro.models.deepgen  # noqa: F401
        from repro.train.circuit_trainer import (CircuitTrainConfig,
                                                 CircuitTrainer)
        CircuitTrainConfig(model="deepgen")
    except (ImportError, TypeError) as e:
        raise SystemExit(f"the program has no DeepGEN model: {e}")
    import jax
    import generator as G
    import work
    from drivers.train import CompileCounter, _flat_params, _Spans
    from repro.graphs.circuit import relation_plan_of
    from repro.graphs.generator import pack_graph_parallel

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

    packed = [pack(p) for p in pool]
    phase("pool packed")
    tc = CircuitTrainConfig(
        model="deepgen", hidden=cfg["hidden"], n_layers=cfg["n_layers"],
        lr=cfg["lr"], weight_decay=cfg["weight_decay"],
        backend=cfg["backend"], use_plan=cfg["use_plan"], seed=w_seed)
    trainer = CircuitTrainer(tc, cfg["f_cell"], cfg["f_net"])
    phase("trainer built")
    spans = _Spans(tracing=False)

    def one_step(i: int) -> float:
        g = packed[order[i % len(pool)]]
        return spans("step", trainer.train_epoch, [g])

    # --- set-up: the checked steps, then the rest of one pass ------------
    p0 = per_layer(_flat_params(trainer.params))
    losses, m1 = [], None
    for i in range(N_CHECKED):
        losses.append(one_step(i))
        if i == 0:
            m1 = per_layer(_flat_params(trainer.opt_state.m))
        phase(f"step {i + 1} (checked)")
    p3 = per_layer(_flat_params(trainer.params))
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
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
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
        f"{counter.count}")
    peak = int((jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0))

    # --- free the program's state, then run the reference ------------------
    del trainer, packed
    gc.collect()
    from reference import compare
    from reference import deepgen as R
    checked = [pool[order[i]] for i in range(N_CHECKED)]
    t_ref = time.perf_counter()
    ref = R.train_steps(cfg, checked, w_seed)
    peak_after = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0)
    log(f"reference: {N_CHECKED} steps in {time.perf_counter() - t_ref:.2f} s"
        f"; device peak after it {peak_after} B (program {peak} B)")
    return dict(setup_s=setup_s, window_s=window_s, steps=steps,
                attempted=len(window_shapes), failed=skipped,
                window_shapes=window_shapes, memory_peak_bytes=peak,
                spans=dict(spans.total), compiles_in_window=counter.count,
                readings=compare.readings(prog, ref))
