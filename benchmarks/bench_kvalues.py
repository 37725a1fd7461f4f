"""Fig. 10 analogue: correlation scores + step-time across (k_net, k_cell)
on Mini-CircuitNet (synthetic).  Short training runs; rank correlations are
the metric that matters (Sec. 4.3)."""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.graphs.generator import generate_design
from repro.train.circuit_trainer import CircuitTrainConfig, CircuitTrainer


def bench(scale=0.05, epochs=4):
    train = generate_design(0, "small", scale=scale)
    test = generate_design(99, "small", scale=scale)
    base = None
    for k in (2, 4, 8, 16, 32):
        cfg = CircuitTrainConfig(epochs=epochs, hidden=64,
                                 k_cell=k, k_net=k)
        tr = CircuitTrainer(cfg, 16, 16)
        t0 = time.perf_counter()
        out = tr.fit(train, eval_graphs=test)
        dt = (time.perf_counter() - t0) * 1e6 / epochs / len(train)
        if base is None:
            base = dt
        m = out["final"]
        emit(f"kvalue_sweep/k{k}", dt,
             f"pearson={m['pearson']:.3f};spearman={m['spearman']:.3f};"
             f"kendall={m['kendall']:.3f};mae={m['mae']:.3f};"
             f"rmse={m['rmse']:.3f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    bench()
