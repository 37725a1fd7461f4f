"""Fig. 12 / Table 3 analogue: end-to-end breakdown of the two optimizations
— DR-SpMM kernel savings vs parallel (fused) subgraph scheduling savings —
against the sequential dense baseline (the DGL/cuSPARSE-analogue)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.hetero_mp import HeteroMPConfig
from repro.graphs.generator import generate_design
from repro.models.hgnn import init_drcircuitgnn, loss_fn


def _step_time(graph, cfg, sequential: bool, iters=5):
    params = init_drcircuitgnn(jax.random.PRNGKey(0), 16, 16, cfg.hidden)
    # graph closed over (not traced): the adjacency is static per design —
    # the paper's per-graph preprocessing contract — letting XLA specialize
    # the gather/scatter patterns.
    grad_fn = jax.jit(lambda p: jax.grad(loss_fn)(p, graph, cfg))
    if sequential:
        # module-by-module with host sync between edge types (DGL-analogue):
        # emulate by splitting the loss into per-edge partial passes.
        from repro.core.hetero_mp import _aggregate, _sparsify

        def aggregate(et):
            x = (graph.x_cell @ params.in_cell if et != "pinned"
                 else graph.x_net @ params.in_net)
            c = _sparsify(x, cfg.k_cell, cfg) if cfg.use_drelu else None
            return _aggregate(graph, et, x, c, cfg)

        aggs = {et: jax.jit(lambda et=et: aggregate(et))
                for et in ("near", "pin", "pinned")}

        def run():
            for et, f in aggs.items():
                jax.block_until_ready(f())         # sequential module sync
            jax.block_until_ready(grad_fn(params))
    else:
        def run():
            jax.block_until_ready(grad_fn(params))

    run()                                          # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        run()
    return (time.perf_counter() - t0) / iters * 1e6


def bench(scale=0.08):
    graphs = generate_design(2, "medium", scale=scale)[:2]
    for gi, g in enumerate(graphs):
        # the serial per-relation path (use_plan=False) is the reference
        # point for the relation-fused plan path
        base_cfg = HeteroMPConfig(hidden=64, use_drelu=False)
        dr_cfg = HeteroMPConfig(hidden=64, k_cell=16, k_net=16,
                                use_drelu=True, use_plan=False)
        fused_cfg = HeteroMPConfig(hidden=64, k_cell=16, k_net=16,
                                   use_drelu=True)
        t_base = _step_time(g, base_cfg, sequential=True)
        t_kernel = _step_time(g, dr_cfg, sequential=True)
        t_par = _step_time(g, base_cfg, sequential=False)
        t_both = _step_time(g, dr_cfg, sequential=False)
        t_fused = _step_time(g, fused_cfg, sequential=False)
        emit(f"e2e_baseline/graph{gi}", t_base, "sequential+dense")
        emit(f"e2e_dr_kernel/graph{gi}", t_kernel,
             f"dr_savings={100 * (1 - t_kernel / t_base):.1f}%")
        emit(f"e2e_parallel/graph{gi}", t_par,
             f"parallel_savings={100 * (1 - t_par / t_base):.1f}%")
        emit(f"e2e_both/graph{gi}", t_both,
             f"total_speedup={t_base / t_both:.2f}x")
        emit(f"e2e_fused_exec/graph{gi}", t_fused,
             f"total_speedup={t_base / t_fused:.2f}x;"
             f"vs_serial_dr={t_both / t_fused:.2f}x;"
             f"backend={fused_cfg.backend}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    bench()
