"""Deep-backbone memory/time tradeoff: layer-granular remat (DESIGN.md §13).

Trains-step cost of the DR-CircuitGNN backbone at depth {3, 15}, hidden
128, with layer remat on/off.  Peak training memory is read from the
compiled executable itself — ``jit(value_and_grad(loss)).lower(...)
.compile().memory_analysis().temp_size_in_bytes`` — XLA's own activation
arena size, deterministic and backend-honest (no allocator sampling).
Wall-clock is the usual ``time_jit`` median of the full fwd+bwd step.

The tradeoff being measured: with remat, the backward *recomputes* each
layer's fused forward instead of holding its activations, so peak temp
memory stops scaling with depth while step time pays roughly one extra
forward.  ``--smoke`` (CI leg) asserts the contract:

* remat peak temp bytes STRICTLY below the no-remat baseline at the
  deepest point (depth 15, hidden 128);
* loss and every grad leaf allclose remat-vs-not — remat is a
  rematerialization *schedule*, never a different program.

Rows append to ``BENCH_drspmm.json`` (kind="backbone") so the perf
trajectory records the memory curve across PRs.
"""

from __future__ import annotations

import sys
import time

import jax
import numpy as np

from benchmarks.common import append_json, emit, time_jit
from repro.core.hetero_mp import HeteroMPConfig
from repro.graphs.generator import generate_design
from repro.models.backbone import BackboneSpec
from repro.models.hgnn import init_drcircuitgnn, loss_fn


def _peak_temp_bytes(lowered_jit, *args) -> int:
    """XLA's compiled temp-arena size (activations + scratch) in bytes; 0
    when the backend does not expose a memory analysis."""
    try:
        mem = lowered_jit.lower(*args).compile().memory_analysis()
        return int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    except Exception:
        return 0


def bench_backbone(scale=0.04, size="small", hidden=128, k=16,
                   depths=(3, 15), wiring="plain",
                   out_json="BENCH_drspmm.json", iters=5, smoke=False):
    g = generate_design(1, size, scale=scale)[0]
    fc, fn = g.x_cell.shape[1], g.x_net.shape[1]
    cfg = HeteroMPConfig(hidden=hidden, k_cell=k, k_net=k)
    entries = []
    peaks = {}
    for depth in depths:
        params = init_drcircuitgnn(jax.random.PRNGKey(0), hidden=hidden,
                                   n_layers=depth, f_cell=fc, f_net=fn)
        row = dict(depth=depth, hidden=hidden, wiring=wiring)
        out = {}
        for remat in (False, True):
            spec = BackboneSpec(depth=depth, hidden=hidden, wiring=wiring,
                                remat=remat)
            step = jax.jit(jax.value_and_grad(
                lambda p: loss_fn(p, g, cfg, spec)))
            peak = _peak_temp_bytes(step, params)
            us = time_jit(step, params, iters=iters)
            loss, grads = step(params)
            out[remat] = (peak, us, float(loss), grads)
            tag = "remat" if remat else "noremat"
            row[f"{tag}_peak_bytes"] = peak
            row[f"{tag}_step_us"] = us
        p0, t0, l0, g0 = out[False]
        p1, t1, l1, g1 = out[True]
        row["peak_ratio"] = ratio = p1 / max(p0, 1)
        row["time_ratio"] = t1 / max(t0, 1e-9)
        entries.append(row)
        peaks[depth] = (p0, p1)
        emit(f"backbone_step/d{depth}/h{hidden}/noremat", t0, f"peak={p0}B")
        emit(f"backbone_step/d{depth}/h{hidden}/remat", t1,
             f"peak={p1}B;peak_ratio_vs_noremat={ratio:.3f}x;"
             f"time_ratio={row['time_ratio']:.2f}x")
        # Parity is the contract, smoke or not: same loss, same grads.
        np.testing.assert_allclose(l1, l0, rtol=1e-5, atol=1e-7,
                                   err_msg=f"remat loss drifted, d={depth}")
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
                err_msg=f"remat grads drifted, d={depth}")
    if smoke:
        p0, p1 = peaks[max(depths)]
        assert 0 < p1 < p0, (
            f"remat must strictly cut peak temp memory at depth "
            f"{max(depths)}: remat={p1}B vs noremat={p0}B")
    append_json(out_json, dict(
        ts=time.time(), kind="backbone", size=size, scale=scale,
        hidden=hidden, wiring=wiring, backend=jax.default_backend(),
        entries=entries))
    return entries


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--smoke" in sys.argv:
        # CI-sized: tiny graph, but the REAL depth/width points of the
        # acceptance bar (depth 15, hidden 128) with the memory + parity
        # contracts asserted.
        bench_backbone(scale=0.02, iters=3, smoke=True)
    else:
        bench_backbone()
