"""Fig. 11 analogue: DR-SpMM forward/backward kernel runtime vs the dense
SpMM baseline (cuSPARSE-analogue) and the row-balanced dense-operand SpMM
(GNNAdvisor-analogue), swept over K and embedding dim on the three
representative design sizes (Table 1, scaled for CPU wall-clock).

Timings use the bucketed XLA execution path (the Pallas kernels are
validated in interpret mode, which is not wall-clock-representative on CPU);
the *derived* column reports the byte-model speedup the CBSR gather traffic
predicts on TPU: dense reads N·D per aggregated row, DR reads N·k values +
indices.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import append_json, emit, time_jit
from repro.core.cbsr import cbsr_from_dense
from repro.core.drelu import drelu
from repro.graphs.generator import generate_design
from repro.kernels import ops


def bench(scale=0.08):
    rng = np.random.default_rng(0)
    for size in ("small", "medium", "large"):
        g = generate_design(1, size, scale=scale)[0]
        for etype in ("near", "pin", "pinned"):
            es = g.edges[etype]
            n_src = es.adj.n_src
            for dim in (64, 128):
                x = jnp.asarray(rng.normal(size=(n_src, dim))
                                .astype(np.float32))
                t_dense = time_jit(
                    lambda xv: ops.spmm(es.adj, es.adj_t, xv), x)
                for k in (8, 16, 32):
                    if k >= dim:
                        continue
                    c = cbsr_from_dense(drelu(x, k), k)
                    t_dr = time_jit(
                        lambda v: ops.drspmm(es.adj, es.adj_t, v, c.idx,
                                             dim), c.values)
                    # backward
                    def bwd_dr(v):
                        return jax.grad(lambda q: jnp.sum(ops.drspmm(
                            es.adj, es.adj_t, q, c.idx, dim) ** 2))(v)

                    def bwd_dense(xv):
                        return jax.grad(lambda q: jnp.sum(ops.spmm(
                            es.adj, es.adj_t, q) ** 2))(xv)

                    t_dr_b = time_jit(bwd_dr, c.values)
                    t_dense_b = time_jit(bwd_dense, x)
                    byte_model = dim / (2 * k)      # val+idx per survivor
                    emit(f"drspmm_fwd/{size}/{etype}/d{dim}/k{k}", t_dr,
                         f"speedup_vs_dense={t_dense / t_dr:.2f}x;"
                         f"tpu_byte_model={byte_model:.1f}x")
                    emit(f"drspmm_bwd/{size}/{etype}/d{dim}/k{k}", t_dr_b,
                         f"speedup_vs_dense={t_dense_b / t_dr_b:.2f}x")


def _count_pallas(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        elif eqn.primitive.name == "cond":
            # one branch runs — e.g. the native/interpreted pair of
            # kernels.drspmm.run_pallas, of which the lowering keeps one
            n += max(_count_pallas(b.jaxpr) for b in eqn.params["branches"])
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += _count_pallas(sub)
    return n


def dispatch_count(fn, *args) -> int:
    """Number of pallas_call dispatches in the traced computation."""
    return _count_pallas(jax.make_jaxpr(fn)(*args).jaxpr)


def bench_fused(scale=0.08, size="medium", dim=64, k=16,
                out_json="BENCH_drspmm.json", iters=10, smoke=False):
    """Single-dispatch fused executor vs the per-bucket reference path.

    Two measurements per edge-type direction, matching the repo's timing
    convention (Pallas is validated in interpret mode on CPU, which is not
    wall-clock-representative — see ``bench()``):

    * **dispatches** — pallas_call count in the traced computation:
      ``pallas_fused`` must be exactly 1 per direction vs one per degree
      bucket for ``pallas``.
    * **wall-clock** — the fused arena layout vs the per-bucket layout, both
      executed at real XLA speed (``xla_fused`` vs ``xla``).  This isolates
      what the fused packing buys structurally: the adaptive per-row-block
      chunking (~2× fewer padded slots on heavy-tailed degrees) and one
      segment-combine instead of a scatter-add per bucket.

    Results are appended to ``BENCH_drspmm.json`` so the perf trajectory is
    recorded across PRs.
    """
    rng = np.random.default_rng(0)
    g = generate_design(1, size, scale=scale)[0]
    entries = []
    tot = {"xla": 0.0, "xla_fused": 0.0}
    for etype in ("near", "pin", "pinned"):
        es = g.edges[etype]
        n_src = es.adj.n_src
        x = jnp.asarray(rng.normal(size=(n_src, dim)).astype(np.float32))
        c = cbsr_from_dense(drelu(x, k), k)

        def fwd(v, be):
            return ops.drspmm(es.adj, es.adj_t, v, c.idx, dim, backend=be)

        def bwd(v, be):
            return jax.grad(lambda q: jnp.sum(fwd(q, be) ** 2))(v)

        disp = {be: dispatch_count(lambda v: fwd(v, be), c.values)
                for be in ("pallas", "pallas_fused")}
        stats = {}
        for be in ("xla", "xla_fused"):
            stats[be] = dict(
                fwd_us=time_jit(lambda v: fwd(v, be), c.values, iters=iters),
                bwd_us=time_jit(lambda v: bwd(v, be), c.values, iters=iters),
            )
            tot[be] += stats[be]["fwd_us"] + stats[be]["bwd_us"]
        n_buckets = len(es.adj.buckets)
        sp_f = stats["xla"]["fwd_us"] / stats["xla_fused"]["fwd_us"]
        sp_b = stats["xla"]["bwd_us"] / stats["xla_fused"]["bwd_us"]
        emit(f"fused_fwd/{size}/{etype}/d{dim}/k{k}",
             stats["xla_fused"]["fwd_us"],
             f"speedup_vs_bucketed={sp_f:.2f}x;"
             f"dispatches={disp['pallas_fused']}"
             f"(bucketed={disp['pallas']},buckets={n_buckets})")
        emit(f"fused_bwd/{size}/{etype}/d{dim}/k{k}",
             stats["xla_fused"]["bwd_us"],
             f"speedup_vs_bucketed={sp_b:.2f}x")
        entries.append(dict(etype=etype, size=size, dim=dim, k=k,
                            n_buckets=n_buckets, nnz=es.adj.nnz,
                            dispatches_fused=disp["pallas_fused"],
                            dispatches_bucketed=disp["pallas"],
                            **{f"{be}_{m}": v for be, s in stats.items()
                               for m, v in s.items()},
                            fwd_speedup=sp_f, bwd_speedup=sp_b))
    agg = tot["xla"] / max(tot["xla_fused"], 1e-9)
    emit(f"fused_aggregate/{size}", tot["xla_fused"],
         f"aggregate_speedup_vs_bucketed={agg:.2f}x")
    append_json(out_json, dict(
        ts=time.time(), kind="fused_vs_bucketed", size=size, scale=scale,
        backend=jax.default_backend(), aggregate_speedup=agg,
        entries=entries))
    if smoke:
        # §14 acceptance guard: size-adaptive tiering leaves no relation
        # slower than the bucketed baseline in EITHER direction
        bad = [(e["etype"], e["fwd_speedup"], e["bwd_speedup"])
               for e in entries
               if e["fwd_speedup"] < 1.0 or e["bwd_speedup"] < 1.0]
        assert not bad, f"sub-1.0x fused_vs_bucketed rows: {bad}"
    return entries


def bench_learnable(scale=0.08, size="medium", dim=64, k=16,
                    out_json="BENCH_drspmm.json", iters=10):
    """Fused learnable-edge path vs the per-bucket slab loop, fwd + bwd.

    ``drspmm_learnable`` (differentiable per-edge weights) over each
    edge-type direction: the per-bucket reference gathers the canonical
    weight vector into one eid slab per degree bucket and loops
    (backend="xla"); the fused path gathers straight into the single
    arena (backend="xla_fused").  Timing follows the repo convention
    (xla-family wall-clock on CPU; Pallas interpret-mode anti-correlates
    with TPU, see ``bench()``); the pallas-family dispatch counts record
    the single-dispatch property.  The backward leg times BOTH gradients
    (dw + dx) — the sampled-dot dw reduction rides the same arena.
    """
    from repro.graphs.ell import ell_to_coo, pack_eid_slabs

    rng = np.random.default_rng(0)
    g = generate_design(1, size, scale=scale)[0]
    entries = []
    tot = {"xla": 0.0, "xla_fused": 0.0}
    for etype in ("near", "pin", "pinned"):
        es = g.edges[etype]
        dst, src, _w = ell_to_coo(es.adj)
        order = np.argsort(dst, kind="stable")
        fwd, bwd, _o, nnz = pack_eid_slabs(dst[order], src[order],
                                           es.adj.n_dst, es.adj.n_src)
        n_src = es.adj.n_src
        x = jnp.asarray(rng.normal(size=(n_src, dim)).astype(np.float32))
        c = cbsr_from_dense(drelu(x, k), k)
        w = jnp.asarray(rng.normal(size=nnz).astype(np.float32))

        def fwd_call(wv, be):
            return ops.drspmm_learnable(fwd, bwd, nnz, wv, c.values, c.idx,
                                        dim, backend=be)

        def bwd_call(wv, be):
            return jax.grad(
                lambda q, v: jnp.sum(ops.drspmm_learnable(
                    fwd, bwd, nnz, q, v, c.idx, dim, backend=be) ** 2),
                argnums=(0, 1))(wv, c.values)

        disp = {be: dispatch_count(lambda v: fwd_call(v, be), w)
                for be in ("pallas", "pallas_fused")}
        stats = {}
        for be in ("xla", "xla_fused"):
            stats[be] = dict(
                fwd_us=time_jit(lambda v: fwd_call(v, be), w, iters=iters),
                bwd_us=time_jit(lambda v: bwd_call(v, be), w, iters=iters),
            )
            tot[be] += stats[be]["fwd_us"] + stats[be]["bwd_us"]
        n_buckets = len(fwd.buckets)
        sp_f = stats["xla"]["fwd_us"] / stats["xla_fused"]["fwd_us"]
        sp_b = stats["xla"]["bwd_us"] / stats["xla_fused"]["bwd_us"]
        emit(f"learnable_fwd/{size}/{etype}/d{dim}/k{k}",
             stats["xla_fused"]["fwd_us"],
             f"speedup_vs_bucketed={sp_f:.2f}x;"
             f"dispatches={disp['pallas_fused']}"
             f"(bucketed={disp['pallas']},buckets={n_buckets})")
        emit(f"learnable_bwd/{size}/{etype}/d{dim}/k{k}",
             stats["xla_fused"]["bwd_us"],
             f"speedup_vs_bucketed={sp_b:.2f}x")
        entries.append(dict(etype=etype, size=size, dim=dim, k=k, nnz=nnz,
                            n_buckets=n_buckets,
                            dispatches_fused=disp["pallas_fused"],
                            dispatches_bucketed=disp["pallas"],
                            **{f"{be}_{m}": v for be, s in stats.items()
                               for m, v in s.items()},
                            fwd_speedup=sp_f, bwd_speedup=sp_b))
    agg = tot["xla"] / max(tot["xla_fused"], 1e-9)
    emit(f"learnable_aggregate/{size}", tot["xla_fused"],
         f"aggregate_speedup_vs_bucketed={agg:.2f}x")
    append_json(out_json, dict(
        ts=time.time(), kind="learnable_fused_vs_bucketed", size=size,
        scale=scale, backend=jax.default_backend(), aggregate_speedup=agg,
        entries=entries))
    return entries


def bench_hetero(scale=0.08, size="medium", dim=64, k=16,
                 out_json="BENCH_drspmm.json", iters=10, smoke=False):
    """Relation-fused mega-dispatch vs the serial per-direction hetero
    layer (DESIGN.md §9).

    One full HeteroConv layer, forward and forward+backward, with
    ``use_plan`` toggling between the RelationPlan super-arena path (ONE
    dispatch per populated TIER per direction-group, DESIGN.md §14) and the
    serial loop (one per edge-type direction).  Wall-clock follows the repo
    convention — the xla family on CPU (Pallas interpret-mode
    anti-correlates with TPU, see ``bench()``) — while the pallas family
    records the dispatch counts; ``smoke=True`` asserts them (fwd = number
    of populated tiers ≤ 2, grad = 2× that, vs 3 / 6 serial) plus the §14
    no-regression property (plan path at least as fast as serial), the
    acceptance guards CI runs.  The JSON row carries a per-phase forward
    breakdown — host pack (one-time, amortized), type-concat CBSR gather,
    tiered kernel dispatches, output split — so forward-path overhead
    regressions are attributable without a profiler.
    """
    from repro.core.hetero_mp import (HeteroMPConfig, hetero_conv,
                                      init_hetero_layer)
    from repro.graphs.circuit import relation_plan_of

    rng = np.random.default_rng(0)
    g = generate_design(1, size, scale=scale)[0]
    lp = init_hetero_layer(jax.random.PRNGKey(0), dim)
    x_cell = jnp.asarray(rng.normal(size=(g.n_cell, dim)).astype(np.float32))
    x_net = jnp.asarray(rng.normal(size=(g.n_net, dim)).astype(np.float32))

    def cfg_of(backend, use_plan):
        return HeteroMPConfig(hidden=dim, k_cell=k, k_net=k,
                              backend=backend, use_plan=use_plan)

    def fwd(cfg):
        return lambda xc: hetero_conv(lp, g, xc, x_net, cfg)

    def fwd_bwd(cfg):
        # sum over BOTH outputs, differentiate wrt BOTH inputs, so no
        # direction's forward or backward is dead-code-eliminated
        return lambda xc, xn: jax.grad(lambda qc, qn: sum(
            jnp.sum(y ** 2) for y in hetero_conv(lp, g, qc, qn, cfg)),
            argnums=(0, 1))(xc, xn)

    plan = relation_plan_of(g)
    disp = {}
    for name, use_plan in (("plan", True), ("serial", False)):
        c = cfg_of("pallas_fused", use_plan)
        disp[name] = dict(fwd=dispatch_count(fwd(c), x_cell),
                          grad=dispatch_count(fwd_bwd(c), x_cell, x_net))
    if smoke:
        # one dispatch per POPULATED tier per direction (§14): a mixed-tier
        # plan costs 2 fwd / 4 bwd, single-tier plans keep the original 1/2
        n_tiers = int(plan.has_arena) + int(plan.has_dense)
        assert disp["plan"] == dict(fwd=n_tiers, grad=2 * n_tiers), \
            (disp, n_tiers)
        assert disp["serial"] == dict(fwd=3, grad=6), disp

    stats = {}
    for name, use_plan in (("plan", True), ("serial", False)):
        c = cfg_of("xla_fused", use_plan)
        stats[name] = dict(
            fwd_us=time_jit(fwd(c), x_cell, iters=iters),
            grad_us=time_jit(fwd_bwd(c), x_cell, x_net, iters=iters))
    sp_f = stats["serial"]["fwd_us"] / stats["plan"]["fwd_us"]
    sp_g = stats["serial"]["grad_us"] / stats["plan"]["grad_us"]
    if smoke:
        # §14 acceptance guard: the tiered plan path never loses to serial
        assert sp_f >= 1.0 and sp_g >= 1.0, (sp_f, sp_g)

    # Per-phase forward breakdown: pack is host-side wall-clock on a fresh
    # identical graph (the memo makes the resident plan free); the other
    # phases isolate the plan forward's three jitted stages.
    cb = {"cell": cbsr_from_dense(drelu(x_cell, k), k),
          "net": cbsr_from_dense(drelu(x_net, k), k)}
    vals = tuple(cb[t].values for t in plan.src_types)
    idxs = tuple(cb[t].idx for t in plan.src_types)
    g2 = generate_design(1, size, scale=scale)[0]
    t0 = time.perf_counter()
    relation_plan_of(g2)
    pack_us = (time.perf_counter() - t0) * 1e6
    xv, xi, _ = ops._multi_concat(plan, vals, idxs)
    y_cat = ops._hybrid_fwd(plan, xv, xi, dim, "xla_fused")
    phases = dict(
        pack_us=pack_us,
        gather_us=time_jit(lambda *v: ops._multi_concat(plan, v, idxs),
                           *vals, iters=iters),
        kernel_us=time_jit(
            lambda v: ops._hybrid_fwd(plan, v, xi, dim, "xla_fused"),
            xv, iters=iters),
        split_us=time_jit(lambda y: ops._split_out(plan, y), y_cat,
                          iters=iters))
    emit(f"hetero_plan_phases/{size}/d{dim}/k{k}", phases["kernel_us"],
         ";".join(f"{p}={v:.1f}us" for p, v in phases.items()))
    agg = ((stats["serial"]["fwd_us"] + stats["serial"]["grad_us"])
           / (stats["plan"]["fwd_us"] + stats["plan"]["grad_us"]))
    emit(f"hetero_plan_fwd/{size}/d{dim}/k{k}", stats["plan"]["fwd_us"],
         f"speedup_vs_serial={sp_f:.2f}x;"
         f"dispatches={disp['plan']['fwd']}(serial={disp['serial']['fwd']})")
    emit(f"hetero_plan_grad/{size}/d{dim}/k{k}", stats["plan"]["grad_us"],
         f"speedup_vs_serial={sp_g:.2f}x;"
         f"dispatches={disp['plan']['grad']}(serial={disp['serial']['grad']})")
    emit(f"hetero_plan_aggregate/{size}",
         stats["plan"]["fwd_us"] + stats["plan"]["grad_us"],
         f"aggregate_speedup_vs_serial={agg:.2f}x")
    append_json(out_json, dict(
        ts=time.time(), kind="hetero_plan_vs_serial", size=size, scale=scale,
        dim=dim, k=k, backend=jax.default_backend(),
        n_cell=g.n_cell, n_net=g.n_net,
        tiers={s.etype: s.tier for s in plan.segments}, phases=phases,
        dispatches=disp, aggregate_speedup=agg,
        fwd_speedup=sp_f, grad_speedup=sp_g,
        **{f"{n}_{m}": v for n, s in stats.items() for m, v in s.items()}))
    return stats


def bench_sharded(scale=0.08, size="medium", dim=64, k=16,
                  out_json="BENCH_drspmm.json", iters=10, smoke=False,
                  device_counts=(2, 4)):
    """Mesh-sharded mega-dispatch (DESIGN.md §12) vs the single-device plan
    path, per shard count.

    On an accelerator every shard count runs in this process over
    ``jax.devices()[:n]`` with the default backend (one process holds the
    chips; a child could not get them).  On CPU, XLA's device count locks at
    the first jax import, so each shard count runs in a child interpreter
    with ``--xla_force_host_platform_device_count=n`` (the tests/_multidev.py
    pattern) on the xla family — Pallas interpret-mode is not
    wall-clock-representative — and prints one ``SHARDED_RESULT`` JSON line
    this parent collects.  Each leg records the per-device arena footprint
    (owned slabs + halo tables) against full-graph replication.
    ``smoke=True`` asserts numeric parity with ``drspmm_multi`` AND that
    every shard's footprint stays strictly below replicating the whole
    super-arena — the reason sharding exists.
    """
    on_chip = jax.default_backend() != "cpu"
    entries = []
    for n in device_counts:
        if on_chip:
            if n > jax.device_count():
                continue
            res = _sharded_leg(n, scale, size, dim, k, iters, smoke,
                               ops.DEFAULT_BACKEND)
        else:
            res = _sharded_child(n, scale, size, dim, k, iters, smoke)
        emit(f"sharded_fwd/{size}/n{n}/d{dim}/k{k}", res["sharded_fwd_us"],
             f"vs_single={res['single_fwd_us'] / res['sharded_fwd_us']:.2f}x;"
             f"shard_bytes={res['max_shard_bytes']}"
             f"(full={res['full_arena_bytes']})")
        emit(f"sharded_grad/{size}/n{n}/d{dim}/k{k}", res["sharded_grad_us"],
             f"vs_single={res['single_grad_us'] / res['sharded_grad_us']:.2f}x;"
             f"halo_rows={res['total_halo_rows']}(pad={res['halo_pad']})")
        entries.append(res)
    append_json(out_json, dict(
        ts=time.time(), kind="sharded", size=size, scale=scale, dim=dim,
        k=k, backend=jax.default_backend(), entries=entries))
    return entries


def _sharded_child(n, scale, size, dim, k, iters, smoke) -> dict:
    """Run one CPU leg under a forced n-device XLA runtime in a child."""
    import json
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"
    flags = [t for t in env.get("XLA_FLAGS", "").split()
             if not t.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={n}"])
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_drspmm",
         "--_sharded-child", str(n), str(scale), size, str(dim),
         str(k), str(iters), str(int(smoke))],
        env=env, cwd=root, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("SHARDED_RESULT ")][-1]
    return json.loads(line[len("SHARDED_RESULT "):])


def _sharded_leg(n, scale, size, dim, k, iters, smoke, backend) -> dict:
    """One shard count: sharded vs single-device timings, footprint and
    (``smoke``) parity, over the first ``n`` devices."""
    from repro.graphs.circuit import relation_plan_of, sharded_plan_of

    assert jax.device_count() >= n, (jax.device_count(), n)
    rng = np.random.default_rng(0)
    g = generate_design(1, size, scale=scale)[0]
    plan = relation_plan_of(g)
    splan = sharded_plan_of(g, n)
    cc = cbsr_from_dense(drelu(jnp.asarray(
        rng.normal(size=(g.n_cell, dim)).astype(np.float32)), k), k)
    cn = cbsr_from_dense(drelu(jnp.asarray(
        rng.normal(size=(g.n_net, dim)).astype(np.float32)), k), k)

    def call(op, p, vc, vn):
        return op(p, {"cell": (vc, cc.idx), "net": (vn, cn.idx)}, dim,
                  backend=backend)

    def grad_call(op, p):
        return lambda vc, vn: jax.grad(
            lambda qc, qn: sum(jnp.sum(jnp.sin(y)) for y in
                               call(op, p, qc, qn).values()),
            argnums=(0, 1))(vc, vn)

    stats = {}
    for name, op, p in (("sharded", ops.drspmm_multi_sharded, splan),
                        ("single", ops.drspmm_multi, plan)):
        stats[f"{name}_fwd_us"] = time_jit(
            lambda vc, vn: call(op, p, vc, vn), cc.values, cn.values,
            iters=iters)
        stats[f"{name}_grad_us"] = time_jit(
            grad_call(op, p), cc.values, cn.values, iters=iters)

    hs = splan.halo_stats()
    if smoke:
        y_sh = call(ops.drspmm_multi_sharded, splan, cc.values, cn.values)
        y_1 = call(ops.drspmm_multi, plan, cc.values, cn.values)
        for et in y_1:
            ref = np.asarray(y_1[et])
            atol = 1e-4 * max(1.0, float(np.abs(ref).max()))
            np.testing.assert_allclose(np.asarray(y_sh[et]), ref,
                                       atol=atol, rtol=1e-5,
                                       err_msg=f"sharded parity {et}")
        assert hs["max_shard_bytes"] < hs["full_arena_bytes"], hs
    return dict(n_shards=n, n_cell=g.n_cell, n_net=g.n_net,
                backend=backend, platform=jax.devices()[0].platform,
                max_shard_bytes=hs["max_shard_bytes"],
                full_arena_bytes=hs["full_arena_bytes"],
                total_halo_rows=hs["total_halo_rows"], halo_pad=hs["halo_pad"],
                **stats)


if __name__ == "__main__":
    if "--_sharded-child" in sys.argv:
        import json
        a = sys.argv[sys.argv.index("--_sharded-child") + 1:]
        print("SHARDED_RESULT " + json.dumps(_sharded_leg(
            int(a[0]), float(a[1]), a[2], int(a[3]), int(a[4]), int(a[5]),
            bool(int(a[6])), "xla_fused")))
        sys.exit(0)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--smoke" in sys.argv:
        # CI-sized run: tiny graph, fused-vs-bucketed + plan-vs-serial
        # comparisons (fixed-weight, learnable, and hetero-layer legs),
        # with the dispatch-per-tier property and the §14 no-sub-1.0x
        # speedup floors asserted.
        # asserted floors run at 10 iters: the µs-scale dense-tier rows
        # and the ~1.1x plan-vs-serial margin at this scale are real but
        # inside 3-iter median jitter
        bench_fused(scale=0.02, size="small", iters=10, smoke=True)
        bench_learnable(scale=0.02, size="small", iters=3)
        bench_hetero(scale=0.02, size="small", iters=10, smoke=True)
        bench_sharded(scale=0.02, size="small", iters=3, smoke=True)
    else:
        bench_fused()
        bench_learnable()
        bench_hetero()
        bench_sharded()
        bench()
