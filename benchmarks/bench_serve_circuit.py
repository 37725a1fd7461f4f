"""Sequential vs batched vs online multi-device circuit serving (the
ISSUE-2/ISSUE-3 acceptance benchmark).

The stream is the adversarial serving case: many small designs whose sizes
jitter within two size classes, interleaved.  Three modes:

* **sequential** — the natural per-graph path: one jitted forward taking
  each graph as a traced argument, so every distinct graph shape compiles
  and every graph is its own dispatch (the HOGA-motivated pathology);
* **batched** — :class:`CircuitServeEngine.run`: block-diagonal collation
  into quantized shape buckets, one fused dispatch per micro-batch, host
  packing of batch i+1 overlapped with device execution of batch i (pinned
  to one device so the row stays comparable across PRs);
* **online** — ``serve_forever()`` fed from a producer thread: continuous
  intake, deadline batching, and round-robin dispatch over every available
  device (2+ under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

Reported per mode: aggregate graphs/s over the cold stream (compiles
included — that IS serving cost for a mixed stream), steady-state graphs/s
over a warm second pass, p50/p95 request latency, and compile count; the
online row adds per-device dispatch counts and deadline flushes.  Appended
to ``BENCH_serve.json`` so the serving-perf trajectory is recorded across
PRs.  (Interpret-mode caveat: on CPU the timed backends are the XLA-path
ones — see DESIGN.md §4/§7 — so these numbers track real wall-clock.)
"""

from __future__ import annotations

import sys
import threading
import time

import jax
import numpy as np

from benchmarks.common import append_json, emit
from repro.core.hetero_mp import HeteroMPConfig
from repro.fault.inject import FaultInjector, FaultRule
from repro.graphs.generator import generate_partition, pack_graph_parallel
from repro.models.hgnn import drcircuitgnn_forward, init_drcircuitgnn
from repro.serve import CircuitServeEngine
from repro.train.metrics import percentile


def make_stream(rng, n_per_class: int, classes=((220, 110), (430, 215)),
                jitter: float = 0.06):
    """Interleaved mixed-size stream: sizes jitter within each class."""
    per_class = []
    for ci, (nc, nn) in enumerate(classes):
        gs = []
        for s in range(n_per_class):
            c = int(nc * (1 + rng.uniform(-jitter, jitter)))
            n = int(nn * (1 + rng.uniform(-jitter, jitter)))
            coo, xc, xn, y = generate_partition(
                np.random.default_rng(1000 * ci + s), c, n)
            gs.append(pack_graph_parallel(coo, c, n, xc, xn, y))
        per_class.append(gs)
    return [g for tup in zip(*per_class) for g in tup]


def bench_sequential(params, cfg, stream):
    fwd = jax.jit(lambda p, g: drcircuitgnn_forward(p, g, cfg))
    lat = []
    t0 = time.perf_counter()
    for g in stream:
        t1 = time.perf_counter()
        jax.block_until_ready(fwd(params, g))
        lat.append((time.perf_counter() - t1) * 1e3)
    cold_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    for g in stream:                       # warm pass: shapes already built
        jax.block_until_ready(fwd(params, g))
    warm_wall = time.perf_counter() - t0
    lat.sort()
    p50, p95 = percentile(lat, 0.5), percentile(lat, 0.95)
    compiles = fwd._cache_size() if hasattr(fwd, "_cache_size") else -1
    return dict(graphs_per_s=len(stream) / cold_wall,
                warm_graphs_per_s=len(stream) / warm_wall,
                p50_ms=p50, p95_ms=p95, compiles=compiles)


def bench_online(params, cfg, stream, max_batch: int,
                 max_wait_ms: float = 25.0):
    """serve_forever() fed by this (producer) thread; every local device."""
    eng = CircuitServeEngine(params, cfg, max_batch=max_batch,
                             max_wait_ms=max_wait_ms)
    server = threading.Thread(target=eng.serve_forever)
    server.start()
    for g in stream:
        eng.submit(g)
    eng.stop()
    server.join()
    cold = eng.stats()
    server = threading.Thread(target=eng.serve_forever)
    server.start()
    for g in stream:                       # warm pass: buckets already built
        eng.submit(g)
    eng.stop()
    server.join()
    warm = eng.stats()
    warm_gps = ((warm["requests"] - cold["requests"])
                / max(warm["wall_s"] - cold["wall_s"], 1e-9))
    # cold-pass numbers throughout so the row is internally consistent
    # (sum(dispatches_per_device) == batches)
    return dict(graphs_per_s=cold["requests"] / max(cold["wall_s"], 1e-9),
                warm_graphs_per_s=warm_gps,
                p50_ms=cold["p50_ms"], p95_ms=cold["p95_ms"],
                compiles=cold["compiles"], batches=cold["batches"],
                devices=cold["devices"],
                dispatches_per_device=cold["dispatches_per_device"],
                deadline_flushes=cold["deadline_flushes"])


def bench_degraded(params, cfg, stream, max_batch: int,
                   max_wait_ms: float = 25.0):
    """Online serving with 1-of-N ring slots force-quarantined (the state
    after a device loss, or an ops drain for maintenance): the stream must
    complete on the survivors with zero failures, and the row records the
    throughput cost of losing a slot.  With a single local device the ring
    gets two logical slots on it, so routing-around-quarantine is still
    exercised.  The probe interval is pushed out so no re-admission
    muddies the measurement."""
    devs = list(jax.local_devices())
    if len(devs) < 2:
        devs = [devs[0], devs[0]]
    eng = CircuitServeEngine(params, cfg, max_batch=max_batch,
                             max_wait_ms=max_wait_ms, devices=devs,
                             probe_interval_s=1e9)
    lost = len(devs) - 1
    eng.ring.quarantine(lost)
    server = threading.Thread(target=eng.serve_forever)
    server.start()
    for g in stream:
        eng.submit(g)
    eng.stop()
    server.join()
    st = eng.stats()
    assert st["failures"] == 0, st
    assert st["dispatches_per_device"][lost] == 0, st
    return dict(graphs_per_s=st["requests"] / max(st["wall_s"], 1e-9),
                p50_ms=st["p50_ms"], p95_ms=st["p95_ms"],
                devices=st["devices"], quarantined_slot=lost,
                dispatches_per_device=st["dispatches_per_device"],
                device_health=st["device_health"],
                failures=st["failures"])


def bench_sustained(params, cfg, stream, max_batch: int, *,
                    target_qps: float = 80.0, n_producers: int = 2,
                    max_wait_ms: float = 8.0, chaos=None):
    """Sustained-load serving: ``n_producers`` threads submit at an
    aggregate ``target_qps``, paced so inter-arrival gaps exceed
    ``max_wait_ms`` per bucket — the **deadline-flush** regime (partial
    batches shipped when their oldest request's deadline expires), which
    the burst benchmarks above never enter.  Latency percentiles come from
    the engine's metrics registry (``serve.latency_ms`` histogram), and the
    row records flush/shed counts; the chaos variant overlays a seeded
    fault schedule to price the healing ladder under load."""
    eng = CircuitServeEngine(params, cfg, max_batch=max_batch,
                             max_wait_ms=max_wait_ms, chaos=chaos,
                             max_queue=max(4 * max_batch, 16),
                             admission="shed_oldest")
    # warm pass through the SAME engine (run(), unpaced) so the paced phase
    # measures steady-state serving, not bucket compilation; paced-phase
    # numbers below are deltas over this snapshot
    for g in stream:
        eng.submit(g)
    eng.run()
    cold = eng.stats()
    hist = eng.metrics.histogram("serve.latency_ms")
    n_warm = len(hist.window())

    server = threading.Thread(target=eng.serve_forever)
    server.start()
    gap_s = n_producers / max(target_qps, 1e-9)

    def produce(shard):
        for g in shard:
            t_next = time.perf_counter() + gap_s
            eng.submit(g)
            dt = t_next - time.perf_counter()
            if dt > 0:
                time.sleep(dt)

    t0 = time.perf_counter()
    producers = [threading.Thread(target=produce,
                                  args=(stream[i::n_producers],))
                 for i in range(n_producers)]
    for p in producers:
        p.start()
    for p in producers:
        p.join()
    eng.stop()
    server.join()
    wall = time.perf_counter() - t0
    st = eng.stats()
    lat = sorted(hist.window()[n_warm:])   # paced-phase latencies only
    return dict(target_qps=target_qps,
                achieved_qps=(st["requests"] - cold["requests"]) / wall,
                n_producers=n_producers, max_wait_ms=max_wait_ms,
                p50_ms=percentile(lat, 0.50), p95_ms=percentile(lat, 0.95),
                p99_ms=percentile(lat, 0.99),
                deadline_flushes=(st["deadline_flushes"]
                                  - cold["deadline_flushes"]),
                shed=st["admission_shed"] - cold["admission_shed"],
                failures=st["failures"] - cold["failures"],
                retries=st["retries"] - cold["retries"],
                requests=st["requests"] - cold["requests"],
                batches=st["batches"] - cold["batches"],
                chaos=chaos is not None)


def bench_batched(params, cfg, stream, max_batch: int):
    # pinned to one device so the row stays comparable across PRs (the
    # multi-device path gets its own `online` row)
    eng = CircuitServeEngine(params, cfg, max_batch=max_batch,
                             devices=jax.local_devices()[:1])
    for g in stream:
        eng.submit(g)
    eng.run()
    cold = eng.stats()
    for g in stream:                       # warm pass: buckets already built
        eng.submit(g)
    eng.run()
    warm = eng.stats()
    warm_gps = ((warm["requests"] - cold["requests"])
                / max(warm["wall_s"] - cold["wall_s"], 1e-9))
    return dict(graphs_per_s=cold["requests"] / cold["wall_s"],
                warm_graphs_per_s=warm_gps,
                p50_ms=cold["p50_ms"], p95_ms=cold["p95_ms"],
                compiles=cold["compiles"], batches=cold["batches"],
                cell_padding_ratio=cold["cell_padding_ratio"])


def bench(n_per_class: int = 8, max_batch: int = 4, hidden: int = 64,
          classes=((220, 110), (430, 215)),
          out_json: str = "BENCH_serve.json"):
    rng = np.random.default_rng(0)
    stream = make_stream(rng, n_per_class, classes=classes)
    f_cell = stream[0].x_cell.shape[1]
    f_net = stream[0].x_net.shape[1]
    cfg = HeteroMPConfig(hidden=hidden, k_cell=16, k_net=16)
    params = init_drcircuitgnn(jax.random.PRNGKey(0), f_cell, f_net, hidden)

    seq = bench_sequential(params, cfg, stream)
    bat = bench_batched(params, cfg, stream, max_batch)
    onl = bench_online(params, cfg, stream, max_batch)
    deg = bench_degraded(params, cfg, stream, max_batch)
    sus = bench_sustained(params, cfg, stream, max_batch)
    sus_chaos = bench_sustained(
        params, cfg, stream, max_batch,
        chaos=FaultInjector([FaultRule("dispatch", rate=0.05),
                             FaultRule("straggler", rate=0.05,
                                       delay_s=0.01)], seed=7))

    speedup = bat["graphs_per_s"] / max(seq["graphs_per_s"], 1e-9)
    warm_speedup = (bat["warm_graphs_per_s"]
                    / max(seq["warm_graphs_per_s"], 1e-9))
    online_warm_speedup = (onl["warm_graphs_per_s"]
                           / max(seq["warm_graphs_per_s"], 1e-9))
    emit("serve/sequential", 1e6 / max(seq["graphs_per_s"], 1e-9),
         f"graphs_per_s={seq['graphs_per_s']:.2f};"
         f"compiles={seq['compiles']}")
    emit("serve/batched", 1e6 / max(bat["graphs_per_s"], 1e-9),
         f"graphs_per_s={bat['graphs_per_s']:.2f};"
         f"compiles={bat['compiles']};speedup={speedup:.2f}x;"
         f"warm_speedup={warm_speedup:.2f}x")
    emit("serve/online", 1e6 / max(onl["graphs_per_s"], 1e-9),
         f"graphs_per_s={onl['graphs_per_s']:.2f};"
         f"devices={onl['devices']};compiles={onl['compiles']};"
         f"warm_speedup={online_warm_speedup:.2f}x")
    emit("serve/degraded", 1e6 / max(deg["graphs_per_s"], 1e-9),
         f"graphs_per_s={deg['graphs_per_s']:.2f};"
         f"devices={deg['devices']};"
         f"quarantined_slot={deg['quarantined_slot']}")
    emit("serve/sustained", 1e3 * sus["p99_ms"],
         f"qps={sus['achieved_qps']:.1f}/{sus['target_qps']:.0f};"
         f"p50={sus['p50_ms']:.1f}ms;p99={sus['p99_ms']:.1f}ms;"
         f"deadline_flushes={sus['deadline_flushes']};shed={sus['shed']}")
    emit("serve/sustained_chaos", 1e3 * sus_chaos["p99_ms"],
         f"qps={sus_chaos['achieved_qps']:.1f};"
         f"p99={sus_chaos['p99_ms']:.1f}ms;"
         f"retries={sus_chaos['retries']};"
         f"failures={sus_chaos['failures']}")
    record = dict(ts=time.time(), kind="serve_circuit",
                  backend=jax.default_backend(),
                  n_graphs=len(stream), max_batch=max_batch, hidden=hidden,
                  classes=list(map(list, classes)),
                  sequential=seq, batched=bat, online=onl, degraded=deg,
                  sustained=sus, sustained_chaos=sus_chaos,
                  speedup=speedup, warm_speedup=warm_speedup,
                  online_warm_speedup=online_warm_speedup)
    append_json(out_json, record)
    return record


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--smoke" in sys.argv:
        # CI-sized run: tiny classes, small stream
        r = bench(n_per_class=4, max_batch=2, hidden=32,
                  classes=((80, 40), (150, 75)))
        # paced producers must actually enter the deadline-flush regime —
        # the gap ISSUE-6 closed ("deadline_flushes: 0" on burst streams)
        assert r["sustained"]["deadline_flushes"] > 0, r["sustained"]
    else:
        r = bench()
    print(f"[serve] batched vs sequential: {r['speedup']:.2f}x cold, "
          f"{r['warm_speedup']:.2f}x warm "
          f"({r['batched']['compiles']} vs {r['sequential']['compiles']} "
          f"compiles)")
    o = r["online"]
    print(f"[serve] online x{o['devices']} devices: "
          f"{o['graphs_per_s']:.2f} graphs/s cold, "
          f"{r['online_warm_speedup']:.2f}x sequential warm, "
          f"dispatches/device={o['dispatches_per_device']}, "
          f"{o['deadline_flushes']} deadline flushes")
    d = r["degraded"]
    print(f"[serve] degraded (slot {d['quarantined_slot']} of "
          f"{d['devices']} quarantined): {d['graphs_per_s']:.2f} graphs/s, "
          f"dispatches/device={d['dispatches_per_device']}, "
          f"{d['failures']} failures")
    s = r["sustained"]
    print(f"[serve] sustained @{s['target_qps']:.0f} qps "
          f"(achieved {s['achieved_qps']:.1f}): "
          f"p50={s['p50_ms']:.1f}ms p95={s['p95_ms']:.1f}ms "
          f"p99={s['p99_ms']:.1f}ms, "
          f"{s['deadline_flushes']} deadline flushes, {s['shed']} shed")
    sc = r["sustained_chaos"]
    print(f"[serve] sustained+chaos: p99={sc['p99_ms']:.1f}ms, "
          f"{sc['retries']} retries, {sc['failures']} failures")
