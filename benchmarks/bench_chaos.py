"""Chaos-injection serving benchmark: the self-healing ladder under a
fixed-seed fault schedule (DESIGN.md §10, ISSUE-6 acceptance).

One mixed-size stream is served twice over two logical ring slots:

* **clean** — chaos off: the fault-free reference predictions AND the
  throughput baseline for the overhead row;
* **chaos** — a seeded :class:`~repro.fault.inject.FaultInjector` mixing a
  transient dispatch failure, a transient NaN-poisoned output, a
  straggler stall, and a simulated device loss (down long enough to trip
  quarantine, short enough that a probe re-admits the slot).

The run then **asserts** the containment contract rather than just timing
it: every request completes with predictions bit-identical to the clean
pass (``np.array_equal`` — member independence makes this exact), zero
failures, retries > 0, the lost slot quarantined AND re-admitted, and the
NaN poisoning caught by the output guard.  A third phase overloads a
bounded queue under ``admission="shed_oldest"`` and checks the shed
counter exactly.

Reported: healing throughput vs clean throughput (the chaos tax), plus
all ladder counters.  Appended to ``BENCH_serve.json`` (kind
``serve_chaos``) so the robustness trajectory is recorded across PRs.
"""

from __future__ import annotations

import sys
import threading
import time

import jax
import numpy as np

from benchmarks.bench_serve_circuit import make_stream
from benchmarks.common import append_json, emit
from repro.core.hetero_mp import HeteroMPConfig
from repro.fault import FaultInjector, FaultRule
from repro.models.hgnn import init_drcircuitgnn
from repro.serve import CircuitServeEngine


def _serve_stream(eng, stream):
    """Serve ``stream`` through serve_forever(); returns preds by index."""
    server = threading.Thread(target=eng.serve_forever)
    server.start()
    rids = [eng.submit(g) for g in stream]
    preds = [eng.result(r, timeout=600.0).pred for r in rids]
    return server, preds


def _chaos_schedule(seed: int):
    """The fixed schedule: one transient dispatch fault, one poisoned
    output, one straggler stall, one device loss on slot 1."""
    return FaultInjector([
        FaultRule("dispatch", at=(1,)),
        FaultRule("nan_output", at=(0,)),
        FaultRule("straggler", at=(3,), delay_s=0.02),
        FaultRule("device_loss", at=(0,), device=1, down_for=3),
    ], seed=seed)


def bench_chaos(n_per_class: int = 8, max_batch: int = 4, hidden: int = 64,
                classes=((220, 110), (430, 215)), seed: int = 0,
                out_json: str = "BENCH_serve.json"):
    rng = np.random.default_rng(0)
    stream = make_stream(rng, n_per_class, classes=classes)
    f_cell = stream[0].x_cell.shape[1]
    f_net = stream[0].x_net.shape[1]
    cfg = HeteroMPConfig(hidden=hidden, k_cell=16, k_net=16)
    params = init_drcircuitgnn(jax.random.PRNGKey(0), f_cell, f_net, hidden)
    devs = list(jax.local_devices())
    if len(devs) < 2:
        # two logical slots on one device still exercise quarantine routing
        devs = [devs[0], devs[0]]
    devs = devs[:2]
    ladder = dict(max_retries=3, retry_backoff_s=0.01, watchdog_s=120.0,
                  quarantine_after=2, probe_interval_s=0.2)

    # ---- clean pass: fault-free reference + throughput baseline
    eng = CircuitServeEngine(params, cfg, max_batch=max_batch,
                             max_wait_ms=25.0, devices=devs, **ladder)
    server, ref = _serve_stream(eng, stream)
    eng.stop()
    server.join()
    clean = eng.stats()

    # ---- chaos pass: same stream under the seeded schedule
    chaos = _chaos_schedule(seed)
    eng = CircuitServeEngine(params, cfg, max_batch=max_batch,
                             max_wait_ms=25.0, devices=devs, chaos=chaos,
                             **ladder)
    server, preds = _serve_stream(eng, stream)
    # keep a trickle flowing until the lost slot is probed back in
    deadline = time.time() + 300.0
    extra = 0
    while eng.ring.health()["readmissions"] < 1 and time.time() < deadline:
        assert eng.result(eng.submit(stream[0]),
                          timeout=600.0).pred is not None
        extra += 1
        time.sleep(0.02)
    eng.stop()
    server.join()
    st = eng.stats()

    # ---- the containment contract, asserted
    parity = all(np.array_equal(p, r) for p, r in zip(preds, ref))
    assert parity, "healed predictions diverged from the fault-free run"
    assert st["failures"] == 0, st
    assert st["retries"] >= 1, st
    assert st["nonfinite_outputs"] >= 1, st          # poison was caught
    assert st["quarantines"] >= 1 and st["probes"] >= 1, st
    assert st["readmissions"] >= 1, st
    assert st["device_health"] == ["up", "up"], st
    counts = chaos.counts()
    assert counts.get("dispatch") == 1 and counts.get("nan_output") == 1
    assert counts.get("device_loss", 0) >= 1

    # ---- admission overload: bounded queue sheds the FIFO head, exactly
    cap = 4
    burst = stream[:10]
    eng2 = CircuitServeEngine(params, cfg, max_batch=max_batch,
                              max_wait_ms=25.0, devices=devs[:1],
                              max_queue=cap, admission="shed_oldest")
    rids2 = [eng2.submit(g) for g in burst]
    eng2.run()
    shed = eng2.stats()
    assert shed["admission_shed"] == len(burst) - cap, shed
    served = sum(1 for r in rids2
                 if eng2.finished[r].error is None)
    assert served == cap, shed

    chaos_gps = st["requests"] / max(st["wall_s"], 1e-9)
    clean_gps = clean["requests"] / max(clean["wall_s"], 1e-9)
    emit("serve/chaos", 1e6 / max(chaos_gps, 1e-9),
         f"graphs_per_s={chaos_gps:.2f};clean={clean_gps:.2f};"
         f"retries={st['retries']};quarantines={st['quarantines']};"
         f"readmissions={st['readmissions']};parity=ok")
    record = dict(ts=time.time(), kind="serve_chaos", seed=seed,
                  backend=jax.default_backend(),
                  n_graphs=len(stream), extra_probe_requests=extra,
                  max_batch=max_batch, hidden=hidden,
                  classes=list(map(list, classes)),
                  clean_graphs_per_s=clean_gps,
                  chaos_graphs_per_s=chaos_gps,
                  healing_tax=1.0 - chaos_gps / max(clean_gps, 1e-9),
                  healthy_parity=parity,
                  retries=st["retries"], bisects=st["bisects"],
                  failures=st["failures"],
                  nonfinite_outputs=st["nonfinite_outputs"],
                  watchdog_timeouts=st["watchdog_timeouts"],
                  quarantines=st["quarantines"], probes=st["probes"],
                  readmissions=st["readmissions"],
                  admission_shed=shed["admission_shed"],
                  fault_counts=counts)
    append_json(out_json, record)
    return record


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--smoke" in sys.argv:
        # CI-sized run: tiny classes, small stream
        r = bench_chaos(n_per_class=4, max_batch=2, hidden=32,
                        classes=((80, 40), (150, 75)))
    else:
        r = bench_chaos()
    print(f"[chaos] healed stream: {r['chaos_graphs_per_s']:.2f} graphs/s "
          f"vs {r['clean_graphs_per_s']:.2f} clean "
          f"({100 * r['healing_tax']:.1f}% healing tax), parity=ok, "
          f"retries={r['retries']}, quarantine->probe->readmit="
          f"{r['quarantines']}/{r['probes']}/{r['readmissions']}, "
          f"shed={r['admission_shed']}")
