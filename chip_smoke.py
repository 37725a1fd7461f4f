"""Smoke test: the DR-CircuitGNN training step on a TPU, end to end.

    python chip_smoke.py                # one chip: train + evaluate + parity
    python chip_smoke.py --four-chips   # the sharded giant-graph path only

One process drives the chip through the user-facing entry points:
full-scale ``large`` partitions (Table 1 at ``scale=1.0``, seeded), a
``CircuitTrainer`` at its defaults (hidden 64, k 16, relation plans, the
platform's default backend — ``pallas_fused`` on TPU), a few optimizer steps
on one partition and an ``evaluate`` on another.  It checks that

* the device is a TPU (no fallback to the CPU),
* the compiled step calls Mosaic kernels (``tpu_custom_call``) and no kernel
  on the TPU branch of the step is interpreted,
* the losses are finite and fall,
* only the fused Pallas executors were dispatched (no per-bucket or XLA
  downgrade),
* a forward agrees with the dense oracle backend within ``PARITY_TOL``.

``--four-chips`` instead trains ``n_shards=4`` (DESIGN.md §12) on the four
devices of one host and compares losses and a forward with the
single-device plan path on the same graph.

Timings printed here are smoke timings of a single run, not a benchmark.
The last line of standard output is one JSON object; any failed phase exits
non-zero before printing it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# predictions are sigmoid outputs in [0, 1]; Pallas vs dense oracle, both
# at f32 ("highest") matmul precision
PARITY_TOL = 1e-3
# sharded vs single-device plan path: same math, different summation order
SHARD_TOL = 1e-4
N_STEPS = 5
N_STEPS_SHARDED = 3
SEED = 0
# the dispatch kinds of the relation-fused plan path (DESIGN.md §9, §12, §14)
PLAN_KINDS = {"multi_fwd", "multi_bwd", "multi_dense_fwd", "multi_dense_bwd"}
SHARD_KINDS = {"shard_fwd", "shard_bwd"}


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def kernel_modes(jaxpr, platform: str = "tpu"):
    """(native, interpreted) ``pallas_call`` counts on the branch a program
    lowered for ``platform`` runs (``kernels.drspmm.run_pallas`` stages both
    and the lowering keeps one)."""
    import jax

    native = interp = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            if eqn.params["interpret"]:
                interp += 1
            else:
                native += 1
            continue
        subs = jax.core.jaxprs_in_params(eqn.params)
        plats = eqn.params.get("branches_platforms")
        if eqn.primitive.name == "cond" and plats:
            pick = next(i for i, ps in enumerate(plats)
                        if ps is None or platform in ps)
            subs = [eqn.params["branches"][pick].jaxpr]
        for sub in subs:
            n, i = kernel_modes(sub, platform)
            native += n
            interp += i
    return native, interp


def dispatch_counts():
    from repro.obs.metrics import DEFAULT_REGISTRY
    return {f"{dict(lab)['family']}:{dict(lab)['kind']}": int(m.value)
            for lab, m in DEFAULT_REGISTRY.series("ops.dispatch").items()}


def graph_sizes(g) -> dict:
    return dict(n_cell=g.n_cell, n_net=g.n_net,
                nnz={et: int(es.adj.nnz) for et, es in g.edges.items()})


def compile_step(trainer, pg):
    """AOT-compile the trainer's jitted step for ``pg``; check it calls
    Mosaic kernels and interprets none.  Returns the compile seconds."""
    import jax

    native, interp = kernel_modes(jax.make_jaxpr(trainer._step_fn)(
        trainer.params, trainer.opt_state, pg).jaxpr)
    t0 = time.perf_counter()
    compiled = trainer._step_fn.lower(trainer.params, trainer.opt_state,
                                      pg).compile()
    compile_s = time.perf_counter() - t0
    n_custom = compiled.as_text().count("tpu_custom_call")
    log(f"step kernels: native={native} interpreted={interp} "
        f"tpu_custom_call={n_custom}")
    check(interp == 0, f"{interp} kernel(s) interpreted on the TPU branch")
    check(native > 0 and n_custom > 0, "compiled step has no Mosaic kernel")
    return compile_s


def train_steps(trainer, g, n: int, label: str):
    """n optimizer steps on one graph; returns per-step losses."""
    import jax

    losses = []
    for s in range(n):
        t0 = time.perf_counter()
        loss = trainer.train_epoch([g])
        jax.block_until_ready(trainer.params)
        ms = (time.perf_counter() - t0) * 1e3
        losses.append(loss)
        log(f"{label} step {s}: loss={loss!r} wall_ms={ms:.1f} "
            f"(smoke timing, not a benchmark)")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    check(trainer.nonfinite_grad_steps == 0, "a step had non-finite grads")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return losses


def peak_bytes(dev) -> int:
    stats = dev.memory_stats()
    check(bool(stats) and "peak_bytes_in_use" in stats,
          f"{dev} reports no peak_bytes_in_use")
    return int(stats["peak_bytes_in_use"])


def max_abs_diff(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


def one_chip(graphs) -> None:
    import jax
    from repro.models.hgnn import drcircuitgnn_forward
    from repro.train.circuit_trainer import CircuitTrainConfig, CircuitTrainer

    g, g_eval = graphs[0], graphs[1]
    cfg = CircuitTrainConfig(seed=SEED)
    check(cfg.backend == "pallas_fused",
          f"default backend on TPU is {cfg.backend!r}, not pallas_fused")
    trainer = CircuitTrainer(cfg, g.x_cell.shape[1], g.x_net.shape[1])
    log(f"config: hidden={cfg.hidden} k_cell={cfg.k_cell} k_net={cfg.k_net} "
        f"layers={cfg.n_layers} use_plan={cfg.use_plan} "
        f"backend={cfg.backend}")

    before = dispatch_counts()
    compile_s = compile_step(trainer, trainer._planned(g))
    log(f"step compile_s={compile_s:.2f} (smoke timing, not a benchmark)")
    train_steps(trainer, g, N_STEPS, "train")
    metrics = trainer.evaluate([g_eval])
    log(f"evaluate (held-out partition): {json.dumps(metrics)}")
    check(all(math.isfinite(v) for v in metrics.values()),
          f"non-finite eval metrics {metrics}")
    after = dispatch_counts()
    delta = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    log(f"ops.dispatch (train + evaluate): {json.dumps(delta)}")
    check(bool(delta), "no dispatch recorded")
    bad = [k for k in delta
           if not k.startswith("pallas:") or k.split(":")[1] not in PLAN_KINDS]
    check(not bad, f"dispatches off the fused Pallas plan path: {bad}")
    log(f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}")

    dense_cfg = dataclasses.replace(trainer.mp_cfg, backend="dense")
    with jax.default_matmul_precision("highest"):
        y = jax.jit(lambda p: drcircuitgnn_forward(
            p, g, trainer.mp_cfg, trainer.spec))(trainer.params)
        y_ref = jax.jit(lambda p: drcircuitgnn_forward(
            p, g, dense_cfg, trainer.spec))(trainer.params)
    err = max_abs_diff(y, y_ref)
    log(f"forward parity vs dense backend: max_abs_diff={err!r} "
        f"tol={PARITY_TOL}")
    check(err <= PARITY_TOL, "forward disagrees with the dense oracle")


def four_chips(graphs) -> None:
    import jax
    import numpy as np
    from repro.models.hgnn import drcircuitgnn_forward
    from repro.sharding.specs import shard_mesh
    from repro.train.circuit_trainer import CircuitTrainConfig, CircuitTrainer

    n = 4
    check(len(jax.devices()) >= n, f"{len(jax.devices())} devices, need {n}")
    mesh = shard_mesh(n)
    devs = list(mesh.devices.flat)
    log(f"mesh {dict(mesh.shape)}: devices={[d.id for d in devs]} "
        f"platforms={sorted({d.platform for d in devs})}")
    check(len({d.id for d in devs}) == n
          and all(d.platform == "tpu" for d in devs),
          "shard mesh does not span four distinct TPU devices")

    g = graphs[0]
    f_cell, f_net = g.x_cell.shape[1], g.x_net.shape[1]
    sharded = CircuitTrainer(CircuitTrainConfig(seed=SEED, n_shards=n),
                             f_cell, f_net)
    single = CircuitTrainer(CircuitTrainConfig(seed=SEED), f_cell, f_net)
    pg = sharded._planned(g)
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree.leaves(pg.plan) if hasattr(leaf, "sharding")}
    log(f"sharded plan leaves span {sorted(spans)} devices")
    check(spans == {n}, "sharded plan is not spread over the mesh")

    # same params for both executors: forward parity
    with jax.default_matmul_precision("highest"):
        y_sh = jax.jit(lambda p: drcircuitgnn_forward(
            p, g, sharded.mp_cfg, sharded.spec))(single.params)
        y_1 = jax.jit(lambda p: drcircuitgnn_forward(
            p, g, single.mp_cfg, single.spec))(single.params)
    err = max_abs_diff(y_sh, y_1)
    log(f"forward parity sharded vs single: max_abs_diff={err!r} "
        f"tol={SHARD_TOL}")
    check(err <= SHARD_TOL, "sharded forward disagrees with single-device")

    before = dispatch_counts()
    compile_s = compile_step(sharded, pg)
    log(f"sharded step compile_s={compile_s:.2f} "
        f"(smoke timing, not a benchmark)")
    l_sh = train_steps(sharded, g, N_STEPS_SHARDED, "sharded")
    delta = {k: v - before.get(k, 0) for k, v in dispatch_counts().items()
             if v != before.get(k, 0)}
    log(f"ops.dispatch (sharded train): {json.dumps(delta)}")
    bad = [k for k in delta
           if not k.startswith("pallas:") or k.split(":")[1] not in SHARD_KINDS]
    check(bool(delta) and not bad, f"dispatches off the sharded path: {delta}")
    l_1 = train_steps(single, g, N_STEPS_SHARDED, "single")
    dl = float(np.max(np.abs(np.asarray(l_sh) - np.asarray(l_1))))
    log(f"loss parity sharded vs single: max_abs_diff={dl!r} tol={SHARD_TOL}")
    check(dl <= SHARD_TOL, "sharded losses disagree with single-device")
    log(f"peak_bytes_in_use per device="
        f"{[peak_bytes(d) for d in devs]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the n_shards=4 path on four devices")
    args = ap.parse_args()

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"JAX found no TPU (platform "
          f"{dev.platform!r}); this smoke test does not run on the CPU")
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()))
    log(f"device: {json.dumps(device)}")
    log(f"compile cache: {enable_compile_cache()}")

    from repro.graphs.generator import generate_design
    t0 = time.perf_counter()
    graphs = generate_design(SEED, "large", scale=1.0)
    log(f"generated {len(graphs)} 'large' partitions at scale=1.0 in "
        f"{time.perf_counter() - t0:.1f}s: "
        f"{json.dumps([graph_sizes(g) for g in graphs])}")

    (four_chips if args.four_chips else one_chip)(graphs)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
