"""End-to-end DR-CircuitGNN trainer for congestion prediction.

Mirrors the paper's experimental protocol (Sec. 4.1): MSE regression on
per-cell congestion, rank-correlation metrics, per-design graph lists, and
the parallel (fused) vs sequential (DGL-analogue) execution toggle.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hetero_mp import HeteroMPConfig, plan_applicable
from repro.fault.inject import FaultInjector
from repro.fault.monitor import StepMonitor
from repro.graphs.circuit import CircuitGraph, relation_plan_of
from repro.graphs.collate import collate_graphs
from repro.kernels import ops
from repro.models.backbone import BackboneSpec
from repro.models.hgnn import (DRCircuitGNNParams, batched_loss_fn,
                               drcircuitgnn_forward, init_drcircuitgnn,
                               loss_fn)
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_RECORDER, Recorder
from repro.optim import adamw_init, adamw_update, constant
from repro.sharding.specs import DeviceRing
from repro.train import metrics as M


@dataclasses.dataclass
class CircuitTrainConfig:
    hidden: int = 64
    n_layers: int = 2
    k_cell: int = 16
    k_net: int = 16
    auto_k: bool = False              # profile per-graph optimal K (Sec. 4.3)
    lr: float = 2e-4                  # paper's optimal DR-CircuitGNN setup
    weight_decay: float = 1e-5
    epochs: int = 10
    backend: str = ops.DEFAULT_BACKEND   # fused path everywhere by default
    use_drelu: bool = True
    # Relation-fused layer dispatch (DESIGN.md §9): single-graph steps
    # attach each graph's RelationPlan (cached per graph, device-resident)
    # so the jitted step runs ONE dispatch per direction-group; collated
    # batches carry plans from the collator.  False pins the serial loop.
    use_plan: bool = True
    # Giant-graph sharded steps (DESIGN.md §12): > 1 partitions each
    # graph's plan over that many mesh devices and the jitted step runs the
    # message passing SPMD with one all-to-all halo exchange per direction
    # — each device holds only its arena slices.  Needs that many visible
    # devices; parity with the single-device plan path:
    # tests/test_sharded_parity.py.
    n_shards: int = 0
    # Dense-tier crossover override threaded to HeteroMPConfig (DESIGN.md
    # §14): None keeps the measured constant; <= -1 forces all-arena.
    dense_threshold: Optional[int] = None
    seed: int = 0
    # graphs per optimizer step: an epoch over a design list is
    # ceil(n/batch_size) collated dispatches instead of n (graphs/collate.py)
    batch_size: int = 1
    # Deep-backbone knobs (models/backbone.py, DESIGN.md §13).  ``n_layers``
    # above is the single depth source of truth end-to-end (it sizes the
    # params AND the spec).  ``remat=True`` checkpoints each hetero layer:
    # the backward recomputes the layer's fused forward instead of storing
    # its activations, so depth-15 trains at roughly depth-3 peak memory
    # (bench_backbone asserts it).  ``wiring`` selects the DeepGEN-style
    # reuse pattern: "plain" | "residual" | "dense".
    remat: bool = False
    wiring: str = "plain"


def _grads_finite(grads) -> jax.Array:
    """Scalar bool: every gradient leaf is NaN/Inf-free (traceable)."""
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(g))
                              for g in jax.tree.leaves(grads)]))


def _where_tree(ok, new, old):
    """``new`` where ``ok`` else ``old``, leafwise — a skipped step is a
    true no-op (params, moments, AND the opt step counter stay put)."""
    return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)


class CircuitTrainer:
    def __init__(self, cfg: CircuitTrainConfig, f_cell: int, f_net: int, *,
                 chaos: Optional[FaultInjector] = None,
                 monitor: Optional[StepMonitor] = None,
                 registry: Optional[MetricsRegistry] = None,
                 recorder: Optional[Recorder] = None):
        self.cfg = cfg
        self.mp_cfg = HeteroMPConfig(hidden=cfg.hidden, k_cell=cfg.k_cell,
                                     k_net=cfg.k_net, backend=cfg.backend,
                                     use_drelu=cfg.use_drelu,
                                     use_plan=cfg.use_plan,
                                     n_shards=cfg.n_shards,
                                     dense_threshold=cfg.dense_threshold)
        # the backbone spec shares cfg.n_layers with init_drcircuitgnn —
        # one depth knob end-to-end (trainer, examples, benches)
        self.spec = BackboneSpec(depth=cfg.n_layers, hidden=cfg.hidden,
                                 wiring=cfg.wiring, remat=cfg.remat)
        key = jax.random.PRNGKey(cfg.seed)
        self.params = init_drcircuitgnn(key, f_cell, f_net, cfg.hidden,
                                        cfg.n_layers)
        self.opt_state = adamw_init(self.params)
        self.lr = constant(cfg.lr)
        self._step_fn = self._build_step()
        self._batched_step_fn = self._build_batched_step()
        self._grad_fn = self._build_grad()
        self._apply_fn = self._build_apply()
        self._fwd_fn, self._batched_fwd_fn = self._build_fwd_losses()
        self._batch_cache = {}        # id-tuple of member graphs -> device batch
        self._plan_cache = {}         # id(graph) -> plan-attached graph
        # Robustness (DESIGN.md §10): the chaos harness (fault/inject.py)
        # can stall steps; the StepMonitor flags the resulting stragglers
        # (slack -> rebalance -> restart escalation); non-finite-grad steps
        # are skipped in-jit (update frozen leafwise) and counted here.
        self.chaos = chaos
        self.monitor = monitor if monitor is not None \
            else StepMonitor(n_hosts=1)
        # Observability (DESIGN.md §11): per-trainer registry; counters
        # replace the ad-hoc ints but keep attribute-read back-compat via
        # the ``nonfinite_grad_steps`` property below.
        self.metrics = registry if registry is not None else MetricsRegistry()
        self._rec = recorder if recorder is not None else NULL_RECORDER
        if self.chaos is not None and self._rec.enabled:
            self.chaos.recorder = self._rec
        self._c_steps = self.metrics.counter("train.steps")
        self._c_nonfinite = self.metrics.counter("train.nonfinite_grad_steps")
        self._h_step_ms = self.metrics.histogram("train.step_ms")
        # Deep-backbone memory accounting (§11 gauges, backend-guarded —
        # see _peak_memory_bytes / _recompute_ms): peak device bytes after
        # each step, and the per-step recompute-cost estimate remat pays.
        self._g_peak = self.metrics.gauge("train.peak_memory_bytes")
        self._g_recompute = self.metrics.gauge("train.recompute_ms")
        self._fwd_time_cache = {}     # id(step input) -> (pin, est_ms)
        self._global_step = 0

    @property
    def nonfinite_grad_steps(self) -> int:
        """Skipped-step count (back-compat attribute over the registry)."""
        return int(self._c_nonfinite.value)

    def stats(self) -> Dict[str, float]:
        """Registry-backed trainer counters + step-time percentiles."""
        p50, p95, p99 = self._h_step_ms.percentiles((0.50, 0.95, 0.99))
        return {
            "steps": int(self._c_steps.value),
            "nonfinite_grad_steps": int(self._c_nonfinite.value),
            "step_p50_ms": p50, "step_p95_ms": p95, "step_p99_ms": p99,
            "peak_memory_bytes": int(self._g_peak.value),
            "recompute_ms": float(self._g_recompute.value),
        }

    def _peak_memory_bytes(self) -> int:
        """Peak device memory: an accelerator's ``peak_bytes_in_use`` from
        ``device.memory_stats()``, and an accelerator that does not report
        it raises.  Only the CPU backend, which has no memory stats, falls
        back to a live-buffer estimate (Σ nbytes over ``jax.live_arrays()``).
        The deterministic compiled-peak measure (``memory_analysis()``)
        lives in benchmarks/bench_backbone.py."""
        dev = jax.devices()[0]
        if dev.platform == "cpu":
            return int(sum(x.nbytes for x in jax.live_arrays()))
        ms = dev.memory_stats()
        if not ms or "peak_bytes_in_use" not in ms:
            raise RuntimeError(f"{dev.device_kind} reports no "
                               f"peak_bytes_in_use in memory_stats()")
        return int(ms["peak_bytes_in_use"])

    def _recompute_ms(self, fwd_fn, args) -> float:
        """Per-step recompute-cost estimate under remat: the backward
        re-runs each checkpointed layer's forward exactly once, so the
        extra work per step ≈ one forward pass — measured on the jitted
        forward loss once per step input (id-cached, pinned like
        _plan_cache) and emitted as the ``train.recompute_ms`` gauge.
        0.0 with remat off."""
        if not self.cfg.remat:
            return 0.0
        key = id(args[0])
        hit = self._fwd_time_cache.get(key)
        if hit is not None and hit[0] is args[0]:
            return hit[1]
        fwd_fn(self.params, *args).block_until_ready()   # compile warm-up
        t0 = time.perf_counter()
        fwd_fn(self.params, *args).block_until_ready()
        est = (time.perf_counter() - t0) * 1e3
        self._fwd_time_cache[key] = (args[0], est)
        return est

    def _tick(self, duration_s: float, recompute_ms: float = 0.0) -> None:
        """Feed one step's wall-clock to the StepMonitor (host 0 — the
        single-process trainer; multi-host callers own their monitor) and
        refresh the §11 memory/recompute gauges."""
        self.monitor.record(self._global_step, 0, duration_s)
        self._global_step += 1
        self._c_steps.inc()
        self._h_step_ms.observe(duration_s * 1e3)
        self._g_peak.set(self._peak_memory_bytes())
        self._g_recompute.set(recompute_ms)

    def _build_step(self):
        mp_cfg, lr, wd = self.mp_cfg, self.lr, self.cfg.weight_decay
        spec = self.spec

        @jax.jit
        def step(params, opt_state, graph: CircuitGraph):
            loss, grads = jax.value_and_grad(loss_fn)(params, graph, mp_cfg,
                                                      spec)
            ok = _grads_finite(grads)
            new_p, new_o = adamw_update(params, grads, opt_state,
                                        lr(opt_state.step),
                                        weight_decay=wd)
            return (_where_tree(ok, new_p, params),
                    _where_tree(ok, new_o, opt_state), loss, ok)

        return step

    def _build_batched_step(self):
        mp_cfg, lr, wd = self.mp_cfg, self.lr, self.cfg.weight_decay
        spec = self.spec

        @jax.jit
        def step(params, opt_state, graph: CircuitGraph, cell_w):
            loss, grads = jax.value_and_grad(batched_loss_fn)(
                params, graph, cell_w, mp_cfg, spec)
            ok = _grads_finite(grads)
            new_p, new_o = adamw_update(params, grads, opt_state,
                                        lr(opt_state.step),
                                        weight_decay=wd)
            return (_where_tree(ok, new_p, params),
                    _where_tree(ok, new_o, opt_state), loss, ok)

        return step

    def _build_grad(self):
        """Loss+grad over one collated shard — the per-device half of a
        data-parallel step.  Placement follows the committed arguments, so
        dispatching shard d with replica-d params runs on device d."""
        mp_cfg, spec = self.mp_cfg, self.spec

        @jax.jit
        def gfn(params, graph: CircuitGraph, cell_w):
            return jax.value_and_grad(batched_loss_fn)(params, graph,
                                                       cell_w, mp_cfg, spec)

        return gfn

    def _build_fwd_losses(self):
        """Jitted forward-only losses — the measurement probes behind the
        ``train.recompute_ms`` gauge (one forward ≈ the extra work a remat
        backward pays per step)."""
        mp_cfg, spec = self.mp_cfg, self.spec
        f = jax.jit(lambda p, g: loss_fn(p, g, mp_cfg, spec))
        fb = jax.jit(lambda p, g, w: batched_loss_fn(p, g, w, mp_cfg, spec))
        return f, fb

    def _build_apply(self):
        lr, wd = self.lr, self.cfg.weight_decay

        @jax.jit
        def apply(params, opt_state, grads):
            return adamw_update(params, grads, opt_state,
                                lr(opt_state.step), weight_decay=wd)

        return apply

    def _dp_step(self, graphs: List[CircuitGraph], ring: DeviceRing):
        """One data-parallel optimizer step over ``graphs``: members are
        sharded round-robin onto the ring devices, per-shard grads (each a
        mean over its members) dispatch concurrently — independent collated
        batches are embarrassingly parallel, the same property the serve
        engine routes on — then combine as a member-count-weighted mean into
        ONE adamw update.  The gradient equals the single-device batched
        step over the same members (weights 1/(n_shard·n_cell_i) scaled by
        n_shard/n_total compose to 1/(n_total·n_cell_i))."""
        n_dev = min(len(ring), len(graphs))
        shards = [graphs[d::n_dev] for d in range(n_dev)]
        outs, weights = [], []
        for d, shard in enumerate(shards):
            graph, cell_w, n_real = self._collate(shard,
                                                  device=ring.devices[d])
            p_d = jax.device_put(self.params, ring.devices[d])
            outs.append(self._grad_fn(p_d, graph, cell_w))   # async, dev d
            weights.append(n_real)
        total = sum(weights)
        dev0 = ring.devices[0]
        losses = [jax.device_get(loss) for loss, _ in outs]
        grads = jax.tree.map(
            lambda *gs: sum((w / total) * jax.device_put(g, dev0)
                            for w, g in zip(weights, gs)),
            *[g for _, g in outs])
        if not all(np.isfinite(np.asarray(g)).all()
                   for g in jax.tree.leaves(grads)):
            # poisoned shard: skip the whole combined update (the same
            # no-op the jitted steps apply in-trace)
            return float(np.average(losses, weights=weights)), total, False
        self.params, self.opt_state = self._apply_fn(
            jax.device_put(self.params, dev0), self.opt_state, grads)
        return float(np.average(losses, weights=weights)), total, True

    def _planned(self, g: CircuitGraph) -> CircuitGraph:
        """``g`` with its RelationPlan attached and device-resident, cached
        per graph — the jitted step takes the graph as a traced argument,
        so the plan must ride along as pytree leaves (host packing is
        impossible inside the trace); caching the ``device_put`` avoids
        re-uploading the plan's host arrays every step.  The jit cache is
        keyed by shapes, so equal-shaped graphs still share one executable.
        """
        if not plan_applicable(self.mp_cfg, self.cfg.hidden):
            return g
        key = id(g)
        hit = self._plan_cache.get(key)
        if hit is not None and hit[0] is g:
            return hit[1]
        if self.cfg.n_shards > 1:
            # giant-graph step: the partitioned plan's stacked tables are
            # device_put PRE-SHARDED over the ("shard",) mesh, so each
            # device ever holds only its arena slices and the jitted step's
            # shard_map consumes them without resharding
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.graphs.circuit import sharded_plan_of
            from repro.sharding.specs import shard_mesh
            sp = sharded_plan_of(g, self.cfg.n_shards)
            mesh = shard_mesh(self.cfg.n_shards)
            pg = dataclasses.replace(g, plan=jax.device_put(
                sp, NamedSharding(mesh, P("shard"))))
        else:
            pg = dataclasses.replace(
                g, plan=jax.device_put(relation_plan_of(g)))
        self._plan_cache[key] = (g, pg)
        return pg

    def _collate(self, graphs: List[CircuitGraph], device=None):
        """Collate (and device-put) a batch once; reuse across epochs.  The
        quantized fused arenas mean batches of one shape bucket also share
        the jitted step's compiled executable.

        The cache key is the member id-tuple; the entry pins the member
        graphs (so their ids cannot be reused while it lives) and the hit
        path re-checks identity — the same guard _FUSE_CACHE uses."""
        key = (tuple(id(g) for g in graphs), getattr(device, "id", None))
        hit = self._batch_cache.get(key)
        if hit is not None and all(a is b for a, b in zip(hit[0], graphs)):
            return hit[1]
        batch = collate_graphs(graphs)
        entry = (jax.device_put(batch.graph, device),
                 jax.device_put(batch.cell_weight, device), batch.n_real)
        self._batch_cache[key] = (tuple(graphs), entry)
        return entry

    def train_epoch(self, graphs: List[CircuitGraph],
                    batch_size: int = None, devices=None) -> float:
        """One epoch.  ``batch_size > 1`` collates consecutive graphs
        block-diagonally so the epoch is ceil(n/B) dispatches instead of n
        (one optimizer step per *batch*, gradient = mean of member
        losses).

        ``devices`` opts into data-parallel steps: each batch's members are
        sharded over a :class:`DeviceRing` (a device sequence, or ``True``
        for the mesh/local default) and the per-shard grads averaged into
        one update — the serve engine's multi-device dispatch reused for
        training (same math as the single-device batched step)."""
        b = self.cfg.batch_size if batch_size is None else batch_size
        if b <= 1:
            losses = []
            for g in graphs:
                if self.chaos is not None:
                    self.chaos.stall("straggler")
                pg = self._planned(g)
                t_step = time.perf_counter()
                self.params, self.opt_state, loss, ok = self._step_fn(
                    self.params, self.opt_state, pg)
                ok = bool(ok)                  # device barrier ends the step
                self._tick(time.perf_counter() - t_step,
                           self._recompute_ms(self._fwd_fn, (pg,)))
                if not ok:
                    self._c_nonfinite.inc()
                    if self._rec.enabled:
                        self._rec.instant("train", "nonfinite_grads_skip",
                                          step=self._global_step)
                    continue                   # skipped: a true no-op step
                losses.append(float(loss))
            return float(np.mean(losses)) if losses else float("nan")
        ring = None
        if devices is not None:
            ring = DeviceRing(None if devices is True else devices)
        losses, weights = [], []
        for i in range(0, len(graphs), b):
            chunk = graphs[i:i + b]
            if self.chaos is not None:
                self.chaos.stall("straggler")
            t_step = time.perf_counter()
            recompute = 0.0
            if ring is not None and len(chunk) > 1:
                loss, n_real, ok = self._dp_step(chunk, ring)
            else:
                graph, cell_w, n_real = self._collate(chunk)
                self.params, self.opt_state, loss, ok = \
                    self._batched_step_fn(self.params, self.opt_state,
                                          graph, cell_w)
                ok = bool(ok)
                recompute = self._recompute_ms(self._batched_fwd_fn,
                                               (graph, cell_w))
            self._tick(time.perf_counter() - t_step, recompute)
            if not ok:
                self._c_nonfinite.inc()
                if self._rec.enabled:
                    self._rec.instant("train", "nonfinite_grads_skip",
                                      step=self._global_step)
                continue
            losses.append(float(loss))
            weights.append(n_real)
        return float(np.average(losses, weights=weights)) if losses \
            else float("nan")

    def profile_k(self, graphs: List[CircuitGraph]) -> Dict[str, int]:
        """The paper's preprocessing profiler (Sec. 4.3): pick the
        cost-model-optimal K per node type from the graphs' degree
        statistics, then rebuild the step function with those K's."""
        import numpy as np
        from repro.core.drelu import profile_optimal_k

        deg_by_src = {"cell": [], "net": []}
        for g in graphs:
            for et, es in g.edges.items():
                src_t = {"near": "cell", "pin": "cell", "pinned": "net"}[et]
                w = np.asarray(es.adj.to_dense())
                deg_by_src[src_t].append((w != 0).sum(1))
        ks = {}
        for t, degs in deg_by_src.items():
            deg = np.concatenate([d[d > 0] for d in degs])
            ks[t] = min(profile_optimal_k(deg, self.cfg.hidden),
                        self.cfg.hidden)
        self.mp_cfg = dataclasses.replace(self.mp_cfg, k_cell=ks["cell"],
                                          k_net=ks["net"])
        self._step_fn = self._build_step()
        self._batched_step_fn = self._build_batched_step()
        self._grad_fn = self._build_grad()
        self._fwd_fn, self._batched_fwd_fn = self._build_fwd_losses()
        self._fwd_time_cache.clear()
        return ks

    def fit(self, train_graphs: List[CircuitGraph],
            eval_graphs: Optional[List[CircuitGraph]] = None,
            log_every: int = 1) -> Dict:
        if self.cfg.auto_k:
            ks = self.profile_k(train_graphs)
            print(f"[profile] optimal K per node type: {ks}")
        history = []
        t0 = time.perf_counter()
        for ep in range(self.cfg.epochs):
            loss = self.train_epoch(train_graphs)
            rec = {"epoch": ep, "loss": loss,
                   "wall_s": time.perf_counter() - t0}
            if eval_graphs is not None and (ep + 1) % log_every == 0:
                rec.update(self.evaluate(eval_graphs))
            history.append(rec)
        return {"history": history, "final": history[-1]}

    def evaluate(self, graphs: List[CircuitGraph]) -> Dict[str, float]:
        preds, labels = [], []
        for g in graphs:
            p = drcircuitgnn_forward(self.params, g, self.mp_cfg,
                                     self.spec)
            preds.append(np.asarray(p))
            labels.append(np.asarray(g.y_cell))
        return M.all_metrics(np.concatenate(preds), np.concatenate(labels))
