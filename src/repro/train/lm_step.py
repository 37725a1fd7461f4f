"""Distributed train / serve steps for the LM substrate.

``make_train_step`` returns a jit-able (state, batch) -> (state, metrics)
closure with:
  * microbatch gradient accumulation (``grad_accum``) via lax.scan — the
    grads of microbatch i+1 overlap XLA's reduce-scatter of i (latency
    hiding), and the optimizer's cross-replica sync happens once per step;
  * optional int8-compressed cross-pod gradient all-reduce
    (optim/compression.py) for the slow inter-pod links;
  * AdamW + schedule (WSD for minicpm).

``make_serve_steps`` returns (prefill_fn, decode_fn).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.lm import serve
from repro.models.lm.model import LM
from repro.optim import AdamWState, adamw_init, adamw_update, cosine, wsd
from repro.sharding.specs import get_mesh


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState


def make_schedule(cfg: ArchConfig, lr: float, total_steps: int):
    if cfg.lr_schedule == "wsd":
        return wsd(lr, total_steps)
    return cosine(lr, total_steps, warmup=max(total_steps // 100, 1))


def make_train_step(lm: LM, *, lr: float = 3e-4, total_steps: int = 10_000,
                    weight_decay: float = 0.1, grad_clip: float = 1.0,
                    grad_accum: int = 1,
                    compress_pod_grads: bool = False) -> Callable:
    sched = make_schedule(lm.cfg, lr, total_steps)

    def loss_fn(params, batch):
        return lm.loss(params, batch)

    def value_and_grads(params, batch):
        if grad_accum > 1:
            # batch leading dim = grad_accum microbatches
            def micro(acc, mb):
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return jax.tree.map(jnp.add, acc, (l, g)), None

            zeros = (jnp.zeros(()),
                     jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                  params))
            (loss, grads), _ = jax.lax.scan(micro, zeros, batch)
            return loss / grad_accum, jax.tree.map(
                lambda g: g / grad_accum, grads)
        return jax.value_and_grad(loss_fn)(params, batch)

    def train_step(state: TrainState, batch: Dict) -> Tuple[TrainState, Dict]:
        mesh = get_mesh()
        if (compress_pod_grads and mesh is not None
                and "pod" in mesh.axis_names and mesh.shape["pod"] > 1):
            # pod-local grads; explicit int8-compressed all-reduce on the
            # slow inter-pod links.  data/model axes stay auto-sharded.
            from jax.sharding import PartitionSpec as P
            from repro.optim.compression import int8_allreduce_sum
            n_pod = mesh.shape["pod"]

            @jax.shard_map(
                mesh=mesh, axis_names={"pod"},
                in_specs=(P(), P("pod")), out_specs=(P(), P()),
                check_vma=False)
            def pod_grads(params, b):
                l, g = value_and_grads(params, b)
                l = jax.lax.pmean(l, "pod")
                g = jax.tree.map(
                    lambda x: int8_allreduce_sum(x, "pod") / n_pod, g)
                return l, g

            loss, grads = pod_grads(state.params, batch)
        else:
            loss, grads = value_and_grads(state.params, batch)

        params, opt = adamw_update(state.params, grads, state.opt,
                                   sched(state.opt.step),
                                   weight_decay=weight_decay,
                                   grad_clip=grad_clip)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        return TrainState(params, opt), {"loss": loss, "grad_norm": gnorm,
                                         "lr": sched(state.opt.step)}

    return train_step


def init_train_state(lm: LM, key) -> TrainState:
    params = lm.init(key)
    return TrainState(params=params, opt=adamw_init(params))


def abstract_train_state(lm: LM) -> TrainState:
    """ShapeDtypeStruct state for dry-run lowering (no allocation)."""
    params = lm.abstract_params()
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return TrainState(
        params=params,
        opt=AdamWState(step=jax.ShapeDtypeStruct((), jnp.int32),
                       m=jax.tree.map(f32, params),
                       v=jax.tree.map(f32, params)))


def train_state_shardings(lm: LM, mesh) -> TrainState:
    ps = lm.param_shardings(mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    scalar = NamedSharding(mesh, P())
    return TrainState(params=ps,
                      opt=AdamWState(step=scalar, m=ps, v=ps))


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------

def make_serve_steps(lm: LM):
    def prefill_fn(params, tokens, extra=None):
        return serve.prefill(lm, params, tokens, extra)

    def decode_fn(params, cache, token, pos):
        return serve.decode_step(lm, params, cache, token, pos)

    return prefill_fn, decode_fn
