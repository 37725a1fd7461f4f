"""Minimal, shardable AdamW (pure pytree — no optax dependency).

Optimizer state mirrors the parameter pytree leaf-for-leaf, so any sharding
applied to params transfers to m/v verbatim — this is what lets the dry-run
lower the optimizer over the production mesh without extra spec plumbing.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array          # scalar int32
    m: Any                   # first moment, like params
    v: Any                   # second moment, like params


def adamw_init(params) -> AdamWState:
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return AdamWState(step=jnp.zeros((), jnp.int32),
                      m=jax.tree.map(zeros, params),
                      v=jax.tree.map(zeros, params))


@jax.named_scope("adamw")
def adamw_update(params, grads, state: AdamWState, lr,
                 b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.0, grad_clip: float = 0.0):
    """One AdamW step.  ``lr`` may be a scalar array (schedule output)."""
    step = state.step + 1
    if grad_clip > 0.0:
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)

    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
                     state.m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2)
                     * jnp.square(g.astype(jnp.float32)), state.v, grads)
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, m_, v_):
        mh = m_ / bc1
        vh = v_ / bc2
        delta = mh / (jnp.sqrt(vh) + eps) + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype)

    new_params = jax.tree.map(upd, params, m, v)
    return new_params, AdamWState(step=step, m=m, v=v)
