"""Pipeline parallelism: GPipe-style microbatch pipelining over a ``stage``
mesh axis, as a composable shard_map transform.

``pipeline_apply(mesh, stage_fn, stage_params, microbatches)`` runs

    y_m = stage_fn(p_{S-1}, ... stage_fn(p_1, stage_fn(p_0, x_m)))

for every microbatch m, with stage s resident on mesh slice ``stage=s`` and
activations moving stage→stage via ``collective_permute`` (the ICI-neighbor
transfer on a real TPU torus).  The schedule is the classic GPipe ramp:
T = n_micro + n_stages − 1 ticks; at tick t, stage s works on microbatch
t − s (bubble fraction (S−1)/T).  The backward pass falls out of autodiff —
``collective_permute`` transposes to the reverse permute, giving the
standard reverse-schedule pipeline backward.

Composition caveat: on this JAX version, partial-manual shard_map
(``axis_names={'stage'}`` with auto data/model axes) rejects replicated
out_specs, so ``pipeline_apply`` currently targets a stage-only mesh (or a
mesh where the other axes are handled by an outer pjit).  Intra-stage
TP composes by nesting the model axis inside ``stage_fn`` via the usual
``constrain`` hints once that JAX limitation lifts.

Integration note (DESIGN.md §5): the LM cells use DP×TP×SP×EP meshes where
depth fits memory after remat; PP is provided for the deeper-than-memory
regime and validated on a 4-stage pipeline in tests/test_pipeline.py.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, stage_fn: Callable, stage_params,
                   microbatches: jax.Array, *, axis: str = "stage"
                   ) -> jax.Array:
    """Run ``microbatches`` (M, mb, ...) through S pipelined stages.

    ``stage_params``: pytree with leading stage axis S on every leaf.
    Returns (M, mb, ...) outputs (shapes preserved by stage_fn).
    """
    n_stage = mesh.shape[axis]
    m = microbatches.shape[0]
    ticks = m + n_stage - 1
    perm_fwd = [(i, i + 1) for i in range(n_stage - 1)]

    p_spec = jax.tree.map(lambda _: P(axis), stage_params)

    @jax.shard_map(
        mesh=mesh, axis_names={axis},
        in_specs=(p_spec, P()), out_specs=P(), check_vma=False)
    def run(params_l, mbs):
        sid = jax.lax.axis_index(axis)
        p_local = jax.tree.map(lambda a: a[0], params_l)   # squeeze stage dim
        buf0 = jnp.zeros_like(mbs[0])
        outs0 = jnp.zeros_like(mbs)

        def tick(t, carry):
            incoming, outs = carry
            # stage 0 injects microbatch t (clamped; inactive ticks are
            # masked out by the collection step below)
            inject = mbs[jnp.clip(t, 0, m - 1)]
            cur = jnp.where(sid == 0, inject, incoming)
            y = stage_fn(p_local, cur)
            # last stage emits microbatch t-(S-1)
            out_idx = jnp.clip(t - (n_stage - 1), 0, m - 1)
            emit = jnp.logical_and(sid == n_stage - 1,
                                   t - (n_stage - 1) >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y.astype(o.dtype), out_idx, 0),
                lambda o: o, outs)
            nxt = jax.lax.ppermute(y, axis, perm_fwd)
            return nxt, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf0, outs0))
        # replicate the last stage's collected outputs to every stage
        keep = (sid == n_stage - 1).astype(outs.dtype)
        return jax.lax.psum(outs * keep, axis)

    return run(stage_params, microbatches)


def sequential_reference(stage_fn, stage_params, microbatches):
    """Oracle: apply the S stages in sequence, no pipelining."""
    def one(x):
        def body(x_, p):
            return stage_fn(p, x_), None
        y, _ = jax.lax.scan(body, x, stage_params)
        return y
    return jax.vmap(one)(microbatches)
