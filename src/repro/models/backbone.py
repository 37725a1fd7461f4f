"""Deep-backbone stack machinery: declarative specs, wiring, remat.

The exemplar deep circuit models (GSR-GNN, circuit-fewshot's DeepGEN
configs) are 10–15 layers at hidden 128; training them naively holds every
layer's activations — and, on the plan path, nothing extra, but the
activations alone — live through the backward.  This module turns the
ad-hoc ``for lp in layers`` loops of models/hgnn.py into a first-class
backbone (DESIGN.md §13):

* :class:`BackboneSpec` — the declarative stack description (depth,
  hidden, wiring, remat) shared by the trainer, the serve engine, the
  benches, and the examples.  ``CircuitTrainConfig.n_layers`` is its
  single depth source of truth.
* :func:`apply_stack` — the one stack executor.  ``wiring`` draws the
  skip pattern around the DR hetero layer: ``"plain"``
  (h_i = f_i(h_{i-1})), ``"residual"`` (+ h_{i-1} from the second layer
  on, so depth-1 is exactly the vanilla stack), ``"dense"`` (+ Σ of all
  previous layer states).  DeepGEN's pre-activation res+ GENConv stack is
  its own model (models/deepgen.py), not a wiring.  ``remat=True`` wraps
  each layer in :func:`jax.checkpoint`: the backward *recomputes* the
  layer's fused forward instead of storing its activations, and peak
  training memory stops scaling with depth.
* :func:`init_stack` — the shared init-key plumbing
  (``init_drcircuitgnn`` / ``init_homo`` are thin wrappers over it with
  bit-identical RNG streams to the pre-backbone code).

Remat boundary vs the custom-vjp leaf
-------------------------------------
``jax.checkpoint`` is drawn at the layer boundary: the checkpointed body
is one ``hetero_conv`` + its inter-layer activation, taking
``(layer_params, state, const)`` as explicit arguments.  Everything the
layer does NOT own — the graph, and the :class:`RelationPlan` super-arena
riding on it — goes through ``const``, so remat saves those leaves as
plain input residuals: stored once by reference (every layer's residual
aliases the same jit-argument buffers), never rematerialized, never
re-``device_put`` on recompute.  Inside the body, the plan executor
(``kernels/ops.py::drspmm_multi``) is the non-rematerialized *leaf*: its
custom VJP already recomputes nothing (its only data residual is the CBSR
index set), and under a checkpoint trace it threads the plan as a
custom-vjp primal (``ops._multi_traced``) so no closure captures
checkpoint-scope tracers.  The id-keyed executor LRU is untouched by
remat — checkpoint bodies always trace, and traced plans bypass the cache
— so recompute cannot thrash it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp

WIRINGS = ("plain", "residual", "dense")


@dataclasses.dataclass(frozen=True)
class BackboneSpec:
    """Declarative stack spec.  ``depth`` must match ``len(params.layers)``
    of the params it is applied to (:func:`spec_for` derives it)."""
    depth: int = 2
    hidden: int = 64
    wiring: str = "plain"        # plain | residual | dense
    remat: bool = False

    def __post_init__(self):
        if self.wiring not in WIRINGS:
            raise ValueError(f"unknown wiring {self.wiring!r}; "
                             f"expected one of {WIRINGS}")


def spec_for(layers: Sequence, hidden: int, *, wiring: str = "plain",
             remat: bool = False) -> BackboneSpec:
    """The spec describing an existing layer tuple — the back-compat
    default the thin wrappers use when no spec is passed."""
    return BackboneSpec(depth=len(layers), hidden=hidden, wiring=wiring,
                        remat=remat)


def init_stack(key, n_layers: int, layer_init: Callable, *,
               n_pre: int = 0, n_post: int = 0):
    """Shared init-key plumbing: split ``key`` into ``n_pre`` leading keys,
    one key per layer, and ``n_post`` trailing keys — the exact split
    pattern (and therefore the exact RNG stream) of the pre-backbone
    ``init_drcircuitgnn`` (pre=2, post=1) and ``init_homo`` (pre=0,
    post=2).  ``layer_init(key_i, i)`` builds layer ``i``'s params.

    Returns ``(pre_keys, layers, post_keys)``."""
    ks = jax.random.split(key, n_layers + n_pre + n_post)
    pre = tuple(ks[:n_pre])
    layers = tuple(layer_init(ks[n_pre + i], i) for i in range(n_layers))
    post = tuple(ks[n_pre + n_layers:])
    return pre, layers, post


def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def apply_stack(layers: Sequence, state, body: Callable, spec: BackboneSpec,
                const=None):
    """Run ``state`` through ``layers`` with the spec's wiring and remat.

    ``body(layer_params, state, const) -> state`` is one layer's compute
    (conv + activation); ``const`` carries the layer-invariant operands
    (graph + plan) as explicit arguments so remat saves them once as
    aliased input residuals (see module docstring).  Wiring:

    * ``plain``     s_i = body(l_i, s_{i-1})
    * ``residual``  s_i = body(l_i, s_{i-1}) + s_{i-1}   (i ≥ 1)
    * ``dense``     s_i = body(l_i, s_{i-1}) + Σ_{j<i} s_j   (i ≥ 1)

    Skips start at the SECOND layer — the first acts as the stem — so a
    depth-1 residual/dense stack is exactly the vanilla one
    (tests/test_backbone.py::test_residual_depth1_degenerate)."""
    if len(layers) != spec.depth:
        raise ValueError(f"spec.depth={spec.depth} but {len(layers)} "
                         f"layer params given")
    b = jax.checkpoint(body) if spec.remat else body
    acc = None                      # Σ of post-wiring layer states
    for i, lp in enumerate(layers):
        y = b(lp, state, const)
        if i and spec.wiring == "residual":
            y = _tree_add(y, state)
        elif i and spec.wiring == "dense":
            y = _tree_add(y, acc)
        acc = y if acc is None else _tree_add(acc, y)
        state = y
    return state
