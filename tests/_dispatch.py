"""Kernel-launch counting on a traced computation: how many ``pallas_call``
dispatches a function issues (the one-dispatch-per-direction properties of
DESIGN.md §1 and §9)."""

import jax


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
