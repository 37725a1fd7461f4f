"""Device milliseconds per step of the DR-SpMM forward kernels: the Pallas
kernels named ``drspmm_arena_fwd`` (relation-fused super-arena) and
``drspmm_dense_fwd`` (dense tier).  A named ``pallas_call`` gives its HLO
instruction the kernel's name, so the op's text in the trace starts with
it (``%drspmm_arena_fwd.3 = f32[...] custom-call(...)``).  A program
whose kernels carry no name reads nothing here."""

import trace_reduce

PATTERN = r"^%?drspmm_(?:arena|dense)_fwd(?:\.\d+)?(?:\s|$)"


def read(ctx):
    if ctx["trace"] is None or not ctx["steps"]:
        return None
    t = trace_reduce.op_time(ctx["trace"], PATTERN)
    return t * 1e3 / ctx["steps"] if t > 0 else None
