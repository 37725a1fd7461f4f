"""Device milliseconds per step of the DR-SpMM sampled backward kernels:
the Pallas kernels named ``drspmm_arena_bwd`` and ``drspmm_dense_bwd``,
matched by name as in ``kernels.drspmm_fwd_ms``."""

import trace_reduce

PATTERN = r"^%?drspmm_(?:arena|dense)_bwd(?:\.\d+)?(?:\s|$)"


def read(ctx):
    if ctx["trace"] is None or not ctx["steps"]:
        return None
    t = trace_reduce.op_time(ctx["trace"], PATTERN)
    return t * 1e3 / ctx["steps"] if t > 0 else None
