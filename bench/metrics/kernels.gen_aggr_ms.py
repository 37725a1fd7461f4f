"""Device milliseconds per step of DeepGEN's softmax-aggregation kernels:
the Pallas kernels named ``gen_aggr_fwd`` and ``gen_aggr_bwd``, matched
by name as in ``kernels.drspmm_fwd_ms``.  A program without them reads
nothing here."""

import trace_reduce

PATTERN = r"^%?gen_aggr_(?:fwd|bwd)(?:\.\d+)?(?:\s|$)"


def read(ctx):
    if ctx["trace"] is None or not ctx["steps"]:
        return None
    t = trace_reduce.op_time(ctx["trace"], PATTERN)
    return t * 1e3 / ctx["steps"] if t > 0 else None
