"""Share of the softmax-aggregation kernels' roofline: the least time the
window's ``gen_aggr`` calls require (per call the larger of operations
over peak FLOP/s and bytes over peak HBM bandwidth, counted from exact
shapes by work_deepgen.py) over the kernels' device time in the trace."""

import readers
import trace_reduce
import work_deepgen

PATTERN = readers.load("kernels.gen_aggr_ms").PATTERN


def read(ctx):
    if ctx["trace"] is None or ctx["peak"] is None:
        return None
    t = trace_reduce.op_time(ctx["trace"], PATTERN)
    if t <= 0:
        return None
    least = sum(work_deepgen.gen_aggr_least_s(s, ctx["cfg"], ctx["peak"])
                ["least_s"] for s in ctx["window_shapes"][:ctx["steps"]])
    return 100.0 * least / t
