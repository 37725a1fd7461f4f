"""Share of the DR-SpMM kernels' roofline: the least time the window's
DR-SpMM calls require (per call the larger of FLOPs over peak FLOP/s and
bytes over peak HBM bandwidth, counted from exact shapes by work.py) over
the kernels' device time in the trace.  The calls are memory-bound at
these shapes."""

import readers
import trace_reduce
import work

PATTERN = readers.load("kernels.drspmm_ms").PATTERN


def read(ctx):
    if ctx["trace"] is None or ctx["peak"] is None:
        return None
    t = trace_reduce.op_time(ctx["trace"], PATTERN)
    if t <= 0:
        return None
    least = sum(work.drspmm_least_s(s, ctx["cfg"], ctx["peak"])["least_s"]
                for s in ctx["window_shapes"][:ctx["steps"]])
    return 100.0 * least / t
