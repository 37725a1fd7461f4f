"""Model FLOP/s utilisation of the training step: the forward and backward
FLOPs the window's steps require (work.py, recomputation not counted) over
the window times the chip's bf16 peak.  The program runs float32, so this
is a share of a peak it cannot reach; it is stated against the bf16 peak
so that it never reads high."""

import work


def read(ctx):
    if ctx["peak"] is None or not ctx["steps"]:
        return None
    flops = sum(work.step_flops(s, ctx["cfg"])
                for s in ctx["window_shapes"][:ctx["steps"]])
    return 100.0 * flops / (ctx["window_s"] * ctx["peak"]["flops_bf16"])
