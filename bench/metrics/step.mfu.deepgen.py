"""Model FLOP/s utilisation of the DeepGEN training step: the matmul FLOPs
the window's steps require (work_deepgen.py) over the window times the
chip's bf16 peak, as ``step.mfu``."""

import work_deepgen


def read(ctx):
    if ctx["peak"] is None or not ctx["steps"]:
        return None
    flops = sum(work_deepgen.step_flops(s, ctx["cfg"])
                for s in ctx["window_shapes"][:ctx["steps"]])
    return 100.0 * flops / (ctx["window_s"] * ctx["peak"]["flops_bf16"])
