"""Device milliseconds per step of the DR-SpMM Pallas kernels (arena
forward, arena sampled backward, dense tier).

The trace names no Pallas kernel by its function: each shows as an HLO
custom-call (``%branch_0_fun.N = f32[...] custom-call(...),
custom_call_target="tpu_custom_call"``).  Every Mosaic kernel of these
cells' steps is a DR-SpMM kernel (D-ReLU runs as XLA top-k), so the
reader takes all of them."""

import trace_reduce

PATTERN = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx["trace"] is None or not ctx["steps"]:
        return None
    t = trace_reduce.op_time(ctx["trace"], PATTERN)
    return t * 1e3 / ctx["steps"] if t > 0 else None
