"""Device milliseconds per step of D-ReLU's top-k.  On the TPU, XLA runs
``lax.top_k`` as a ``sort`` op, and so is the CBSR encoding's argsort of
the kept column indices; these cells' steps have no other sort, so the
reader takes every ``sort``."""

import trace_reduce

PATTERN = r"(?:^|\s)sort\("


def read(ctx):
    if ctx["trace"] is None or not ctx["steps"]:
        return None
    t = trace_reduce.op_time(ctx["trace"], PATTERN)
    return t * 1e3 / ctx["steps"] if t > 0 else None
