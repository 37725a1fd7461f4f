"""Host milliseconds per step of the trainer's ``train.plan_upload`` span:
the ``device_put`` of a graph's relation plan on a plan-cache miss, inside
the benchmark's ``step`` span and so not in ``host.pack_ms``."""

import program_trace


def read(ctx):
    return program_trace.span_ms(ctx, "train.plan_upload")
