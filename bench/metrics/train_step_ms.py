"""Milliseconds per optimizer step: the whole measured window (host
packing, dispatch, the device barrier and the trainer's per-step
bookkeeping included) over the steps completed in it."""


def read(ctx):
    if not ctx["steps"]:
        return None
    return ctx["window_s"] * 1e3 / ctx["steps"]
