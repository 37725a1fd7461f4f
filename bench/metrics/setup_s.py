"""Seconds from process start to the first timed step: generation,
packing, weight init, compiles (or loads from the persistent cache) and
the warm-up pass, which includes the three checked steps."""


def read(ctx):
    return ctx["setup_s"]
