"""Peak device memory in use over the run (``peak_bytes_in_use`` read
after the window), in megabytes (1e6 bytes)."""


def read(ctx):
    b = ctx["memory_peak_bytes"]
    return b / 1e6 if b else None
