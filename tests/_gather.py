"""Which gather path the DR-SpMM arena kernels took, read from the
``kernels.gather_path`` counter they bump once per launch as it is traced
(kernels/drspmm.py)."""

from repro.obs.metrics import DEFAULT_REGISTRY


def launches():
    """Arena-kernel launches traced so far, per gather path."""
    out = {"vmem": 0.0, "dma": 0.0}
    for labels, counter in DEFAULT_REGISTRY.series(
            "kernels.gather_path").items():
        out[dict(labels)["path"]] += counter.value
    return out


def path_of(fn, *args):
    """(fn(*args), the one gather path its arena-kernel launches took)."""
    before = launches()
    out = fn(*args)
    took = [p for p, n in launches().items() if n > before[p]]
    assert len(took) == 1, took
    return out, took[0]
