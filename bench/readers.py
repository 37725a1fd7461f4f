"""Loads a metric's reader, ``bench/metrics/<name>.py``, by the metric's
name (names hold dots, so readers are loaded from their path)."""

from __future__ import annotations

import importlib.util
import os

METRICS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "metrics")


def load(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
