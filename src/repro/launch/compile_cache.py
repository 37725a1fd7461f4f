"""Persistent XLA compilation cache for the entry points that compile for
the chip (``chip_smoke.py``, the benchmark CLIs).

``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX itself reads it
and nothing else is configured.  Otherwise the cache lives at one fixed path
inside the checkout, ``<repo>/.jax_cache`` (git-ignored): a fixed directory
is what lets a later run from the same checkout find earlier entries.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
