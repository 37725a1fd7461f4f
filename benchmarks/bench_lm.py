"""LM substrate micro-benchmarks: per-family train-step and decode-step
wall time on reduced configs (CPU proxy; full configs are covered by the
dry-run roofline)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_jit
from repro.configs.base import get_config, reduced
from repro.models.lm import serve
from repro.models.lm.model import build_lm
from repro.train import lm_step

ARCHS = ("qwen3-0.6b", "mamba2-1.3b", "granite-moe-1b-a400m", "zamba2-1.2b")


def bench():
    for arch in ARCHS:
        cfg = reduced(get_config(arch))
        lm = build_lm(cfg)
        params = lm.init(jax.random.PRNGKey(0))
        b, s = 4, 64
        batch = {"tokens": jnp.zeros((b, s), jnp.int32),
                 "targets": jnp.ones((b, s), jnp.int32)}
        state = lm_step.init_train_state(lm, jax.random.PRNGKey(1))
        step = jax.jit(lm_step.make_train_step(lm, total_steps=100))
        t = time_jit(step, state, batch, iters=5)
        tokens_per_s = b * s / (t / 1e6)
        emit(f"lm_train/{arch}", t, f"tokens_per_s={tokens_per_s:.0f}")

        cache, _ = serve.prefill(lm, params, batch["tokens"], None)
        dec = jax.jit(lambda p, c, tok, pos:
                      serve.decode_step(lm, p, c, tok, pos))
        t = time_jit(dec, params, cache, jnp.zeros((b, 1), jnp.int32),
                     jnp.asarray(s - 1, jnp.int32), iters=5)
        emit(f"lm_decode/{arch}", t, f"tokens_per_s={b / (t / 1e6):.0f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    bench()
