"""Pallas TPU flash-attention kernel (forward) + jit wrapper.

Beyond-paper kernel: the LM substrate's training path uses the pure-jnp
chunked flash (models/lm/attention.py) because its scan composes with
autodiff/remat; this kernel is the TPU-native single-pass version for
serving/prefill, with explicit VMEM tiling:

* grid = (batch·heads, Sq / BLOCK_Q); each grid cell owns one q tile;
* k/v stream through VMEM in BLOCK_K-sized tiles via an in-kernel fori
  over the kv range (the whole per-head k/v lives in one BlockSpec block —
  rows are touched tile-by-tile, matching how Mosaic schedules the loads);
* online softmax in f32 VREGs; causal masking by absolute position;
* MXU-aligned tiles: BLOCK_Q = BLOCK_K = 128, head_dim padded to 128.

Validated in interpret mode against a naive softmax oracle
(tests/test_flash_kernel.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.drspmm import run_pallas

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool, sk: int,
                  block_k: int, scale: float):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)                 # (BQ, hd)
    bq, hd = q.shape
    nk = sk // block_k

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, hd), jnp.float32)

    def body(ki, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.dslice(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.dslice(ki * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1)
        acc = acc * corr[:, None] + jnp.dot(p, v,
                                            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    # causal: kv tiles beyond this q tile's diagonal contribute nothing —
    # bound the loop structurally (the in-kernel brick schedule).
    upper = (qi + 1) * bq
    n_vis = (upper + block_k - 1) // block_k if causal else nk
    m, l, acc = jax.lax.fori_loop(0, n_vis, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, interpret: bool | None = None
                    ) -> jax.Array:
    """q (B, Sq, H, hd); k/v (B, Sk, H, hd) — H already tiled/padded.

    Returns (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    bq = min(BLOCK_Q, sq)
    bk = min(BLOCK_K, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk)
    scale = 1.0 / (hd ** 0.5)

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, hd)

    out = run_pallas(lambda interp: pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, sk=sk, block_k=bk,
                          scale=scale),
        grid=(b * h, sq // bq),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, sk, hd), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((None, sk, hd), lambda g, i: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        interpret=interp,
    )(qf, kf, vf), interpret)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
