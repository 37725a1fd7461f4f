"""Attention: GQA (flat-head internal layout) with optional qk-norm, a
chunked-flash training path, and a distributed flash-decode path for
sequence-sharded KV caches.

Layout decisions (DESIGN.md §5):

* **Flat padded heads.**  Q projections produce a flat (B, S, H_pad, hd)
  tensor with H_pad = round-up of n_heads to the TP degree; the padded heads
  have zero in/out weights and are numerically inert.  KV heads are kept at
  their true count and *tiled* to H_pad at use (q head h reads kv head
  h % n_kv), so every real kv head keeps an equal share of real q heads.
  This makes the head axis always shardable — archs like minitron (24H),
  minicpm (36H), whisper (20H) would otherwise replicate all attention
  compute across the 16-way model axis.
* **Chunked flash** (online softmax over q-chunk × kv-chunk scans): the
  (S×S) score matrix is never materialized — required for prefill_32k.
  ``causal_mode="brick"`` prunes upper-triangle chunk pairs with *static*
  prefix slices so the pruned FLOPs are absent from the HLO (§Perf lever).
* **Distributed flash-decode**: 32k–500k KV caches are sequence-sharded
  over ``model``; decode attention computes per-shard partial (max, sum,
  acc) inside shard_map and psum-combines — no KV all-gather ever.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.lm.common import head_rms_norm, rope
from repro.sharding.specs import (batch_axes, constrain, get_mesh,
                                  manual_axes)

NEG_INF = -1e30


def _pick_chunk(s: int, target: int) -> int:
    """Largest divisor of ``s`` that is ≤ target (handles e.g. 1500-frame
    whisper memories and 1600-token image grids)."""
    c = min(target, s)
    while s % c:
        c -= 1
    return c


def tile_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, H, hd); q head h reads kv head h % KV."""
    kv = k.shape[2]
    if kv == n_heads:
        return k
    reps = n_heads // kv
    return jnp.tile(k, (1, 1, reps, 1))


def _flash_inner(qc, ks, vs, qi, q_chunk, kv_chunk, causal, q_offset):
    """Online-softmax scan of one q-chunk over kv chunks.

    qc (B,qc,H,hd); ks/vs (B,nk,kc,H,hd).  Returns (B,qc,H,hd) f32.
    """
    b, qlen, h, hd = qc.shape
    nk = ks.shape[1]
    scale = 1.0 / (hd ** 0.5)
    q_pos = jnp.arange(q_chunk)
    k_pos = jnp.arange(kv_chunk)
    m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
    a0 = jnp.zeros((b, q_chunk, h, hd), jnp.float32)

    def kv_body(carry, inp):
        ki, kc, vc = inp
        m_prev, l_prev, acc = carry
        s = jnp.einsum("bqhd,bshd->bhqs", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            qp = q_offset + qi * q_chunk + q_pos
            kp = ki * kv_chunk + k_pos
            mask = qp[:, None] >= kp[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1)
        acc = (acc * corr.transpose(0, 2, 1)[..., None]
               + jnp.einsum("bhqs,bshd->bqhd", p, vc.astype(p.dtype)))
        return (m_new, l_new, acc), None

    # checkpoint each kv step: without it, autodiff saves the (nk, B, H,
    # qc, kc) probability tensors across the scan — the exact buffers flash
    # attention exists to avoid (measured 270 GB/step × 448 on qwen3
    # train_4k).  Recomputing scores in the backward costs ~1 extra qk
    # matmul but keeps residuals O(qc·hd) (§Perf iteration 2).
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(kv_body), (m0, l0, a0),
        (jnp.arange(nk), ks.transpose(1, 0, 2, 3, 4),
         vs.transpose(1, 0, 2, 3, 4)))
    return acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      *, causal: bool = True, q_chunk: int = 1024,
                      kv_chunk: int = 1024, causal_mode: str = "masked",
                      q_offset: int = 0) -> jax.Array:
    """Flash attention.  q (B,Sq,H,hd); k/v (B,Sk,H,hd) (already tiled)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    q_chunk = _pick_chunk(sq, q_chunk)
    kv_chunk = _pick_chunk(sk, kv_chunk)
    nq, nk = sq // q_chunk, sk // kv_chunk
    qs = q.reshape(b, nq, q_chunk, h, hd)
    ks = k.reshape(b, nk, kv_chunk, h, hd)
    vs = v.reshape(b, nk, kv_chunk, h, hd)

    if causal and causal_mode == "brick" and q_offset == 0 and sq == sk:
        # static prefix slices: q chunk i sees kv chunks [0, i] only;
        # upper-triangle work never enters the HLO.
        outs = [
            _flash_inner(qs[:, qi], ks[:, : qi + 1], vs[:, : qi + 1],
                         qi, q_chunk, kv_chunk, True, q_offset)
            for qi in range(nq)
        ]
        out = jnp.stack(outs, 1)
    else:
        def q_body(_, inp):
            qi, qc = inp
            return None, _flash_inner(qc, ks, vs, qi, q_chunk, kv_chunk,
                                      causal, q_offset)

        _, out = jax.lax.scan(q_body, None,
                              (jnp.arange(nq), qs.transpose(1, 0, 2, 3, 4)))
        out = out.transpose(1, 0, 2, 3, 4)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# full attention block (training / prefill)
# ---------------------------------------------------------------------------

def attention_block(x, wq, wk, wv, wo, *, n_kv: int,
                    qk_q: Optional[jax.Array] = None,
                    qk_k: Optional[jax.Array] = None,
                    rope_theta: float = 1e6,
                    positions: Optional[jax.Array] = None,
                    causal: bool = True,
                    kv_x: Optional[jax.Array] = None,
                    causal_mode: str = "masked",
                    return_kv: bool = False):
    """Projections + RoPE + chunked flash + out-projection.

    x (B,S,d) residual (sequence-sharded; the einsum boundary is where XLA
    all-gathers — Megatron-SP).  ``kv_x`` switches to cross-attention
    (no RoPE, no causal mask).  wq (d,H,hd); wk/wv (d,KV,hd); wo (H,hd,d).
    """
    b, s, d = x.shape
    src = x if kv_x is None else kv_x
    h = wq.shape[1]

    q = jnp.einsum("bsd,dhe->bshe", x, wq)
    k = jnp.einsum("bsd,dke->bske", src, wk)
    v = jnp.einsum("bsd,dke->bske", src, wv)
    if qk_q is not None:
        q = head_rms_norm(q, qk_q)
        k = head_rms_norm(k, qk_k)
    if kv_x is None:
        if positions is None:
            positions = jnp.arange(s)[None, :]
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    q = constrain(q, ("batch", None, "heads", None))
    # named residuals for the "proj" remat policy: the backward reuses the
    # projections instead of recomputing them (and re-all-gathering x)
    from jax.ad_checkpoint import checkpoint_name
    q = checkpoint_name(q, "proj")
    k = checkpoint_name(k, "proj")
    v = checkpoint_name(v, "proj")
    kt = tile_kv(k, h)
    vt = tile_kv(v, h)
    kt = constrain(kt, ("batch", None, "heads", None))
    vt = constrain(vt, ("batch", None, "heads", None))

    ctx = chunked_attention(q, kt, vt, causal=causal and kv_x is None,
                            causal_mode=causal_mode)
    ctx = constrain(ctx, ("batch", None, "heads", None))
    ctx = checkpoint_name(ctx, "proj")
    out = jnp.einsum("bshe,hed->bsd", ctx, wo)
    out = constrain(out, ("batch", "sp", None))
    if return_kv:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# decode: sequence-sharded KV cache, distributed flash-decode
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, pos, new_k, new_v):
    """One-token attention against a (possibly seq-sharded) KV cache.

    q (B,1,H,hd); caches (B,S_max,KV,hd) physically P(batch,'model',·,·);
    new_k/new_v (B,1,KV,hd) written at ``pos`` before attending.
    ``pos`` is a scalar (lockstep batch) or a (B,) vector (continuous
    batching: every slot at its own position — repro/serve/engine.py).
    Returns (ctx (B,1,H,hd), k_cache, v_cache).
    """
    mesh = get_mesh()
    s_max = k_cache.shape[1]
    h = q.shape[2]
    use_shmap = (mesh is not None and "model" in mesh.axis_names
                 and not manual_axes()
                 and mesh.shape["model"] > 1
                 and s_max % mesh.shape["model"] == 0)
    if not use_shmap:
        if getattr(pos, "ndim", 0) == 1:           # per-slot positions
            b_idx = jnp.arange(q.shape[0])
            k_cache = k_cache.at[b_idx, pos].set(new_k[:, 0])
            v_cache = v_cache.at[b_idx, pos].set(new_v[:, 0])
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, new_k,
                                                          pos, 1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, new_v,
                                                          pos, 1)
        ctx = _local_decode(q, k_cache, v_cache, pos, 0)
        return ctx, k_cache, v_cache

    dp = batch_axes(mesh)
    b = q.shape[0]
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    bspec = (dp if len(dp) > 1 else (dp[0] if dp else None))
    if b % max(n_dp, 1) != 0:
        bspec = None                                      # tiny-batch decode
    cache_spec = P(bspec, "model", None, None)
    q_spec = P(bspec, None, None, None)
    new_spec = P(bspec, None, None, None)

    @jax.shard_map(
        mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, P(), new_spec, new_spec),
        out_specs=(q_spec, cache_spec, cache_spec),
        check_vma=False)
    def shmap_decode(q_l, kc_l, vc_l, pos_, nk_l, nv_l):
        shard = jax.lax.axis_index("model")
        s_local = kc_l.shape[1]
        offset = shard * s_local
        local_pos = pos_ - offset
        in_range = jnp.logical_and(local_pos >= 0, local_pos < s_local)
        safe_pos = jnp.clip(local_pos, 0, s_local - 1)
        if getattr(pos_, "ndim", 0) == 1:          # per-slot positions
            b_idx = jnp.arange(kc_l.shape[0])
            sel = in_range[:, None, None]
            kc_l = kc_l.at[b_idx, safe_pos].set(
                jnp.where(sel, nk_l[:, 0], kc_l[b_idx, safe_pos]))
            vc_l = vc_l.at[b_idx, safe_pos].set(
                jnp.where(sel, nv_l[:, 0], vc_l[b_idx, safe_pos]))
        else:
            kc_new = jax.lax.dynamic_update_slice_in_dim(kc_l, nk_l,
                                                         safe_pos, 1)
            vc_new = jax.lax.dynamic_update_slice_in_dim(vc_l, nv_l,
                                                         safe_pos, 1)
            kc_l = jnp.where(in_range, kc_new, kc_l)
            vc_l = jnp.where(in_range, vc_new, vc_l)
        m, l, acc = _partial_decode(q_l, kc_l, vc_l, pos_, offset)
        m_g = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, "model")
        acc_g = jax.lax.psum(acc * corr.transpose(0, 2, 1)[..., None],
                             "model")
        ctx = acc_g / jnp.maximum(l_g, 1e-30).transpose(0, 2, 1)[..., None]
        return ctx.astype(q_l.dtype), kc_l, vc_l

    return shmap_decode(q, k_cache, v_cache, pos, new_k, new_v)


def _partial_decode(q, kc, vc, pos, offset):
    """Masked partial attention stats over one KV shard (f32).

    q (B,1,H,hd); kc/vc (B,S_l,KV,hd); pos scalar or (B,)."""
    b, _, h, hd = q.shape
    s_local = kc.shape[1]
    scale = 1.0 / (hd ** 0.5)
    kt = tile_kv(kc, h)
    vt = tile_kv(vc, h)
    s = jnp.einsum("bqhd,bshd->bhqs", q, kt,
                   preferred_element_type=jnp.float32) * scale
    span = jnp.arange(s_local) + offset
    if getattr(pos, "ndim", 0) == 1:
        valid = span[None, :] <= pos[:, None]          # (B, S_l)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    else:
        valid = span <= pos
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    m = s.max(-1)                                          # (B,H,1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    acc = jnp.einsum("bhqs,bshd->bqhd", p, vt.astype(p.dtype))
    return m, l, acc


def _local_decode(q, kc, vc, pos, offset):
    m, l, acc = _partial_decode(q, kc, vc, pos, offset)
    return (acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
            ).astype(q.dtype)
