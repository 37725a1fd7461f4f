"""FFN layers: SwiGLU, D-ReLU-sparsified SwiGLU (the paper's technique
generalized to LM FFNs), and expert-parallel MoE.

D-ReLU on the FFN hidden (``drelu_k``): the hidden activation keeps its
top-k entries per token (balanced row sparsity, Eqs. 2-3 of the paper).
* Training lowers it as a masked dense matmul (the sparsity regularizes and
  the mask is what the SSpMM backward would sample — bitwise the same math).
* Decode exploits it structurally: the down-projection gathers only the k
  surviving rows of W_down per token (``vals · W_down[idx]``), the direct
  analogue of DR-SpMM consuming CBSR operands — FLOPs drop by k/d_ff.

MoE: the router *is* a per-row dynamic top-k (same operator family as
D-ReLU).  Experts are sharded over the ``model`` axis (EP); tokens arrive
sequence-sharded, are all-gathered over ``model``, processed by the local
expert slice with a capacity buffer, and psum-scattered back — the a2a-free
EP scheme (comm = 2× activation volume on the Megatron-SP boundary).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.lm.common import round_up
from repro.sharding.specs import (batch_axes, constrain, get_mesh,
                                  manual_axes)


def swiglu_ffn(x, w_gate, w_up, w_down, drelu_k: int = 0,
               drelu_groups: int = 1):
    """(B,S,d) -> (B,S,d).  ``drelu_k`` > 0 sparsifies the hidden row-wise
    via grouped D-ReLU (groups = TP degree so the top-k is shard-local)."""
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, w_gate))
    h = h * jnp.einsum("bsd,df->bsf", x, w_up)
    h = constrain(h, ("batch", None, "mlp"))
    if 0 < drelu_k < h.shape[-1]:
        # Balanced top-k (D-ReLU): mask form — the matmul consumes a
        # k-per-row-sparse operand; decode uses the gather form below.
        h = _drelu_sharded(h, drelu_k, drelu_groups)
        h = constrain(h, ("batch", None, "mlp"))
    from jax.ad_checkpoint import checkpoint_name
    h = checkpoint_name(h, "proj")
    out = jnp.einsum("bsf,fd->bsd", h, w_down)
    return constrain(out, ("batch", "sp", None))


def _drelu_sharded(h, k: int, groups: int):
    """Grouped D-ReLU with the top-k forced shard-local.

    A bare ``lax.top_k`` on the model-sharded FFN hidden makes the SPMD
    partitioner replicate the sort operand (measured on qwen3-1.7b
    train_4k: a (256,4096,16,384) f32 all-gather ×2/layer ≈ 1.4 TB/device
    per step).  Running the same top-k inside a partial shard_map over the
    ``model`` axis pins every group's sort to its own shard — zero
    communication.  See EXPERIMENTS.md §Perf iteration 1.
    """
    from repro.core.drelu import drelu_grouped, _drelu_dense
    from repro.sharding.specs import manual_axes
    mesh = get_mesh()
    f = h.shape[-1]
    mp = mesh.shape.get("model", 1) if mesh is not None else 1
    if (mesh is None or mp == 1 or groups % mp or f % groups
            or k % groups or k >= f or manual_axes()):
        # manual_axes(): already inside a shard_map (e.g. the compressed
        # cross-pod gradient region) — nested full-manual maps are invalid;
        # the grouped form is still shard-local-friendly via its constraint.
        return drelu_grouped(h, k, groups)
    b, s, _ = h.shape
    hg = h.reshape(b, s, groups, f // groups)
    # fully manual: with only 'model' manual, the partitioner still chose to
    # replicate the batch over 'data' for the sort (measured 45 GB/layer
    # gathers) — pinning every mesh axis removes all SPMD freedom.
    dp = batch_axes(mesh)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    bspec = (dp if len(dp) > 1 else (dp[0] if dp else None))
    if b % max(n_dp, 1) != 0:
        bspec = None
    spec = P(bspec, None, "model", None)

    @jax.shard_map(mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
    def local_topk(x):
        return _drelu_dense(x, k // groups)

    return local_topk(hg).reshape(b, s, f)


def swiglu_ffn_decode_sparse(x, w_gate, w_up, w_down, drelu_k: int):
    """Decode-path FFN exploiting D-ReLU sparsity structurally.

    x: (B, 1, d).  The down-projection touches only the k surviving rows of
    W_down per token: y = Σ_t vals_t · W_down[idx_t] — CBSR-consuming matmul.
    """
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, w_gate))
    h = h * jnp.einsum("bsd,df->bsf", x, w_up)
    b, s, f = h.shape
    if not (0 < drelu_k < f):
        return jnp.einsum("bsf,fd->bsd", h, w_down)
    from repro.core.cbsr import cbsr_from_dense
    c = cbsr_from_dense(h.reshape(b * s, f), drelu_k)
    rows = jnp.take(w_down, c.idx, axis=0)          # (B*S, k, d) weight gather
    y = jnp.einsum("tk,tkd->td", c.values, rows)
    return y.reshape(b, s, -1)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def moe_capacity(tokens_per_shard: int, n_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    c = int(tokens_per_shard * top_k / n_experts * capacity_factor)
    return max(round_up(c, 8), 8)


def _route(x2d, router_w, top_k: int):
    """Top-k routing (the D-ReLU operator on the expert axis).

    Returns (probs (T,k), ids (T,k) int32, full_probs (T,E))."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    full = jax.nn.softmax(logits, axis=-1)
    probs, ids = jax.lax.top_k(full, top_k)
    probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    return probs.astype(x2d.dtype), ids.astype(jnp.int32), full


def _expert_ffn(buf, w_gate, w_up, w_down):
    """buf (E_l, C, d) through per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_local(x, router_w, w_gate, w_up, w_down, top_k, capacity_factor,
               e_offset: int, n_experts_global: int):
    """Single-shard MoE over local experts; x (B,S,d) fully local."""
    b, s, d = x.shape
    e_local = w_gate.shape[0]
    x2d = x.reshape(b * s, d)
    t = b * s
    probs, ids, _ = _route(x2d, router_w, top_k)

    cap = moe_capacity(t, n_experts_global, top_k, capacity_factor)
    flat_ids = ids.reshape(-1)                        # (T*k,)
    flat_probs = probs.reshape(-1)
    local = (flat_ids >= e_offset) & (flat_ids < e_offset + e_local)
    el = jnp.where(local, flat_ids - e_offset, e_local)   # sentinel drops
    onehot = jax.nn.one_hot(el, e_local + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot             # position in expert
    p = jnp.take_along_axis(pos, el[:, None], axis=1)[:, 0]
    keep = local & (p < cap)
    el_safe = jnp.where(keep, el, e_local)                # -> dropped row
    p_safe = jnp.where(keep, p, cap)

    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
    buf = jnp.zeros((e_local + 1, cap + 1, d), x.dtype)
    buf = buf.at[el_safe, p_safe].set(x2d[tok], mode="drop")
    y_buf = _expert_ffn(buf[:e_local, :cap], w_gate, w_up, w_down)
    y_buf = jnp.pad(y_buf, ((0, 1), (0, 1), (0, 0)))

    gathered = y_buf[el_safe, p_safe]                     # (T*k, d)
    contrib = gathered * (flat_probs * keep.astype(flat_probs.dtype))[:, None]
    y = jnp.zeros((t, d), x.dtype).at[tok].add(contrib)
    return y.reshape(b, s, d)


def moe_ffn(x, router_w, w_gate, w_up, w_down, *, n_experts: int,
            top_k: int, capacity_factor: float = 1.25) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE.  x (B,S,d) sequence-sharded on entry.

    Returns (y, aux_loss).  aux is the standard load-balance loss computed
    from the (cheap) router replay on the sharded view.
    """
    mesh = get_mesh()
    b, s, d = x.shape

    # load-balance aux (router on the sharded view — tiny matmul)
    _, ids_aux, full_aux = _route(x.reshape(b * s, d), router_w, top_k)
    frac = jnp.mean(jax.nn.one_hot(ids_aux, n_experts, dtype=jnp.float32),
                    axis=(0, 1))
    imp = jnp.mean(full_aux, axis=0)
    aux = n_experts * jnp.sum(frac * imp)

    use_shmap = (mesh is not None and "model" in mesh.axis_names
                 and not manual_axes()
                 and mesh.shape["model"] > 1
                 and n_experts % mesh.shape["model"] == 0
                 and s % mesh.shape["model"] == 0)
    if not use_shmap:
        y = _moe_local(x, router_w, w_gate, w_up, w_down, top_k,
                       capacity_factor, 0, n_experts)
        return y, aux

    mp = mesh.shape["model"]
    e_local = n_experts // mp
    dp = batch_axes(mesh)
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    if b % max(mesh.shape.get("pod", 1) * mesh.shape.get("data", 1), 1) != 0:
        bspec = None
    x_spec = P(bspec, "model", None)
    w_spec = P("model", None, None)

    @jax.shard_map(mesh=mesh,
                   in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
                   out_specs=x_spec, check_vma=False)
    def shmap_moe(x_l, rw, wg_l, wu_l, wd_l):
        shard = jax.lax.axis_index("model")
        # recover the full sequence on each model shard (SP boundary gather)
        x_full = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)
        y_full = _moe_local(x_full, rw, wg_l, wu_l, wd_l, top_k,
                            capacity_factor, shard * e_local, n_experts)
        # sum expert contributions across shards AND re-shard the sequence
        return jax.lax.psum_scatter(y_full, "model", scatter_dimension=1,
                                    tiled=True)

    y = shmap_moe(x, router_w, w_gate, w_up, w_down)
    return constrain(y, ("batch", "sp", None)), aux
