"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B, H, Tq, D); k/v: (B, KVH, Tk, D). Returns (B, H, Tq, D)."""
    B, H, Tq, D = q.shape
    KVH, Tk = k.shape[1], k.shape[2]
    g = H // KVH
    qg = q.reshape(B, KVH, g, Tq, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgtd,bksd->bkgts", qg, kf) / math.sqrt(D)
    qpos = jnp.arange(Tq)[:, None]
    kpos = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bksd->bkgtd", p, vf)
    return out.reshape(B, H, Tq, D).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_table, lengths, layer,
                        k_scale=None, v_scale=None):
    """Decode attention over one layer of a stacked paged cache.

    q: (B, H, D); k_pages/v_pages: (L, P, ps, KVH, D) (int8 when scales
    given, scales (L, P, ps, KVH) f32); block_table: (B, max_pages) int32;
    lengths: (B,) tokens per slot; layer: scalar int32. Returns (B, H, D).
    """
    B, H, D = q.shape
    _, P, ps, KVH, _ = k_pages.shape
    max_pages = block_table.shape[1]
    S = max_pages * ps
    g = H // KVH

    def per_slot(qb, bt, L):
        pages = jnp.clip(bt, 0, P - 1)
        kk = k_pages[layer, pages].reshape(S, KVH, D).astype(jnp.float32)
        vv = v_pages[layer, pages].reshape(S, KVH, D).astype(jnp.float32)
        if k_scale is not None:
            ks = k_scale[layer, pages].reshape(S, KVH)
            vs = v_scale[layer, pages].reshape(S, KVH)
            kk = kk * ks[..., None]
            vv = vv * vs[..., None]
        qh = qb.reshape(KVH, g, D).astype(jnp.float32)
        s = jnp.einsum("kgd,skd->kgs", qh, kk) / math.sqrt(D)
        valid = jnp.arange(S) < L
        s = jnp.where(valid[None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgs,skd->kgd", p, vv).reshape(H, D)

    return jax.vmap(per_slot)(q, block_table, lengths).astype(q.dtype)


def fused_rmsnorm_ref(x, scale, residual=None, eps: float = 1e-6):
    """y = rmsnorm(x [+ residual]) * scale; returns (y, x+residual)."""
    xr = x if residual is None else x + residual
    xf = xr.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype), xr
