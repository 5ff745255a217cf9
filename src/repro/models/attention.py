"""Attention: GQA (full / sliding-window) and MLA (DeepSeek-style latent).

Three execution modes share one parameter layout:
  * ``train``   — full sequence, no cache, causal (or bidirectional) mask.
  * ``prefill`` — full sequence, writes the KV cache, returns it.
  * ``decode``  — q_len == 1 against a cache with per-slot positions.

Cache layout (GQA):  {"k": (B, S, n_kv, Dh), "v": ..., "kpos": (B, S) int32}
  ``kpos`` holds the absolute position of each cache row (-2**30 = empty),
  which uniformly supports full caches, ring-buffer sliding windows, and
  continuous batching with ragged per-slot lengths.
Cache layout (MLA):  {"ckv": (B, S, rank), "kr": (B, S, rope), "kpos": ...}
Cache layout (paged GQA): {"k": (L, P, page, n_kv, Dh), "v": ...} — one
  block-table page store per segment, shared by every slot and stacked over
  the segment's L layers. The paged functions take the whole stacked store
  and a layer index: writes land at [layer, page, offset] and reads gather
  (or DMA) that layer's pages, so a layer's store is never sliced out. The
  per-slot page list and live lengths arrive as separate decode-step inputs
  (``gqa_decode_paged``), and the attention read runs through the Pallas
  paged kernel or its XLA reference (kernels/ops.py dispatch).
Int8 KV (beyond-paper optimization): "k"/"v" stored int8 + "k_scale"/"v_scale"
  (B, S, n_kv) float32 per-token-per-head scales (paged: (L, P, page, n_kv)).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, KeyGen, dense_init
from repro.models.layers import apply_rope, apply_norm
from repro.models.blocked_attn import flash_sdpa

NEG_INF = -1e30
EMPTY_POS = -(1 << 30)


# ======================================================================
# parameter init
# ======================================================================

def init_attention(cfg: ModelConfig, key, cross: bool = False):
    if cfg.attn_type == "mla" and not cross:
        return _init_mla(cfg, key)
    return _init_gqa(cfg, key, cross=cross)


def _init_gqa(cfg: ModelConfig, key, cross: bool = False):
    kg = KeyGen(key)
    dt = cfg.compute_dtype
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(kg(), (d, h * dh), dt),
        "wk": dense_init(kg(), (d, kv * dh), dt),
        "wv": dense_init(kg(), (d, kv * dh), dt),
        "wo": dense_init(kg(), (h * dh, d), dt, scale=1.0 / math.sqrt(h * dh)),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((h * dh,), dt)
        p["bk"] = jnp.zeros((kv * dh,), dt)
        p["bv"] = jnp.zeros((kv * dh,), dt)
        p["bo"] = jnp.zeros((d,), dt)
    return p


def _init_mla(cfg: ModelConfig, key):
    kg = KeyGen(key)
    dt = cfg.compute_dtype
    d, h = cfg.d_model, cfg.n_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    p = {}
    if cfg.q_lora_rank > 0:
        p["wq_a"] = dense_init(kg(), (d, cfg.q_lora_rank), dt)
        p["q_norm"] = {"scale": jnp.ones((cfg.q_lora_rank,), jnp.float32)}
        p["wq_b"] = dense_init(kg(), (cfg.q_lora_rank, h * (nope + rope)), dt)
    else:
        p["wq"] = dense_init(kg(), (d, h * (nope + rope)), dt)
    p["wkv_a"] = dense_init(kg(), (d, cfg.kv_lora_rank + rope), dt)
    p["kv_norm"] = {"scale": jnp.ones((cfg.kv_lora_rank,), jnp.float32)}
    p["wkv_b"] = dense_init(kg(), (cfg.kv_lora_rank, h * (nope + vd)), dt)
    p["wo"] = dense_init(kg(), (h * vd, d), dt, scale=1.0 / math.sqrt(h * vd))
    return p


# ======================================================================
# KV quantization helpers (int8 per-token-per-head symmetric)
# ======================================================================

def quantize_kv(x):
    """x: (B, T, n_kv, Dh) -> (int8 values, float32 scales (B, T, n_kv))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ======================================================================
# masks
# ======================================================================

def _causal_window_mask(q_pos, k_pos, window: int, causal: bool):
    """q_pos: (..., Tq), k_pos: (..., Tk) -> bool (..., Tq, Tk)."""
    dq = q_pos[..., :, None]
    dk = k_pos[..., None, :]
    m = dk > EMPTY_POS // 2  # valid rows only
    if causal:
        m &= dk <= dq
    if window > 0:
        m &= dk > dq - window
    return m


# ======================================================================
# core attention math (XLA path; fp32 softmax)
# ======================================================================

def _sdpa(q, k, v, mask, softcap: float = 0.0):
    """q: (B,Tq,KV,G,D)  k: (B,Tk,KV,D)  v: (B,Tk,KV,Dv)  mask: (B,Tq,Tk) or (Tq,Tk)."""
    d = q.shape[-1]
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(v.dtype), v)
    return out


# ======================================================================
# GQA forward
# ======================================================================

def _project_qkv(cfg: ModelConfig, p, x, positions, rope: bool = True):
    B, T = x.shape[:2]
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.use_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, h, dh)
    k = k.reshape(B, T, kv, dh)
    v = v.reshape(B, T, kv, dh)
    if rope and cfg.pos == "rope":
        q = apply_rope(cfg, q, positions)
        k = apply_rope(cfg, k, positions)
    return q, k, v


def gqa_full(cfg: ModelConfig, p, x, positions, *, causal: bool = True,
             window: int = 0, kv_override=None):
    """train/prefill attention over the whole sequence (no cache read)."""
    B, T = x.shape[:2]
    h, kv_h, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if kv_override is None:
        q, k, v = _project_qkv(cfg, p, x, positions)
    else:  # cross-attention: kv computed from encoder output
        q = (x @ p["wq"] + (p["bq"] if cfg.use_bias else 0)).reshape(B, T, h, dh)
        k, v = kv_override
    g = h // k.shape[2]
    qg = q.reshape(B, T, k.shape[2], g, dh)
    use_flash = (cfg.attn_impl in ("blocked", "pallas")
                 and cfg.logit_softcap == 0.0 and kv_override is None)
    if use_flash:
        qpos = jnp.broadcast_to(positions, (B, T)) if positions.ndim == 1 else positions
        out = flash_sdpa(qg, k, v, qpos, qpos, causal=causal, window=window)
    else:
        if kv_override is None:
            mask = _causal_window_mask(positions, positions, window, causal)
        else:
            Tk = k.shape[1]
            mask = jnp.ones((B, T, Tk), bool)
        out = _sdpa(qg, k, v, mask, cfg.logit_softcap)
    out = out.reshape(B, T, h * dh)
    y = out @ p["wo"]
    if cfg.use_bias:
        y = y + p["bo"]
    return y, (k, v)


def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int, window: int = 0):
    S = min(max_len, window) if window > 0 else max_len
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    c = {"kpos": jnp.full((batch, S), EMPTY_POS, jnp.int32)}
    if cfg.kv_cache_dtype == "int8":
        c["k"] = jnp.zeros((batch, S, kv, dh), jnp.int8)
        c["v"] = jnp.zeros((batch, S, kv, dh), jnp.int8)
        c["k_scale"] = jnp.zeros((batch, S, kv), jnp.float32)
        c["v_scale"] = jnp.zeros((batch, S, kv), jnp.float32)
    else:
        c["k"] = jnp.zeros((batch, S, kv, dh), cfg.kv_dtype)
        c["v"] = jnp.zeros((batch, S, kv, dh), cfg.kv_dtype)
    return c


def _cache_write(cache, new_k, new_v, positions, window: int,
                 quantized: bool):
    """Scatter one token per batch row into the cache at ring/linear slots."""
    S = cache["k"].shape[1]
    slot = positions % S if window > 0 else jnp.minimum(positions, S - 1)

    def wr(buf, val):  # buf: (B,S,...), val: (B,...) one token
        return jax.vmap(lambda b, v_, i: b.at[i].set(v_))(buf, val, slot)

    if quantized:
        qk, sk = quantize_kv(new_k)
        qv, sv = quantize_kv(new_v)
        cache = dict(cache,
                     k=wr(cache["k"], qk[:, 0]),
                     v=wr(cache["v"], qv[:, 0]),
                     k_scale=wr(cache["k_scale"], sk[:, 0]),
                     v_scale=wr(cache["v_scale"], sv[:, 0]))
    else:
        cache = dict(cache, k=wr(cache["k"], new_k[:, 0]),
                     v=wr(cache["v"], new_v[:, 0]))
    cache["kpos"] = jax.vmap(lambda b, i, pv: b.at[i].set(pv))(
        cache["kpos"], slot, positions)
    return cache


def gqa_decode(cfg: ModelConfig, p, x, positions, cache, *, window: int = 0,
               kv_override=None):
    """x: (B, 1, d); positions: (B,) absolute position of the new token."""
    B = x.shape[0]
    h, dh = cfg.n_heads, cfg.head_dim
    quantized = cfg.kv_cache_dtype == "int8"
    if kv_override is None:
        q, k_new, v_new = _project_qkv(cfg, p, x, positions[:, None])
        cache = _cache_write(cache, k_new, v_new, positions, window, quantized)
        if quantized:
            k = dequantize_kv(cache["k"], cache["k_scale"], cfg.compute_dtype)
            v = dequantize_kv(cache["v"], cache["v_scale"], cfg.compute_dtype)
        else:
            k = cache["k"].astype(cfg.compute_dtype)
            v = cache["v"].astype(cfg.compute_dtype)
        mask = _causal_window_mask(positions[:, None], cache["kpos"],
                                   window, causal=True)
    else:
        q = (x @ p["wq"] + (p["bq"] if cfg.use_bias else 0)).reshape(B, 1, h, dh)
        if cfg.pos == "rope":
            q = apply_rope(cfg, q, positions[:, None])
        k, v = kv_override
        mask = jnp.ones((B, 1, k.shape[1]), bool)
    kv_h = k.shape[2]
    qg = q.reshape(B, 1, kv_h, h // kv_h, dh)
    out = _sdpa(qg, k, v, mask, cfg.logit_softcap)
    y = out.reshape(B, 1, h * dh) @ p["wo"]
    if cfg.use_bias:
        y = y + p["bo"]
    return y, cache


# ======================================================================
# chunked prefill append (continuous batching; serving hot path)
# ======================================================================

def gqa_chunk_append(cfg: ModelConfig, p, x, positions, valid, lane, cache,
                     *, block_table=None, layer=None):
    """Append one prompt *chunk* for a single lane and attend causally over
    everything that lane has written so far (earlier chunks included).

    x: (1, C, d) chunk hidden states; positions: (C,) absolute positions;
    valid: (C,) bool (False rows are pad — their writes are dropped);
    lane: scalar int32 — the batch row / block-table row this chunk owns.

    Dense cache (leaves (B, S, ...)): chunk K/V is scattered into the
    lane's linear region with an explicit index scatter (invalid rows are
    redirected to out-of-bounds index S, which JAX drops), then the lane's
    full row is read back with the kpos causal mask. Write-before-attend
    gives cross-chunk causality for free: every row with kpos <= q_pos was
    freshly written by this request (chunks land in order), and rows this
    request has not yet written carry kpos from a previous occupant only
    at positions > q_pos, which the causal mask excludes.

    Paged cache (block_table given; the segment's stacked store, leaves
    (L, P, ps, ...), and this block's ``layer``): writes go through the
    lane's block-table row to [layer, page, offset] (unmapped / invalid
    entries are redirected to the out-of-bounds page P and dropped), reads
    gather only the lane's pages of that layer and mask by absolute index
    <= q_pos — positional validity, exactly like decode.
    """
    C = x.shape[1]
    h, dh = cfg.n_heads, cfg.head_dim
    quantized = cfg.kv_cache_dtype == "int8"
    q, k_new, v_new = _project_qkv(cfg, p, x, positions[None, :])
    if quantized:
        qk, sk = quantize_kv(k_new)
        qv, sv = quantize_kv(v_new)

    if block_table is None:
        S = cache["k"].shape[1]
        idx = jnp.where(valid, jnp.minimum(positions, S - 1), S)

        def wr(buf, val):          # buf (B, S, ...), val (1, C, ...)
            return buf.at[lane, idx].set(val[0].astype(buf.dtype))

        with jax.named_scope("sprout.kv_write"):
            if quantized:
                cache = dict(cache, k=wr(cache["k"], qk), v=wr(cache["v"], qv),
                             k_scale=wr(cache["k_scale"], sk),
                             v_scale=wr(cache["v_scale"], sv))
            else:
                cache = dict(cache, k=wr(cache["k"], k_new),
                             v=wr(cache["v"], v_new))
            cache["kpos"] = cache["kpos"].at[lane, idx].set(positions)
        if quantized:
            k = dequantize_kv(cache["k"][lane], cache["k_scale"][lane],
                              cfg.compute_dtype)[None]
            v = dequantize_kv(cache["v"][lane], cache["v_scale"][lane],
                              cfg.compute_dtype)[None]
        else:
            k = cache["k"][lane][None].astype(cfg.compute_dtype)
            v = cache["v"][lane][None].astype(cfg.compute_dtype)
        mask = _causal_window_mask(positions[None, :], cache["kpos"][lane][None],
                                   0, causal=True)
    else:
        P, ps = cache["k"].shape[1:3]
        bt = block_table[lane]                       # (max_pages,)
        pidx = jnp.clip(positions // ps, 0, bt.shape[0] - 1)
        entry = bt[pidx]
        page = jnp.where(valid & (entry >= 0), entry, P)
        off = positions % ps

        def wr(buf, val):          # buf (L, P, ps, ...), val (1, C, ...)
            return buf.at[layer, page, off].set(val[0].astype(buf.dtype))

        with jax.named_scope("sprout.kv_write"):
            if quantized:
                cache = dict(cache, k=wr(cache["k"], qk), v=wr(cache["v"], qv),
                             k_scale=wr(cache["k_scale"], sk),
                             v_scale=wr(cache["v_scale"], sv))
            else:
                cache = dict(cache, k=wr(cache["k"], k_new),
                             v=wr(cache["v"], v_new))
        # gather the lane's pages (clamped; stale/unmapped rows sit at
        # absolute indices > q_pos and are masked positionally)
        safe = jnp.clip(bt, 0, P - 1)

        def lane_pages(buf):       # (max_pages * ps, ...) of this layer
            return buf[layer, safe].reshape(-1, *buf.shape[3:])

        kp, vp = lane_pages(cache["k"]), lane_pages(cache["v"])
        if quantized:
            ksp = lane_pages(cache["k_scale"])
            vsp = lane_pages(cache["v_scale"])
            k = dequantize_kv(kp, ksp, cfg.compute_dtype)[None]
            v = dequantize_kv(vp, vsp, cfg.compute_dtype)[None]
        else:
            k = kp[None].astype(cfg.compute_dtype)
            v = vp[None].astype(cfg.compute_dtype)
        kidx = jnp.arange(k.shape[1])
        mask = (kidx[None, None, :] <= positions[None, :, None])

    kv_h = k.shape[2]
    qg = q.reshape(1, C, kv_h, h // kv_h, dh)
    out = _sdpa(qg, k, v, mask, cfg.logit_softcap)
    y = out.reshape(1, C, h * dh) @ p["wo"]
    if cfg.use_bias:
        y = y + p["bo"]
    return y, cache


# ======================================================================
# paged GQA decode (block-table cache; serving hot path)
# ======================================================================

def init_paged_gqa_cache(cfg: ModelConfig, n_pages: int, page_size: int):
    """One layer's page store: K/V for every slot live in shared pages."""
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    if cfg.kv_cache_dtype == "int8":
        return {"k": jnp.zeros((n_pages, page_size, kv, dh), jnp.int8),
                "v": jnp.zeros((n_pages, page_size, kv, dh), jnp.int8),
                "k_scale": jnp.zeros((n_pages, page_size, kv), jnp.float32),
                "v_scale": jnp.zeros((n_pages, page_size, kv), jnp.float32)}
    return {"k": jnp.zeros((n_pages, page_size, kv, dh), cfg.kv_dtype),
            "v": jnp.zeros((n_pages, page_size, kv, dh), cfg.kv_dtype)}


def _paged_cache_write(store, layer, new_k, new_v, positions, block_table,
                       live, quantized: bool):
    """Append one token per row of ``layer`` through the block table: one
    scatter per leaf of the stacked store at [layer, page, offset], which
    leaves every other layer's pages untouched.

    Rows whose table entry is unmapped (-1) and rows whose lane is dead
    (``live`` False) are redirected to the out-of-bounds page id ``P`` —
    JAX drops out-of-bounds scatter updates, so a dead lane can never
    corrupt a page that was re-allocated to another slot mid-block.
    """
    P, ps = store["k"].shape[1:3]
    entry = jnp.take_along_axis(block_table, (positions // ps)[:, None],
                                axis=1)[:, 0]
    page = jnp.where(entry >= 0, entry, P)
    if live is not None:
        page = jnp.where(live, page, P)
    off = positions % ps

    def wr(buf, val):  # buf (L, P, ps, ...), val (B, ...) one token per row
        return buf.at[layer, page, off].set(val.astype(buf.dtype))

    if quantized:
        qk, sk = quantize_kv(new_k)
        qv, sv = quantize_kv(new_v)
        return dict(store,
                    k=wr(store["k"], qk[:, 0]), v=wr(store["v"], qv[:, 0]),
                    k_scale=wr(store["k_scale"], sk[:, 0]),
                    v_scale=wr(store["v_scale"], sv[:, 0]))
    return dict(store, k=wr(store["k"], new_k[:, 0]),
                v=wr(store["v"], new_v[:, 0]))


def gqa_decode_paged(cfg: ModelConfig, p, x, positions, store, layer,
                     block_table, *, live=None, impl: str = "auto"):
    """Paged decode step of one layer: append the new token's K/V through
    the block table into the segment's stacked ``store`` at ``layer``, then
    attend over the slot's pages of that layer.

    x: (B, 1, d); positions: (B,) absolute position of the new token;
    store: leaves (L, P, page, ...); layer: scalar int32;
    block_table: (B, max_pages) int32 (-1 = unmapped); live: (B,) bool or
    None. ``impl`` picks the attention read: "pallas" /
    "pallas_interpret" force the kernel, "xla" the pure-jnp reference,
    "auto" resolves per backend (kernels/ops.py). Returns (y, store).
    """
    from repro.kernels import ops

    B = x.shape[0]
    h, dh = cfg.n_heads, cfg.head_dim
    quantized = cfg.kv_cache_dtype == "int8"
    q, k_new, v_new = _project_qkv(cfg, p, x, positions[:, None])
    with jax.named_scope("sprout.kv_write"):
        store = _paged_cache_write(store, layer, k_new, v_new, positions,
                                   block_table, live, quantized)
    # append-only pages: validity == index < length, causality is implicit
    lengths = positions + 1
    out = ops.paged_attention(
        q[:, 0], store["k"], store["v"], block_table, lengths, layer,
        store.get("k_scale"), store.get("v_scale"), impl=impl)
    y = out.reshape(B, 1, h * dh) @ p["wo"]
    if cfg.use_bias:
        y = y + p["bo"]
    return y, store


# ======================================================================
# MLA forward
# ======================================================================

def _mla_q(cfg: ModelConfig, p, x, positions):
    B, T = x.shape[:2]
    h, nope, rope = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank > 0:
        cq = apply_norm(cfg, p["q_norm"], x @ p["wq_a"])
        q = cq @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, T, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(cfg, q_rope, positions)
    return q_nope, q_rope


def mla_full(cfg: ModelConfig, p, x, positions, *, causal: bool = True):
    """train/prefill: materialize per-head K/V from the latent."""
    B, T = x.shape[:2]
    h, nope, rope, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(cfg, p, x, positions)

    kv_a = x @ p["wkv_a"]                              # (B,T,rank+rope)
    ckv = apply_norm(cfg, p["kv_norm"], kv_a[..., :rank])
    kr = apply_rope(cfg, kv_a[..., rank:], positions)  # shared across heads
    kv_b = (ckv @ p["wkv_b"]).reshape(B, T, h, nope + vd)
    k_nope, v = kv_b[..., :nope], kv_b[..., nope:]

    if cfg.attn_impl in ("blocked", "pallas"):
        # flash path: per-head K = [k_nope ; kr broadcast], heads as KV, G=1
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr[:, :, None], (B, T, h, rope))], -1)
        q_full = jnp.concatenate([q_nope, q_rope], -1).reshape(
            B, T, h, 1, nope + rope)
        qpos = jnp.broadcast_to(positions, (B, T)) if positions.ndim == 1 else positions
        out = flash_sdpa(q_full, k_full, v, qpos, qpos, causal=causal)
        out = out.reshape(B, T, h * vd)
    else:
        scale = 1.0 / math.sqrt(nope + rope)
        s = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope)
             + jnp.einsum("bthd,bsd->bhts", q_rope, kr)).astype(jnp.float32) * scale
        mask = _causal_window_mask(positions, positions, 0, causal)
        s = jnp.where(mask[:, None], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v)
        out = out.reshape(B, T, h * vd)
    y = out @ p["wo"]
    return y, (ckv, kr)


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int):
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cfg.kv_dtype),
        "kr": jnp.zeros((batch, max_len, cfg.qk_rope_dim), cfg.kv_dtype),
        "kpos": jnp.full((batch, max_len), EMPTY_POS, jnp.int32),
    }


def mla_decode(cfg: ModelConfig, p, x, positions, cache):
    """Absorbed decode: attention runs in the latent space (rank ≪ h·dh).

    This is the TPU-friendly analogue of DeepSeek's weight-absorbed MLA
    inference: K/V are never materialized per-head; the query is mapped into
    the latent via W_kv_b's K-half, context is read in the latent and mapped
    out via the V-half.
    """
    B = x.shape[0]
    h, nope, rope, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(cfg, p, x, positions[:, None])  # (B,1,h,·)

    kv_a = x @ p["wkv_a"]
    ckv_new = apply_norm(cfg, p["kv_norm"], kv_a[..., :rank])
    kr_new = apply_rope(cfg, kv_a[..., rank:], positions[:, None])
    S = cache["ckv"].shape[1]
    slot = jnp.minimum(positions, S - 1)
    wr = lambda b, v_, i: jax.vmap(lambda bb, vv, ii: bb.at[ii].set(vv))(b, v_, i)
    cache = dict(cache,
                 ckv=wr(cache["ckv"], ckv_new[:, 0].astype(cache["ckv"].dtype), slot),
                 kr=wr(cache["kr"], kr_new[:, 0].astype(cache["kr"].dtype), slot),
                 kpos=wr(cache["kpos"], positions, slot))

    wkv_b = p["wkv_b"].reshape(rank, h, nope + vd)
    w_k, w_v = wkv_b[..., :nope], wkv_b[..., nope:]
    # absorb: q_lat (B,h,rank)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_k)
    ckv = cache["ckv"].astype(cfg.compute_dtype)
    kr = cache["kr"].astype(cfg.compute_dtype)
    scale = 1.0 / math.sqrt(nope + rope)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv)
         + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0], kr)).astype(jnp.float32) * scale
    mask = ((cache["kpos"] <= positions[:, None])
            & (cache["kpos"] > EMPTY_POS // 2))[:, None]   # (B,1,S)
    s = jnp.where(mask, s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bhs,bsr->bhr", probs.astype(ckv.dtype), ckv)
    out = jnp.einsum("bhr,rhd->bhd", ctx_lat, w_v)       # (B,h,vd)
    y = out.reshape(B, 1, h * vd) @ p["wo"]
    return y, cache
