"""Unified model composition: init / train / prefill / decode for every family.

A model is a stack of *segments* (contiguous runs of identical block kinds,
``cfg.segments()``); each segment's layer parameters are stacked on axis 0
and executed with ``lax.scan`` so a 61-layer model compiles like a 1-layer
model. Three modes share one parameter layout:

  * ``train``   — full sequence -> logits (B, S, V), aux metrics
  * ``prefill`` — full sequence -> logits + decode caches
  * ``decode``  — one token per row against per-segment caches

Families:
  dense   : [norm->attn] + [norm->mlp]                     (granite, minicpm,
            command-r-plus, starcoder2, internvl2 backbone, llama2)
  moe     : attention (GQA or MLA) + MoE FFN (+ shared)    (deepseek-v3, kimi)
  hybrid  : parallel GQA-attention and Mamba-SSM heads     (hymba)
  ssm     : mLSTM / sLSTM blocks per ``block_pattern``     (xlstm)
  encdec  : bidirectional encoder + cross-attending decoder (whisper; the
            audio conv frontend is a STUB — inputs are frame embeddings)
  vlm     : dense decoder over [patch embeds ; token embeds] (internvl2; the
            ViT frontend is a STUB — inputs are patch embeddings)
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X
from repro.models.common import ModelConfig, KeyGen
from repro.models.layers import (add_positional, apply_mlp, apply_norm,
                                 embed_tokens, init_embeddings, init_mlp,
                                 init_norm, sinusoidal_pos, unembed)
from repro.models.shard_hints import hint

PyTree = Any


# ======================================================================
# per-kind layer init
# ======================================================================

def init_layer(cfg: ModelConfig, kind: str, key) -> PyTree:
    kg = KeyGen(key)
    if kind == "dense":
        return {"ln1": init_norm(cfg, kg()), "attn": A.init_attention(cfg, kg()),
                "ln2": init_norm(cfg, kg()), "mlp": init_mlp(cfg, kg())}
    if kind == "moe":
        return {"ln1": init_norm(cfg, kg()), "attn": A.init_attention(cfg, kg()),
                "ln2": init_norm(cfg, kg()), "moe": M.init_moe(cfg, kg())}
    if kind in ("hyb_local", "hyb_full"):
        return {"ln1": init_norm(cfg, kg()), "attn": A.init_attention(cfg, kg()),
                "ssm": S.init_ssm(cfg, kg()),
                "no_a": init_norm(cfg, kg()), "no_s": init_norm(cfg, kg()),
                "ln2": init_norm(cfg, kg()), "mlp": init_mlp(cfg, kg())}
    if kind == "mlstm":
        return {"ln1": init_norm(cfg, kg()), "cell": X.init_mlstm(cfg, kg())}
    if kind == "slstm":
        return {"ln1": init_norm(cfg, kg()), "cell": X.init_slstm(cfg, kg())}
    if kind == "enc":
        return {"ln1": init_norm(cfg, kg()), "attn": A.init_attention(cfg, kg()),
                "ln2": init_norm(cfg, kg()), "mlp": init_mlp(cfg, kg())}
    if kind == "xdec":
        return {"ln1": init_norm(cfg, kg()), "attn": A.init_attention(cfg, kg()),
                "lnx": init_norm(cfg, kg()),
                "xattn": A.init_attention(cfg, kg(), cross=True),
                "ln2": init_norm(cfg, kg()), "mlp": init_mlp(cfg, kg())}
    raise ValueError(f"unknown layer kind {kind!r}")


def _seg_kinds(cfg: ModelConfig) -> Tuple[Tuple[str, int], ...]:
    if cfg.family == "encdec":
        return (("xdec", cfg.n_layers),)
    return cfg.segments()


def init_model(cfg: ModelConfig, key) -> PyTree:
    kg = KeyGen(key)
    params: Dict[str, Any] = {"embed": init_embeddings(cfg, kg())}
    if cfg.family == "encdec":
        ekeys = jax.random.split(kg(), cfg.n_enc_layers)
        params["enc"] = {
            "layers": jax.vmap(lambda k: init_layer(cfg, "enc", k))(ekeys),
            "norm": init_norm(cfg, kg()),
        }
    segs = []
    for kind, n in _seg_kinds(cfg):
        keys = jax.random.split(kg(), n)
        segs.append(jax.vmap(lambda k, kind=kind: init_layer(cfg, kind, k))(keys))
    params["segs"] = segs
    params["norm"] = init_norm(cfg, kg())
    if cfg.mtp_depth > 0:
        eye = jnp.eye(cfg.d_model, dtype=cfg.compute_dtype)
        params["mtp"] = {
            "nh": init_norm(cfg, kg()), "ne": init_norm(cfg, kg()),
            "proj": jnp.concatenate([eye, eye * 0], axis=0),
            "block": init_layer(cfg, "dense" if cfg.n_experts == 0 else "moe", kg()),
            "norm": init_norm(cfg, kg()),
        }
    return params


# ======================================================================
# caches
# ======================================================================

def _window_for(cfg: ModelConfig, kind: str) -> int:
    if kind == "hyb_local":
        return cfg.sliding_window
    if kind == "dense" and cfg.sliding_window > 0:
        return cfg.sliding_window
    return 0


def _cache_len(cfg: ModelConfig, kind: str, max_len: int) -> int:
    w = _window_for(cfg, kind)
    return min(max_len, w) if w > 0 else max_len


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    if kind in ("dense", "moe"):
        if cfg.attn_type == "mla":
            return A.init_mla_cache(cfg, batch, max_len)
        return A.init_gqa_cache(cfg, batch, _cache_len(cfg, kind, max_len),
                                window=_window_for(cfg, kind))
    if kind in ("hyb_local", "hyb_full"):
        w = _window_for(cfg, kind)
        return {"attn": A.init_gqa_cache(cfg, batch, _cache_len(cfg, kind, max_len), window=w),
                "ssm": S.init_ssm_cache(cfg, batch)}
    if kind == "mlstm":
        return X.init_mlstm_cache(cfg, batch)
    if kind == "slstm":
        return X.init_slstm_cache(cfg, batch)
    if kind == "xdec":
        enc_s = cfg.enc_seq or 1
        kv, dh = cfg.n_kv_heads, cfg.head_dim
        return {"self": A.init_gqa_cache(cfg, batch, max_len),
                "xk": jnp.zeros((batch, enc_s, kv, dh), cfg.compute_dtype),
                "xv": jnp.zeros((batch, enc_s, kv, dh), cfg.compute_dtype)}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Per-segment stacked caches (axis 0 = layer within segment)."""
    caches = []
    for kind, n in _seg_kinds(cfg):
        one = lambda _, kind=kind: init_layer_cache(cfg, kind, batch, max_len)
        caches.append(jax.vmap(one)(jnp.arange(n)))
    return caches


def paged_supported(cfg: ModelConfig) -> bool:
    """Paged decode serves full-attention GQA stacks (dense / moe kinds);
    windowed, recurrent, latent (MLA) and cross-attending segments keep
    the dense per-slot cache path."""
    return (cfg.attn_type == "gqa"
            and all(kind in ("dense", "moe") and _window_for(cfg, kind) == 0
                    for kind, _ in _seg_kinds(cfg)))


def chunked_prefill_supported(cfg: ModelConfig) -> bool:
    """Chunked prefill (continuous batching) serves the same stacks as
    paged decode: full-attention GQA, dense / moe kinds. Windowed,
    recurrent, latent (MLA) and cross-attending segments keep whole-prompt
    prefill — their caches are not append-addressable per chunk."""
    return paged_supported(cfg)


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int):
    """Per-segment stacked page stores (axis 0 = layer within segment).

    Every layer owns its own pages; the block table is SHARED across
    layers (one page id maps the same slot/offset range in each layer's
    store), so the per-slot table stays small enough for the Pallas
    kernel's SMEM scalar prefetch.
    """
    assert paged_supported(cfg), \
        f"paged decode unsupported for {cfg.name} ({cfg.family}/{cfg.attn_type})"
    caches = []
    for _, n in _seg_kinds(cfg):
        one = lambda _: A.init_paged_gqa_cache(cfg, n_pages, page_size)
        caches.append(jax.vmap(one)(jnp.arange(n)))
    return caches


def _fill_gqa_cache(cfg: ModelConfig, cache, k, v, kpos, window: int = 0):
    """Write T contiguous tokens (positions 0..T-1) into a fresh cache."""
    T = k.shape[1]
    S = cache["k"].shape[1]
    if T > S:  # window cache shorter than the prompt: keep the last S tokens
        assert window > 0, \
            f"prompt ({T}) exceeds full-attention cache capacity ({S})"
        k, v, kpos = k[:, -S:], v[:, -S:], kpos[:, -S:]
    if cfg.kv_cache_dtype == "int8":
        qk, sk = A.quantize_kv(k)
        qv, sv = A.quantize_kv(v)
        cache = dict(cache,
                     k=jax.lax.dynamic_update_slice_in_dim(cache["k"], qk, 0, 1),
                     v=jax.lax.dynamic_update_slice_in_dim(cache["v"], qv, 0, 1),
                     k_scale=jax.lax.dynamic_update_slice_in_dim(cache["k_scale"], sk, 0, 1),
                     v_scale=jax.lax.dynamic_update_slice_in_dim(cache["v_scale"], sv, 0, 1))
    else:
        kd = k.astype(cache["k"].dtype)
        vd = v.astype(cache["v"].dtype)
        cache = dict(cache,
                     k=jax.lax.dynamic_update_slice_in_dim(cache["k"], kd, 0, 1),
                     v=jax.lax.dynamic_update_slice_in_dim(cache["v"], vd, 0, 1))
    cache["kpos"] = jax.lax.dynamic_update_slice_in_dim(cache["kpos"], kpos, 0, 1)
    return cache


# ======================================================================
# per-kind block application
# ======================================================================

def block_apply(cfg: ModelConfig, kind: str, p, x, positions, mode: str,
                cache, *, max_len: int = 0, lengths=None, enc_out=None,
                block_table=None, live=None, paged_impl: str = "auto",
                layer=None):
    """Returns (x_out, cache_out, aux).

    ``positions``: (B,S) for train/prefill, (B,) for decode.
    ``lengths``: (B,) valid lengths for ragged prefill.
    ``max_len``: decode-cache capacity to allocate at prefill.
    ``enc_out``: (B, enc_seq, d) encoder output for xdec train/prefill.
    ``block_table``: (B, max_pages) int32 — switches decode attention onto
    the paged path (``cache`` is then the segment's whole layer-stacked
    page store and ``layer`` this block's index in it);
    ``live``/``paged_impl`` predicate dead-lane page writes and pick the
    paged attention implementation.
    """
    aux: Dict[str, Any] = {}
    B = x.shape[0]
    window = _window_for(cfg, kind)

    def kpos_of(T):
        pos = positions if positions.ndim == 2 else jnp.broadcast_to(positions, (B, T))
        if lengths is not None:
            pos = jnp.where(jnp.arange(T)[None, :] < lengths[:, None], pos, A.EMPTY_POS)
        return pos

    # ---------------- recurrent kinds ---------------------------------
    if kind in ("mlstm", "slstm"):
        cell = X.mlstm_step if kind == "mlstm" else X.slstm_step
        scan = X.mlstm_scan if kind == "mlstm" else X.slstm_scan
        h = apply_norm(cfg, p["ln1"], x)
        if mode == "decode":
            y, c2 = cell(cfg, p["cell"], h, cache)
        elif mode == "prefill":
            y, c2 = scan(cfg, p["cell"], h, return_cache=True)
        else:
            y, c2 = scan(cfg, p["cell"], h), None
        return x + y, c2, aux

    # ---------------- attention-style kinds ---------------------------
    is_mla = cfg.attn_type == "mla" and kind in ("dense", "moe")
    h = apply_norm(cfg, p["ln1"], x)
    causal = kind != "enc"
    new_attn_cache = None
    with jax.named_scope("sprout.attention"):
        if mode == "decode":
            if block_table is not None:
                a, new_attn_cache = A.gqa_decode_paged(
                    cfg, p["attn"], h, positions, cache, layer, block_table,
                    live=live, impl=paged_impl)
            elif is_mla:
                a, new_attn_cache = A.mla_decode(cfg, p["attn"], h, positions, cache
                                                 if kind in ("dense", "moe") else cache["attn"])
            else:
                c_in = (cache["self"] if kind == "xdec"
                        else cache["attn"] if kind.startswith("hyb") else cache)
                a, new_attn_cache = A.gqa_decode(cfg, p["attn"], h, positions,
                                                 c_in, window=window)
        else:
            if is_mla:
                a, (ckv, kr) = A.mla_full(cfg, p["attn"], h, positions, causal=causal)
                if mode == "prefill":
                    T = h.shape[1]
                    c0 = A.init_mla_cache(cfg, B, max_len)
                    kp = kpos_of(T)
                    c0 = dict(c0,
                              ckv=jax.lax.dynamic_update_slice_in_dim(
                                  c0["ckv"], ckv.astype(c0["ckv"].dtype), 0, 1),
                              kr=jax.lax.dynamic_update_slice_in_dim(
                                  c0["kr"], kr.astype(c0["kr"].dtype), 0, 1),
                              kpos=jax.lax.dynamic_update_slice_in_dim(c0["kpos"], kp, 0, 1))
                    new_attn_cache = c0
            else:
                a, (k, v) = A.gqa_full(cfg, p["attn"], h, positions,
                                       causal=causal, window=window)
                if mode == "prefill":
                    T = h.shape[1]
                    c0 = A.init_gqa_cache(cfg, B, _cache_len(cfg, kind, max_len),
                                          window=window)
                    new_attn_cache = _fill_gqa_cache(cfg, c0, k, v, kpos_of(T),
                                                     window=window)

    # hybrid: parallel SSM branch, outputs fused via per-branch norms
    if kind.startswith("hyb"):
        if mode == "decode":
            s_out, new_ssm = S.ssm_step(cfg, p["ssm"], h, cache["ssm"])
        elif mode == "prefill":
            s_out, new_ssm = S.ssm_scan(cfg, p["ssm"], h, return_cache=True)
        else:
            s_out, new_ssm = S.ssm_scan(cfg, p["ssm"], h), None
        fused = 0.5 * (apply_norm(cfg, p["no_a"], a) + apply_norm(cfg, p["no_s"], s_out))
        fused = jax.ad_checkpoint.checkpoint_name(fused, "attn_out")
        x = x + fused
        new_cache = ({"attn": new_attn_cache, "ssm": new_ssm}
                     if mode != "train" else None)
    else:
        a = jax.ad_checkpoint.checkpoint_name(a, "attn_out")
        x = x + a
        new_cache = new_attn_cache

    # cross-attention (whisper decoder)
    if kind == "xdec":
        hx = apply_norm(cfg, p["lnx"], x)
        if mode == "decode":
            xa, _ = A.gqa_decode(cfg, p["xattn"], hx, positions, None,
                                 kv_override=(cache["xk"].astype(cfg.compute_dtype),
                                              cache["xv"].astype(cfg.compute_dtype)))
            new_cache = {"self": new_attn_cache, "xk": cache["xk"], "xv": cache["xv"]}
        else:
            kvh, dh = cfg.n_kv_heads, cfg.head_dim
            xk = (enc_out @ p["xattn"]["wk"]).reshape(B, -1, kvh, dh)
            xv = (enc_out @ p["xattn"]["wv"]).reshape(B, -1, kvh, dh)
            if cfg.use_bias:
                xk = xk + p["xattn"]["bk"].reshape(kvh, dh)
                xv = xv + p["xattn"]["bv"].reshape(kvh, dh)
            xa, _ = A.gqa_full(cfg, p["xattn"], hx, positions,
                               kv_override=(xk, xv))
            if mode == "prefill":
                new_cache = {"self": new_attn_cache,
                             "xk": xk.astype(cfg.compute_dtype),
                             "xv": xv.astype(cfg.compute_dtype)}
        x = x + xa

    # FFN
    with jax.named_scope("sprout.mlp"):
        h2 = apply_norm(cfg, p["ln2"], x)
        if kind == "moe":
            y, moe_aux = M.apply_moe(cfg, p["moe"], h2)
            aux.update(moe_aux)
        else:
            y = apply_mlp(cfg, p["mlp"], h2)
    y = jax.ad_checkpoint.checkpoint_name(y, "ffn_out")
    x = x + y
    return x, new_cache, aux


def block_apply_chunk(cfg: ModelConfig, kind: str, p, x, positions, valid,
                      lane, cache, *, block_table=None, layer=None):
    """One block over a single lane's prompt chunk (1, C, d). Attention
    appends the chunk's K/V to the lane's cache and attends causally over
    everything written so far; the FFN is position-wise as usual. Only
    dense / moe kinds reach here (``chunked_prefill_supported``). Paged:
    ``cache`` is the segment's stacked store and ``layer`` this block's
    index in it."""
    h = apply_norm(cfg, p["ln1"], x)
    with jax.named_scope("sprout.attention"):
        a, cache = A.gqa_chunk_append(cfg, p["attn"], h, positions, valid,
                                      lane, cache, block_table=block_table,
                                      layer=layer)
    x = x + a
    with jax.named_scope("sprout.mlp"):
        h2 = apply_norm(cfg, p["ln2"], x)
        if kind == "moe":
            y, _ = M.apply_moe(cfg, p["moe"], h2)
        else:
            y = apply_mlp(cfg, p["mlp"], h2)
    return x + y, cache


def prefill_chunk_step(cfg: ModelConfig, params, tokens, pos0, clen, lane,
                       cache, *, block_table=None):
    """Run ONE prompt chunk for one lane through the whole stack.

    tokens: (C,) int32 chunk token ids (pad beyond ``clen``); pos0: scalar
    absolute position of tokens[0]; clen: scalar valid length (0 = no-op
    step: every write is dropped); lane: scalar cache row / block-table
    row. ``cache`` is the engine's per-segment stacked cache (dense rows
    or paged stores). Returns (last_logits (V,), cache) — the logits at
    position pos0 + clen - 1, meaningful only on a request's final chunk,
    so the serving layer can sample the first token inside the same traced
    program.
    """
    C = tokens.shape[0]
    positions = pos0 + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(C) < clen
    x = embed_tokens(cfg, params["embed"], tokens[None])
    x = add_positional(cfg, params["embed"], x, positions[None])

    new_caches = []
    for i, (kind, n) in enumerate(_seg_kinds(cfg)):
        if block_table is not None:
            def paged(p, x, store, layer, kind=kind):
                x2, store = block_apply_chunk(
                    cfg, kind, p, x, positions, valid, lane, store,
                    block_table=block_table, layer=layer)
                return x2, store, {}

            x, c2, _ = _scan_paged_store(paged, x, params["segs"][i],
                                         cache[i])
        else:
            def body(x, per_layer, kind=kind):
                p, c = per_layer
                return block_apply_chunk(cfg, kind, p, x, positions, valid,
                                         lane, c)

            x, c2 = jax.lax.scan(body, x, (params["segs"][i], cache[i]))
        new_caches.append(c2)

    with jax.named_scope("sprout.lm_head"):
        x = apply_norm(cfg, params["norm"], x)
        last = jnp.clip(clen - 1, 0, C - 1)
        logits = unembed(cfg, params["embed"], x[:, last][:, None])[0, 0]
    return logits, new_caches


# ======================================================================
# segment scan
# ======================================================================

def _scan_paged_store(layer_fn, x, stacked_p, store):
    """Scan a segment's layers with its stacked page store in the carry.

    ``layer_fn(p, x, store, layer) -> (x, store, aux)`` runs one layer:
    it writes that layer's tokens into ``store`` at [layer, page, offset]
    and reads its pages through the layer index. The store is never sliced
    per layer nor restacked as a scan output, so the program updates the
    (donated) buffer in place. Returns (x, store, stacked aux).
    """
    n = jax.tree_util.tree_leaves(stacked_p)[0].shape[0]

    def body(carry, per_layer):
        x, store = carry
        p, layer = per_layer
        x, store, aux = layer_fn(p, x, store, layer)
        return (x, store), aux

    (x, store), auxs = jax.lax.scan(
        body, (x, store), (stacked_p, jnp.arange(n, dtype=jnp.int32)))
    return x, store, auxs


def _seg_apply(cfg: ModelConfig, kind: str, stacked_p, x, positions, mode: str,
               stacked_cache, max_len: int, lengths=None, enc_out=None,
               block_table=None, live=None, paged_impl: str = "auto"):
    """Scan one segment. Returns (x, new_stacked_cache, stacked_aux).

    Paged decode (``block_table`` given) carries the segment's stacked page
    store through the layer scan (``_scan_paged_store``): each layer writes
    its token at [layer, page, offset] and the kernel reads that layer's
    pages by index; it always scans, whatever ``cfg.scan_layers`` says.
    ``block_table``/``live`` are shared across the layers (scan
    constants): one page id addresses the same slot range in every layer.
    Every other cache is scanned per layer as ``xs`` and restacked as
    ``ys``.
    """
    if block_table is not None:
        def paged(p, x, store, layer):
            return block_apply(cfg, kind, p, x, positions, mode, store,
                               block_table=block_table, live=live,
                               paged_impl=paged_impl, layer=layer)

        return _scan_paged_store(paged, x, stacked_p, stacked_cache)

    def body(x, per_layer):
        if mode == "decode":
            p, c = per_layer
        else:
            p, c = per_layer, None
        x2, c2, aux = block_apply(cfg, kind, p, x, positions, mode, c,
                                  max_len=max_len, lengths=lengths,
                                  enc_out=enc_out)
        return x2, (c2, aux)

    if cfg.remat != "none" and mode == "train":
        if cfg.remat == "selective":
            # save ONLY the named per-layer outputs — the post-TP-all-reduce
            # tensors — so backward recompute re-runs neither the collectives
            # nor the big matmuls, at 2 extra (B,S,d) saves per layer
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "ffn_out")
        else:
            policy = None
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)

    if not cfg.scan_layers:
        n = jax.tree_util.tree_leaves(stacked_p)[0].shape[0]
        caches, auxs = [], []
        for i in range(n):
            p_i = jax.tree.map(lambda a: a[i], stacked_p)
            c_i = (jax.tree.map(lambda a: a[i], stacked_cache)
                   if mode == "decode" else None)
            x, (c2, aux) = body(x, (p_i, c_i) if mode == "decode" else p_i)
            caches.append(c2)
            auxs.append(aux)
        stack = lambda *xs: jnp.stack(xs)
        caches = jax.tree.map(stack, *caches) if caches[0] is not None else None
        auxs = jax.tree.map(stack, *auxs) if auxs and auxs[0] else {}
        return x, (caches if mode != "train" else None), auxs

    if mode == "decode":
        x, (caches, auxs) = jax.lax.scan(body, x, (stacked_p, stacked_cache))
        return x, caches, auxs
    x, (caches, auxs) = jax.lax.scan(body, x, stacked_p)
    return x, (caches if mode == "prefill" else None), auxs


# ======================================================================
# encoder (whisper)
# ======================================================================

def encode(cfg: ModelConfig, params, frames):
    """frames: (B, enc_seq, d_model) stub frontend embeddings."""
    B, Se, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(Se), (B, Se))
    x = frames + sinusoidal_pos(pos, cfg.d_model).astype(frames.dtype)

    def body(x, p):
        x2, _, _ = block_apply(cfg, "enc", p, x, pos, "train", None)
        return x2, None

    x, _ = jax.lax.scan(body, x, params["enc"]["layers"])
    return apply_norm(cfg, params["enc"]["norm"], x)


# ======================================================================
# top-level forward
# ======================================================================

def _mean_aux(auxs_list):
    out: Dict[str, Any] = {}
    for auxs in auxs_list:
        for k, v in auxs.items():
            out.setdefault(k, []).append(jnp.mean(v))
    return {k: jnp.mean(jnp.stack(v)) for k, v in out.items()}


def forward(cfg: ModelConfig, params, tokens, *, mode: str = "train",
            positions=None, lengths=None, cache=None, max_len: int = 0,
            frames=None, patches=None, return_hidden: bool = False,
            block_table=None, live=None, paged_impl: str = "auto"):
    """``tokens``: (B,S) int32 (decode: (B,1));
    ``positions``: decode (B,), else (B,S) or None (=arange).
    ``max_len``: cache capacity for prefill.
    ``block_table`` (decode only): (B, max_pages) int32 routes attention
    through the paged path — ``cache`` must then be ``init_paged_cache``
    output; ``live`` (B,) bool predicates dead-lane page writes. Returns:
      train  -> (logits, aux)
      prefill-> (logits, caches, aux)
      decode -> (logits (B,V), caches)
    """
    B, T = tokens.shape
    x = embed_tokens(cfg, params["embed"], tokens)
    x = hint(x, "act_embed")

    if cfg.family == "vlm" and patches is not None and mode != "decode":
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
        T = x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    if mode != "decode":
        x = add_positional(cfg, params["embed"], x, positions)
    else:
        x = add_positional(cfg, params["embed"], x, positions[:, None])[:, 0][:, None] \
            if cfg.pos == "learned" else x

    enc_out = None
    if cfg.family == "encdec" and mode != "decode":
        enc_out = encode(cfg, params, frames)

    seg_defs = _seg_kinds(cfg)
    new_caches = []
    auxs_list = []
    for i, (kind, n) in enumerate(seg_defs):
        seg_p = params["segs"][i]
        seg_c = cache[i] if cache is not None else None
        x, c2, auxs = _seg_apply(cfg, kind, seg_p, x, positions, mode,
                                 seg_c, max_len, lengths=lengths,
                                 enc_out=enc_out, block_table=block_table,
                                 live=live, paged_impl=paged_impl)
        new_caches.append(c2)
        auxs_list.append(auxs)
        x = hint(x, "act_resid")

    with jax.named_scope("sprout.lm_head"):
        x = apply_norm(cfg, params["norm"], x)
        hidden = x
        logits = unembed(cfg, params["embed"], x)
        logits = hint(logits, "act_logits")
    aux = _mean_aux(auxs_list)
    if return_hidden:
        aux["hidden"] = hidden

    if mode == "train":
        return logits, aux
    if mode == "prefill":
        return logits, new_caches, aux
    return logits[:, 0], new_caches


# ======================================================================
# losses / steps
# ======================================================================

def cross_entropy(logits, labels, ignore_label: int = -100):
    """logits (B,S,V) any dtype; labels (B,S) int. Mean over valid tokens."""
    mask = labels != ignore_label
    labels_safe = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels_safe[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)


def _mtp_loss(cfg: ModelConfig, params, hidden, tokens, labels):
    """DeepSeek-V3 multi-token prediction, depth 1: predict t+2 from
    Block(W [norm(h_t); norm(Emb(token_{t+1}))])."""
    p = params["mtp"]
    B, T = tokens.shape
    nxt = jnp.concatenate([tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], 1)
    e = embed_tokens(cfg, params["embed"], nxt)
    h = jnp.concatenate([apply_norm(cfg, p["nh"], hidden),
                         apply_norm(cfg, p["ne"], e)], -1) @ p["proj"]
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    kind = "dense" if cfg.n_experts == 0 else "moe"
    h, _, _ = block_apply(cfg, kind, p["block"], h, pos, "train", None)
    h = apply_norm(cfg, p["norm"], h)
    logits = unembed(cfg, params["embed"], h)
    lab2 = jnp.concatenate([labels[:, 1:],
                            jnp.full((B, 1), -100, labels.dtype)], 1)
    return cross_entropy(logits, lab2)


def loss_fn(cfg: ModelConfig, params, batch, *, aux_weight: float = 1e-2,
            mtp_weight: float = 0.3):
    """batch: {tokens, labels[, frames, patches]}. Returns (loss, metrics)."""
    patches = batch.get("patches")
    frames = batch.get("frames")
    need_hidden = cfg.mtp_depth > 0
    logits, aux = forward(cfg, params, batch["tokens"], mode="train",
                          frames=frames, patches=patches,
                          return_hidden=need_hidden)
    labels = batch["labels"]
    if cfg.family == "vlm" and patches is not None:
        npat = patches.shape[1]
        pad = jnp.full(labels.shape[:1] + (npat,), -100, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    loss = cross_entropy(logits, labels)
    metrics = {"ce": loss}
    if "moe_aux_loss" in aux:
        loss = loss + aux_weight * aux["moe_aux_loss"]
        metrics["moe_aux_loss"] = aux["moe_aux_loss"]
        metrics["moe_drop_frac"] = aux["moe_drop_frac"]
    if cfg.mtp_depth > 0:
        ml = _mtp_loss(cfg, params, aux["hidden"], batch["tokens"], batch["labels"])
        loss = loss + mtp_weight * ml
        metrics["mtp_ce"] = ml
    metrics["loss"] = loss
    return loss, metrics


def prefill(cfg: ModelConfig, params, tokens, *, max_len: int, lengths=None,
            frames=None, patches=None):
    return forward(cfg, params, tokens, mode="prefill", max_len=max_len,
                   lengths=lengths, frames=frames, patches=patches)


def decode_step(cfg: ModelConfig, params, tokens, positions, cache):
    """tokens: (B,1); positions: (B,). Returns (logits (B,V), cache)."""
    return forward(cfg, params, tokens, mode="decode", positions=positions,
                   cache=cache)


def decode_sample_step(cfg: ModelConfig, params, tokens, positions, cache,
                       key, sampling, sample_fn, *, block_table=None,
                       live=None, paged_impl: str = "auto", fold_ids=None,
                       with_ok: bool = False):
    """One decode step with sampling fused into the same traced program.

    ``sampling`` is a tuple of stacked per-row arrays
    ``(temperature (B,) f32, top_k (B,) i32, top_p (B,) f32)`` and
    ``sample_fn(logits, key, *sampling) -> (B,) int32`` performs the draw
    (the serving layer passes ``sampler.sample_logits_batched``; injected
    as a callable so models/ stays import-independent of serving/).
    With ``block_table`` the step reads/writes the paged KV store instead
    of per-slot linear regions (``live`` gates dead-lane page writes).
    ``fold_ids`` (B,) int32 overrides the sampler's per-row PRNG fold so a
    batch-bucketed caller can fold by slot id instead of lane position.
    Returns (next_tokens (B,) int32, cache) — logits never leave the
    program, so a jitted caller pays no host transfer per token.

    ``with_ok=True`` additionally returns a per-row finiteness verdict
    ``ok (B,) bool = isfinite(logits).all(-1)`` so the serving engine can
    detect a poisoned lane (NaN/Inf logits from corrupted KV or a kernel
    fault) *inside* the fused program — the verdict rides the caller's
    existing per-block fetch, adding no host sync of its own.
    """
    logits, cache = forward(cfg, params, tokens, mode="decode",
                            positions=positions, cache=cache,
                            block_table=block_table, live=live,
                            paged_impl=paged_impl)
    toks = sample_fn(logits, key, *sampling, fold_ids=fold_ids)
    if with_ok:
        return toks, cache, jnp.isfinite(logits).all(axis=-1)
    return toks, cache
