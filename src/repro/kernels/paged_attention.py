"""Pallas TPU paged decode-attention kernel (block-table KV cache).

TPU adaptation of vLLM's PagedAttention (DESIGN.md §3): pages are 128–256
tokens (HBM->VMEM DMA wants wide contiguous lanes, unlike GPU's 16-token
pages), and the per-slot page list is delivered through *scalar prefetch*
(``PrefetchScalarGridSpec``) so the page index feeds each grid step's
BlockSpec index_map — the TPU analogue of the GPU kernel's pointer chase,
resolved at DMA-issue time from SMEM.

Grid: (batch, page). The kernel reads the layer-stacked store the model
carries through its layer scan: the layer index is a third scalar-prefetch
operand, so one grid step DMAs one whole page of one layer — every KV head —
as a ``(1, 1, page, KVH, D)`` block, whose last two dims equal the store's,
as the TPU tiling rule requires; no per-layer slice of the store is ever
materialized. A static loop over the KV heads then runs the online softmax
for each head's query group. fp32 (m, l, acc) scratch is ``(KVH, G, 1|D)``,
so no scratch array is 1-D, and persists across the page sweep. Pages past
the slot's length are predicated off with ``pl.when`` (no FLOPs), and their
index_map repeats the slot's last live page, so the pipeline issues no DMA
for them either.
Supports an int8-quantized cache via per-token-per-head scales
(``(1, 1, page, KVH)`` blocks), dequantized in VMEM after the DMA (halves
decode HBM traffic — the memory-roofline win).

Layouts: q (B, H, D); k/v store (L, P, page, KVH, D), scales (L, P, page,
KVH); layer a scalar int32 -> out (B, H, D).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, ks_ref,
                  vs_ref, o_ref, m_scr, l_scr, acc_scr, *, page: int,
                  n_pages: int, n_kv: int, sm_scale: float, quantized: bool):
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(pi * page < length)
    def _body():
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32)           # (G, D)
            k = k_ref[0, 0, :, h, :].astype(jnp.float32)  # (page, D)
            v = v_ref[0, 0, :, h, :].astype(jnp.float32)
            if quantized:
                k = k * ks_ref[0, 0, :, h:h + 1]
                v = v * vs_ref[0, 0, :, h:h + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * sm_scale                               # (G, page)
            pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = pos < length
            s = jnp.where(valid, s, NEG_INF)
            # this page holds at least one valid position, so m_new is
            # finite; the first page's corr is exp(NEG_INF - m_new) = 0
            m_prev = m_scr[h]                              # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(pi == n_pages - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_table, lengths, layer,
                    k_scale=None, v_scale=None, *, interpret: bool = False):
    """Decode attention over one layer of a stacked page store. q: (B, H, D);
    store (L, P, page, KVH, D); block_table (B, max_pages) int32; lengths
    (B,); layer: scalar int32. Returns (B, H, D)."""
    B, H, D = q.shape
    _, P, page, KVH, _ = k_pages.shape
    max_pages = block_table.shape[1]
    group = H // KVH
    quantized = k_scale is not None
    if not quantized:
        # one dummy scale block keeps one kernel signature; its index is
        # constant, so it is fetched once per call
        k_scale = v_scale = jnp.ones((1, 1, page, KVH), jnp.float32)

    # q reorganized to (B, KVH, G, D): one block holds a slot's every head
    qr = q.reshape(B, KVH, group, D)

    def page_of(b, pi, bt, ln):
        # steps past the slot's last live page revisit that page: the
        # block index does not change, so the pipeline skips the DMA
        last = jnp.maximum(ln[b] - 1, 0) // page
        return bt[b, jnp.minimum(pi, last)]

    def q_map(b, pi, bt, ln, ly):
        return (b, 0, 0, 0)

    def kv_map(b, pi, bt, ln, ly):
        return (ly[0], page_of(b, pi, bt, ln), 0, 0, 0)

    def sc_map(b, pi, bt, ln, ly):
        return ((ly[0], page_of(b, pi, bt, ln), 0, 0) if quantized
                else (0, 0, 0, 0))

    kernel = functools.partial(
        _paged_kernel, page=page, n_pages=max_pages, n_kv=KVH,
        sm_scale=1.0 / math.sqrt(D), quantized=quantized)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, KVH, group, D), q_map),
            pl.BlockSpec((1, 1, page, KVH, D), kv_map),
            pl.BlockSpec((1, 1, page, KVH, D), kv_map),
            pl.BlockSpec((1, 1, page, KVH), sc_map),
            pl.BlockSpec((1, 1, page, KVH), sc_map),
        ],
        out_specs=pl.BlockSpec((1, KVH, group, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((KVH, group, 1), jnp.float32),
            pltpu.VMEM((KVH, group, 1), jnp.float32),
            pltpu.VMEM((KVH, group, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, group, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_attention",
        interpret=interpret,
    )(jnp.clip(block_table, 0, P - 1), lengths.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      qr, k_pages, v_pages, k_scale, v_scale)
    return out.reshape(B, H, D)
