"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively; on CPU (this container) they execute
in interpret mode, which is how the tests validate them. ``auto_interpret``
resolves that per backend so callers never pass the flag.

``SPROUT_KERNEL_IMPL`` overrides the "auto" resolution fleet-wide (e.g.
``SPROUT_KERNEL_IMPL=pallas_interpret`` forces the real kernel semantics
through the interpreter on CPU — the CI ``kernels-interpret`` job runs the
pallas suites this way so kernel parity is exercised on CPU runners, not
just the XLA reference path). An explicit ``impl=`` argument always wins;
the env var only redirects callers that asked for "auto".
"""
from __future__ import annotations

import functools
import os

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.rmsnorm import fused_rmsnorm as _rmsnorm

_IMPLS = ("xla", "pallas", "pallas_interpret")


def auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_impl(impl: str = "auto") -> str:
    """Resolve an ``impl`` request to a concrete backend: explicit wins,
    then the ``SPROUT_KERNEL_IMPL`` env override, then per-backend auto
    (native kernel on TPU, XLA reference elsewhere)."""
    if impl != "auto":
        return impl
    env = os.environ.get("SPROUT_KERNEL_IMPL", "").strip()
    if env:
        if env not in _IMPLS:
            raise ValueError(
                f"SPROUT_KERNEL_IMPL={env!r} not in {_IMPLS}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128):
    """q (B,H,Tq,D), k/v (B,KVH,Tk,D) -> (B,H,Tq,D). Pads to block size."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    pq = (-Tq) % bq
    pk = (-Tk) % bk
    if pq or pk:
        # padded keys are masked off by causality only when Tq==Tk; for
        # robustness fall back to the reference on ragged shapes.
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash(q, k, v, causal=causal, window=window, block_q=bq,
                  block_k=bk, interpret=auto_interpret())


def paged_attention(q, k_pages, v_pages, block_table, lengths, layer,
                    k_scale=None, v_scale=None, *, impl: str = "auto"):
    """Decode attention over one layer of a layer-stacked block-table
    cache; see paged_attention.py.

    ``impl``: "auto" (kernel on TPU, reference elsewhere), "pallas"
    (native lowering), "pallas_interpret" (kernel semantics on CPU — how
    the tier-1 tests exercise the real kernel), or "xla" (the pure-jnp
    ``kernels/ref.py`` oracle, the serving engine's CPU fast path).

    Under a context mesh with a ``model`` axis (a tensor-parallel engine
    traces its programs inside ``jax.set_mesh``) the kernel runs per chip
    under ``jax.shard_map``: GSPMD cannot partition a Mosaic kernel. Each
    chip attends with its slice of KV heads and their query groups; where
    the KV heads do not divide the axis, every chip attends over all heads.
    """
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.paged_attention_ref(q, k_pages, v_pages, block_table,
                                       lengths, layer, k_scale, v_scale)
    assert impl in ("pallas", "pallas_interpret"), impl
    kernel = functools.partial(_paged, interpret=impl == "pallas_interpret")
    scales = () if k_scale is None else (k_scale, v_scale)
    mesh = jax.sharding.get_abstract_mesh()
    tp = 1 if mesh.empty else mesh.shape.get("model", 1)
    if tp == 1:
        return kernel(q, k_pages, v_pages, block_table, lengths, layer,
                      *scales)
    heads = "model" if k_pages.shape[3] % tp == 0 else None
    q_spec, kv_spec = P(None, heads, None), P(None, None, None, heads, None)
    sc_spec = P(None, None, None, heads)
    return jax.shard_map(
        kernel, mesh=mesh, check_vma=False, out_specs=q_spec,
        in_specs=(q_spec, kv_spec, kv_spec, P(), P(), P())
        + (sc_spec,) * len(scales),
    )(q, k_pages, v_pages, block_table, lengths, layer, *scales)


def fused_rmsnorm(x, scale, residual=None, *, eps: float = 1e-6):
    """(N,d) fused residual+RMSNorm; falls back to ref on ragged rows."""
    N = x.shape[0]
    block = 256 if N % 256 == 0 else (N if N <= 1024 else 0)
    if block == 0 or N % block:
        return ref.fused_rmsnorm_ref(x, scale, residual, eps)
    return _rmsnorm(x, scale, residual, block_rows=block, eps=eps,
                    interpret=auto_interpret())
