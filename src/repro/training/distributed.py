"""Distributed-optimization tricks for cross-pod training at scale.

These compose as ``grad_transform`` hooks inside the (shard_mapped) train
step — each is a pure function of gradients + mesh axis names:

* ``bucketed_psum``       — flatten grads into ~bucket_bytes buckets; one
  collective per bucket instead of per tensor. Buckets are issued in layer
  order so on hardware each all-reduce overlaps the next bucket's backward
  compute (XLA latency-hiding scheduler handles the interleave; bucket size
  is the overlap granularity knob).
* ``compressed_psum``     — int8-on-the-wire cross-pod all-reduce:
  reduce-scatter int8 chunks (all_to_all) -> local fp32 sum -> requantize ->
  all_gather int8. Wire bytes drop 4x vs fp32; per-chunk fp32 scales ride
  along (amortized, <1%). This is what shrinks the collective roofline term
  on the slow cross-pod (DCI) axis.
* ``periodic_sync``       — local-SGD style: sync every k steps (lax.cond),
  trading staleness for a k-fold cut in cross-pod traffic.

Composition used by the launcher: fast in-pod axes always run fp32
``bucketed_psum``; the slow cross-pod axis runs compressed and/or periodic.
"""
from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp

import numpy as np

PyTree = Any


# ----------------------------------------------------------------------
# bucketing
# ----------------------------------------------------------------------

def _bucket_layout(tree: PyTree, bucket_bytes: int):
    leaves, tdef = jax.tree.flatten(tree)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    buckets: List[List[int]] = [[]]
    acc = 0
    for i, s in enumerate(sizes):
        if acc + s * 4 > bucket_bytes and buckets[-1]:
            buckets.append([])
            acc = 0
        buckets[-1].append(i)
        acc += s * 4
    return leaves, tdef, sizes, buckets


def bucketed_psum(tree: PyTree, axis_name: str,
                  bucket_bytes: int = 4 << 20) -> PyTree:
    """One psum per ~bucket_bytes of gradients (issued in layer order)."""
    leaves, tdef, sizes, buckets = _bucket_layout(tree, bucket_bytes)
    out: List[Optional[jnp.ndarray]] = [None] * len(leaves)
    for idxs in buckets:
        flat = jnp.concatenate([leaves[i].reshape(-1).astype(jnp.float32)
                                for i in idxs])
        red = jax.lax.psum(flat, axis_name)
        off = 0
        for i in idxs:
            out[i] = red[off: off + sizes[i]].reshape(leaves[i].shape
                                                      ).astype(leaves[i].dtype)
            off += sizes[i]
    return jax.tree.unflatten(tdef, out)


# ----------------------------------------------------------------------
# int8-on-the-wire all-reduce
# ----------------------------------------------------------------------

def _quantize_chunks(x: jnp.ndarray, n: int):
    """x: (L,) fp32 -> int8 (n, L/n) + per-chunk scales (n,)."""
    xc = x.reshape(n, -1)
    amax = jnp.max(jnp.abs(xc), axis=1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xc / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum(tree: PyTree, axis_name: str,
                    bucket_bytes: int = 4 << 20) -> PyTree:
    """All-reduce with int8 wire format (reduce-scatter + all-gather).

    Each device quantizes its bucket into N chunks (N = axis size), sends
    chunk j to device j (all_to_all, int8), locally dequantizes + sums its
    owned chunk in fp32, requantizes, and all-gathers the int8 result.
    """
    n = jax.lax.axis_size(axis_name)
    leaves, tdef, sizes, buckets = _bucket_layout(tree, bucket_bytes)
    out: List[Optional[jnp.ndarray]] = [None] * len(leaves)
    for idxs in buckets:
        flat = jnp.concatenate([leaves[i].reshape(-1).astype(jnp.float32)
                                for i in idxs])
        L = flat.shape[0]
        pad = (-L) % n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        q, scale = _quantize_chunks(flat, n)              # (n, C) int8
        # reduce-scatter: device j receives everyone's chunk j
        qt = jax.lax.all_to_all(q[:, None], axis_name, 0, 1,
                                tiled=False)              # (1, n, C)
        st = jax.lax.all_gather(scale, axis_name)          # (n, n)
        mine = jnp.sum(qt[0].astype(jnp.float32)
                       * st[:, jax.lax.axis_index(axis_name)][:, None], axis=0)
        # requantize my reduced chunk, all-gather int8 + scales
        amax = jnp.maximum(jnp.max(jnp.abs(mine)), 1e-12)
        s2 = amax / 127.0
        q2 = jnp.clip(jnp.round(mine / s2), -127, 127).astype(jnp.int8)
        qg = jax.lax.all_gather(q2, axis_name)             # (n, C) int8 wire
        sg = jax.lax.all_gather(s2, axis_name)             # (n,)
        red = (qg.astype(jnp.float32) * sg[:, None]).reshape(-1)[:L]
        off = 0
        for i in idxs:
            out[i] = red[off: off + sizes[i]].reshape(leaves[i].shape
                                                      ).astype(leaves[i].dtype)
            off += sizes[i]
    return jax.tree.unflatten(tdef, out)


# ----------------------------------------------------------------------
# periodic (local-SGD) sync
# ----------------------------------------------------------------------

def periodic_sync(tree: PyTree, axis_name: str, step, every: int,
                  sync_fn=None) -> PyTree:
    """Cross-axis sync only when step % every == 0; otherwise local grads.
    (Bounded-staleness local SGD; cross-pod traffic / every.)"""
    sync = sync_fn or (lambda t: bucketed_psum(t, axis_name))
    do = (step % every) == 0

    def mean_branch(t):
        n = jax.lax.axis_size(axis_name)
        return jax.tree.map(lambda x: x / n, sync(t))

    return jax.lax.cond(do, mean_branch, lambda t: t, tree)


def pmean(tree: PyTree, axis_name: str) -> PyTree:
    n = jax.lax.axis_size(axis_name)
    return jax.tree.map(lambda x: x / n, bucketed_psum(tree, axis_name))
