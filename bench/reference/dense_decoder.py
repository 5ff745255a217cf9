"""Plain float32 reference of a dense pre-norm decoder (Llama-style).

Per layer: x += Wo . attn(RoPE(Wq h), RoPE(Wk h), Wv h), h = RMSNorm(x);
x += W_down (silu(W_gate h2) * W_up h2), h2 = RMSNorm(x); then a final
RMSNorm and the tied LM head (logits = h . E^T). Causal softmax attention
with grouped key/value heads, RoPE rotating the two halves of each head
(the Llama/NeoX convention), no biases. Computed one layer at a time from
the stored weights upcast to float32, at ``highest`` matmul precision.

It imports nothing of the system under test. The weights are read by the
names of the serving engine's parameter tree (its checkpoint format):
``embed.tok (V, d)``, ``segs[0].{ln1,ln2}.scale (L, d)``,
``segs[0].attn.{wq,wk,wv,wo}``, ``segs[0].mlp.{w_gate,w_up,w_down}``
(stacked on a leading layer axis) and ``norm.scale (d,)``.

``quant="fp8"`` is the lower-precision control: every weight matmul runs
on operands rounded to float8 e4m3 (per-row scales for activations,
per-column for weights), the step below the bfloat16 the configurations
serve in.

The weights are used as they are placed: on a tensor-parallel cell they
stay sharded over its chips, and the jitted layer and head follow their
shardings. Memory: one layer's weights upcast to float32 at a time, and
the head upcasts the whole embedding, ``vocab x d_model x 4`` bytes
(granite_3_2b 0.40 GB, minicpm_2b 1.13 GB; divided over the chips that
share the embedding's vocabulary rows).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _fake_fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, w, quant):
    if quant == "fp8":
        a, w = _fake_fp8(a, -1), _fake_fp8(w, 0)
    return a @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[:, None].astype(F32) * inv
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, seg, l, dims, quant):
    H, KV, D, eps, theta = dims
    w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, l, keepdims=False).astype(F32), seg)
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, w["ln1"]["scale"], eps)
    q = _rope(_mm(h, w["attn"]["wq"], quant).reshape(T, H, D), pos, theta)
    k = _rope(_mm(h, w["attn"]["wk"], quant).reshape(T, KV, D), pos, theta)
    v = _mm(h, w["attn"]["wv"], quant).reshape(T, KV, D)
    q = q.reshape(T, KV, H // KV, D)
    s = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(D)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
    x = x + _mm(o.reshape(T, H * D), w["attn"]["wo"], quant)
    h2 = _rms(x, w["ln2"]["scale"], eps)
    m = w["mlp"]
    y = jax.nn.silu(_mm(h2, m["w_gate"], quant)) * _mm(h2, m["w_up"], quant)
    return x + _mm(y, m["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, rows, norm, tok, eps, quant):
    h = _rms(x[rows], norm.astype(F32), eps)
    return _mm(h, tok.astype(F32).T, quant)


class DenseDecoder:
    """``m`` is a configuration's ``model`` block; ``params`` the weights."""

    def __init__(self, m: dict, params, quant: str = ""):
        assert not m.get("use_bias") and m.get("tie_embeddings"), m
        d_head = m.get("d_head") or m["d_model"] // m["n_heads"]
        self.dims = (m["n_heads"], m["n_kv_heads"], d_head,
                     float(m.get("norm_eps", 1e-6)),
                     float(m.get("rope_theta", 10000.0)))
        self.n_layers = m["n_layers"]
        self.params = params
        self.quant = quant

    def logits(self, tokens, rows) -> np.ndarray:
        """float32 logits (len(rows), V) of the sequence ``tokens`` at the
        positions ``rows``; causal, so padding past them changes nothing."""
        tokens = np.asarray(tokens, np.int32)
        T = max(256, 1 << (len(tokens) - 1).bit_length())
        padded = np.zeros(T, np.int32)
        padded[: len(tokens)] = tokens
        tok = self.params["embed"]["tok"]
        seg = self.params["segs"][0]
        with jax.default_matmul_precision("highest"):
            x = jnp.take(tok, jnp.asarray(padded), axis=0).astype(F32)
            for l in range(self.n_layers):
                x = _layer(x, seg, l, self.dims, self.quant)
            out = _head(x, jnp.asarray(rows, jnp.int32),
                        self.params["norm"]["scale"], tok, self.dims[3],
                        self.quant)
        return np.asarray(out)


Reference = DenseDecoder
