"""Operations and bytes a dense decoder step needs, from its shapes.

``m`` is the ``model`` block of a configuration file (ModelConfig field
names). Model FLOPs count a multiply-add as two operations and only the
work the tokens require: no padding rows, dead lanes or recomputation.
"""
from __future__ import annotations

import numpy as np


def head_dim(m: dict) -> int:
    return m.get("d_head") or m["d_model"] // m["n_heads"]


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one layer (q, k, v, o and
    the gated MLP's three matrices)."""
    d, h, kv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       head_dim(m), m["d_ff"])
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def param_count(m: dict) -> int:
    """All parameters: tied embedding, layers (two norms each), final norm."""
    d = m["d_model"]
    emb = m["vocab_size"] * d * (1 if m["tie_embeddings"] else 2)
    return emb + m["n_layers"] * (layer_matmul_params(m) + 2 * d) + d


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Key and value bytes one token holds across all layers."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * head_dim(m) * itemsize


def attn_flops_per_key(m: dict) -> int:
    """QK^T and PV for one query token against one cached key, all layers."""
    return 4 * m["n_layers"] * m["n_heads"] * head_dim(m)


def lm_head_flops(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def token_flops(m: dict, n_keys) -> np.ndarray:
    """Model FLOPs of one token through every layer attending ``n_keys``
    keys (itself included); LM head not counted."""
    n_keys = np.asarray(n_keys, np.float64)
    return (2.0 * m["n_layers"] * layer_matmul_params(m)
            + attn_flops_per_key(m) * n_keys)


def decode_flops(m: dict, n_keys) -> float:
    """Decode tokens, each with its LM head row, at the given key counts."""
    n_keys = np.asarray(n_keys, np.float64)
    return float(np.sum(token_flops(m, n_keys)) + lm_head_flops(m) * n_keys.size)


def prefill_flops(m: dict, positions, n_prompts_finished: int) -> float:
    """Prompt tokens at the given absolute positions (causal: position p
    attends p + 1 keys), plus one LM head row per prompt that finished."""
    positions = np.asarray(positions, np.float64)
    return float(np.sum(token_flops(m, positions + 1))
                 + lm_head_flops(m) * n_prompts_finished)


def paged_attn_bytes(m: dict, n_keys, itemsize: int = 2) -> float:
    """Least HBM bytes paged decode attention moves: every live key and
    value once, plus each query and output row, over all layers."""
    n_keys = np.asarray(n_keys, np.float64)
    q_out = m["n_layers"] * 2 * m["n_heads"] * head_dim(m) * itemsize
    return float(np.sum(n_keys) * kv_bytes_per_token(m, itemsize)
                 + q_out * n_keys.size)


def paged_attn_flops(m: dict, n_keys) -> float:
    return float(np.sum(np.asarray(n_keys, np.float64)) * attn_flops_per_key(m))
