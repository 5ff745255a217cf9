"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

pytest.importorskip("jax.experimental.pallas",
                    reason="Pallas unavailable in this jax build")
pytestmark = pytest.mark.pallas

from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rmsnorm import fused_rmsnorm
from repro.kernels import ref
from repro.kernels import ops
from repro.models.attention import quantize_kv


def _qkv(key, B, H, KVH, T, D, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, T, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, KVH, T, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, KVH, T, D)).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("B,H,KVH,T,D,causal,window", [
    (2, 4, 2, 256, 64, True, 0),
    (1, 2, 2, 128, 32, False, 0),
    (1, 4, 1, 256, 64, True, 96),
    (2, 8, 8, 128, 128, True, 0),
])
def test_flash_kernel_sweep(B, H, KVH, T, D, causal, window, dtype, tol):
    q, k, v = _qkv(jax.random.PRNGKey(0), B, H, KVH, T, D, dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([64, 128, 192]), st.sampled_from([32, 64]),
       st.sampled_from([1, 2, 4]), st.booleans())
def test_flash_kernel_property(T, D, group, causal):
    KVH = 2
    H = KVH * group
    q, k, v = _qkv(jax.random.PRNGKey(T + D), 1, H, KVH, T, D, jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def _stacked(key, shape, n_layers, dtype=jnp.float32):
    """A layer-stacked store (n_layers, *shape) of normal draws."""
    return jax.random.normal(key, (n_layers, *shape)).astype(dtype)


@pytest.mark.parametrize("B,H,KVH,D,P,page,maxp", [
    (3, 8, 2, 64, 16, 32, 4),
    (2, 4, 4, 128, 8, 64, 2),
    (1, 16, 2, 64, 32, 16, 8),
])
def test_paged_kernel_sweep(B, H, KVH, D, P, page, maxp):
    key = jax.random.PRNGKey(B * 100 + H)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kp = _stacked(ks[1], (P, page, KVH, D), 1)
    vp = _stacked(ks[2], (P, page, KVH, D), 1)
    rng = np.random.default_rng(0)
    bt = rng.permutation(P)[: B * maxp].reshape(B, maxp).astype(np.int32)
    lengths = rng.integers(1, page * maxp, B).astype(np.int32)
    out = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths),
                          jnp.int32(0), interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, jnp.asarray(bt),
                                   jnp.asarray(lengths), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_int8():
    key = jax.random.PRNGKey(11)
    B, H, KVH, D, P, page, maxp = 2, 8, 2, 64, 8, 32, 3
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kp = _stacked(ks[1], (P, page, KVH, D), 1)
    vp = _stacked(ks[2], (P, page, KVH, D), 1)
    kq, ksc = quantize_kv(kp.reshape(P * page, 1, KVH, D))
    vq, vsc = quantize_kv(vp.reshape(P * page, 1, KVH, D))
    kq = kq.reshape(kp.shape).astype(jnp.float32)
    vq = vq.reshape(vp.shape).astype(jnp.float32)
    ksc = ksc.reshape(1, P, page, KVH)
    vsc = vsc.reshape(1, P, page, KVH)
    bt = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    lengths = jnp.asarray([70, 96], jnp.int32)
    out = paged_attention(q, kq, vq, bt, lengths, jnp.int32(0), ksc, vsc,
                          interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, bt, lengths, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=0.05, atol=0.05)  # int8 quant noise


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("layer", [0, 2, 4])
def test_paged_kernel_stacked_layer_matches_its_slice(layer, kv_dtype, impl):
    """The stacked-store kernel at layer 0, a middle layer and the last
    layer reads that layer's pages alone: it matches the reference run on
    that layer's slice as a one-layer store, bit for bit in the reference
    and to kernel precision in the interpreted kernel."""
    L, B, H, KVH, D, P, page, maxp = 5, 3, 8, 2, 64, 12, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(21), 5)
    q = jax.random.normal(ks[0], (B, H, D)).astype(jnp.bfloat16)
    if kv_dtype == "int8":
        kp = jax.random.randint(ks[1], (L, P, page, KVH, D), -127, 128,
                                jnp.int8)
        vp = jax.random.randint(ks[2], (L, P, page, KVH, D), -127, 128,
                                jnp.int8)
        scales = (jax.random.uniform(ks[3], (L, P, page, KVH), jnp.float32,
                                     0.005, 0.02),
                  jax.random.uniform(ks[4], (L, P, page, KVH), jnp.float32,
                                     0.005, 0.02))
    else:
        kp = _stacked(ks[1], (P, page, KVH, D), L, jnp.bfloat16)
        vp = _stacked(ks[2], (P, page, KVH, D), L, jnp.bfloat16)
        scales = ()
    rng = np.random.default_rng(layer)
    bt = jnp.asarray(rng.permutation(P)[: B * maxp].reshape(B, maxp),
                     jnp.int32)
    lengths = jnp.asarray([1, 17, page * maxp], jnp.int32)
    out = ops.paged_attention(q, kp, vp, bt, lengths, jnp.int32(layer),
                              *scales, impl=impl)
    one = lambda a: a[layer][None]                    # noqa: E731
    want = ref.paged_attention_ref(q, one(kp), one(vp), bt, lengths,
                                   jnp.int32(0), *map(one, scales))
    tol = 0 if impl == "xla" else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("N,d,block,res", [(512, 128, 128, True),
                                           (256, 256, 64, False),
                                           (128, 64, 128, True)])
def test_rmsnorm_kernel_sweep(N, d, block, res):
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (N, d))
    r = jax.random.normal(ks[1], (N, d)) if res else None
    s = jax.random.normal(ks[2], (d,))
    y, ro = fused_rmsnorm(x, s, r, block_rows=block, interpret=True)
    wy, wro = ref.fused_rmsnorm_ref(x, s, r)
    np.testing.assert_allclose(np.asarray(y), np.asarray(wy), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ro), np.asarray(wro), rtol=1e-6,
                               atol=1e-6)


def test_ops_wrappers_dispatch():
    key = jax.random.PRNGKey(6)
    q, k, v = _qkv(key, 1, 4, 2, 128, 32, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.flash_attention(q, k, v)),
        np.asarray(ref.flash_attention_ref(q, k, v)), rtol=2e-5, atol=2e-5)
    x = jax.random.normal(key, (100, 32))   # ragged rows -> ref fallback
    s = jnp.ones((32,))
    y, _ = ops.fused_rmsnorm(x, s)
    wy, _ = ref.fused_rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(y), np.asarray(wy), rtol=1e-5,
                               atol=1e-5)
