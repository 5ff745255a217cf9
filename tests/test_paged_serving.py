"""Paged KV serving: allocator invariants, paged kernel vs the
``PagedKVCache.gather()`` oracle, int8 parity, page-budget admission, and
dense-vs-paged fused-loop equivalence (the tentpole property: switching
the engine's KV layout must not change a single emitted token)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced
from repro.core.energy import LLAMA2_13B, A100_40GB, EnergyModel
from repro.models import attention as A
from repro.models import model as MD
from repro.models.attention import quantize_kv
from repro.serving import ByteTokenizer, InferenceEngine
from repro.serving.kv_cache import PageAllocator, PagedKVCache

pallas = pytest.importorskip("jax.experimental.pallas",
                             reason="Pallas unavailable in this jax build")
from repro.kernels.paged_attention import paged_attention  # noqa: E402


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced("granite_3_2b").replace(vocab_size=512)
    params = MD.init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ======================================================================
# allocator
# ======================================================================

def test_allocator_exhaustion_release_reuse_roundtrip():
    al = PageAllocator(n_pages=4, page_size=8, n_slots=3, max_len=64)
    al.ensure_capacity(0, 17)                  # 3 pages
    assert al.pages_in_use() == 3
    al.ensure_capacity(1, 8)                   # 1 page -> full
    with pytest.raises(MemoryError, match="exhausted"):
        al.ensure_capacity(2, 1)
    # failed allocation must not leak partial state
    assert al.pages_in_use() == 4
    al.release(0)
    assert al.pages_in_use() == 1
    al.ensure_capacity(2, 9)                   # reuse freed pages
    assert al.pages_in_use() == 3
    # per-slot cap beats pool exhaustion in the error taxonomy
    with pytest.raises(MemoryError, match="max_len"):
        al.ensure_capacity(2, 65)


def test_allocator_deterministic_lowest_id_reuse():
    """Release order must not leak into reuse order: allocation is always
    the lowest-numbered free page, a pure function of alloc/release
    history (the old list-ordered free list depended on interleaving)."""
    al = PageAllocator(n_pages=8, page_size=8, n_slots=4, max_len=64)
    for slot, tokens in ((0, 16), (1, 16), (2, 16)):   # pages 0-5 in order
        al.ensure_capacity(slot, tokens)
    assert al.block_table[0, :2].tolist() == [0, 1]
    al.release(1)                                      # frees 2, 3
    al.release(0)                                      # frees 0, 1
    al.ensure_capacity(3, 32)                          # 4 pages
    assert al.block_table[3, :4].tolist() == [0, 1, 2, 3]


def test_allocator_incremental_counts_and_fragmentation():
    al = PageAllocator(n_pages=8, page_size=8, n_slots=2, max_len=64)
    al.ensure_capacity(0, 12)                  # 2 pages for 12 tokens
    al.lengths[0] = 12
    assert al.pages_in_use() == 2
    assert al.live_tokens() == 12
    assert al.fragmentation() == pytest.approx(1 - 12 / 16)
    rep = al.report()
    assert rep["pages_in_use"] == 2 and rep["occupancy"] == 0.25
    al.release(0)
    assert al.fragmentation() == 0.0 and al.live_tokens() == 0


def test_paged_cache_coalesced_append_matches_gather():
    """Multi-token appends land exactly like token-at-a-time appends and
    cross page boundaries correctly (per-page block writes)."""
    ps, nkv, dh = 8, 2, 4
    key = jax.random.PRNGKey(0)
    k = jax.random.normal(key, (21, nkv, dh))
    v = k * 0.5
    ref = PagedKVCache(n_pages=6, page_size=ps, n_kv=nkv, head_dim=dh,
                       n_slots=1, max_len=48)
    for t in range(21):
        ref.append(0, k[t], v[t])              # one token at a time
    run = PagedKVCache(n_pages=6, page_size=ps, n_kv=nkv, head_dim=dh,
                       n_slots=1, max_len=48)
    run.append(0, k[:5], v[:5])                # runs straddling pages
    run.append(0, k[5:19], v[5:19])
    run.append(0, k[19:], v[19:])
    for a, b in zip(ref.gather(0), run.gather(0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(run.gather(0)[0]), np.asarray(k),
                               rtol=1e-6)


# ======================================================================
# kernel vs gather() oracle
# ======================================================================

def _oracle_from_gather(pc: PagedKVCache, q):
    """Attention computed from the materialized per-slot K/V — the
    independent oracle the kernel must match."""
    outs = []
    for b in range(q.shape[0]):
        kk, vv = pc.gather(b)
        kk = np.asarray(kk, np.float32)        # (L, KVH, D)
        vv = np.asarray(vv, np.float32)
        B, H, D = q.shape
        KVH = kk.shape[1]
        g = H // KVH
        qh = np.asarray(q[b], np.float32).reshape(KVH, g, D)
        s = np.einsum("kgd,skd->kgs", qh, kk) / math.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        outs.append(np.einsum("kgs,skd->kgd", p, vv).reshape(H, D))
    return np.stack(outs)


def _in_stack(store, layer, n_layers=3):
    """``store`` as layer ``layer`` of a stacked store whose other layers
    hold noise, so a kernel that read the wrong layer would fail."""
    noise = jax.random.normal(jax.random.PRNGKey(123),
                              (n_layers, *store.shape)).astype(store.dtype)
    return noise.at[layer].set(store)


@pytest.mark.pallas
@pytest.mark.parametrize("lengths", [
    (15, 16, 17),     # straddle below / exactly on / above a page boundary
    (1, 40, 33),      # near-empty slot + multi-page slots
])
def test_paged_kernel_matches_gather_oracle(lengths):
    ps, nkv, dh, H = 16, 2, 32, 4
    B = len(lengths)
    pc = PagedKVCache(n_pages=12, page_size=ps, n_kv=nkv, head_dim=dh,
                      n_slots=B, max_len=64)
    key = jax.random.PRNGKey(7)
    for b, L in enumerate(lengths):
        kb = jax.random.normal(jax.random.fold_in(key, b), (L, nkv, dh))
        pc.write_prompt(b, kb, kb * 0.25 + 1.0)
    q = jax.random.normal(jax.random.fold_in(key, 99), (B, H, dh))
    bt, ln = pc.device_tables()
    out = paged_attention(q, _in_stack(pc.k, 1), _in_stack(pc.v, 1), bt, ln,
                          jnp.int32(1), interpret=True)
    want = _oracle_from_gather(pc, q)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)


@pytest.mark.pallas
def test_paged_kernel_predicated_empty_pages_no_dma():
    """Unmapped table entries (-1) past a slot's length must not affect
    the output — those grid steps are predicated off entirely."""
    ps, nkv, dh, H = 16, 1, 32, 2
    pc = PagedKVCache(n_pages=8, page_size=ps, n_kv=nkv, head_dim=dh,
                      n_slots=2, max_len=128)        # max_pages=8 > needed
    key = jax.random.PRNGKey(3)
    pc.write_prompt(0, jax.random.normal(key, (5, nkv, dh)),
                    jax.random.normal(key, (5, nkv, dh)))
    assert (pc.block_table[0] >= 0).sum() == 1       # 7 unmapped entries
    q = jax.random.normal(jax.random.fold_in(key, 1), (2, H, dh))
    bt, ln = pc.device_tables()
    out = paged_attention(q, _in_stack(pc.k, 2), _in_stack(pc.v, 2), bt, ln,
                          jnp.int32(2), interpret=True)
    want = _oracle_from_gather(pc, q[:1])
    np.testing.assert_allclose(np.asarray(out)[:1], want, rtol=2e-5,
                               atol=2e-5)
    # slot 1 holds nothing: all pages predicated off -> exactly zero
    np.testing.assert_array_equal(np.asarray(out)[1], 0.0)


@pytest.mark.pallas
def test_paged_kernel_int8_parity_with_fp_oracle():
    ps, nkv, dh, H, B = 16, 2, 32, 4, 2
    key = jax.random.PRNGKey(5)
    pc = PagedKVCache(n_pages=8, page_size=ps, n_kv=nkv, head_dim=dh,
                      n_slots=B, max_len=64)
    for b, L in enumerate((23, 48)):
        kb = jax.random.normal(jax.random.fold_in(key, b), (L, nkv, dh))
        pc.write_prompt(b, kb, kb * 0.5)
    kq, ks = quantize_kv(pc.k.reshape(-1, 1, nkv, dh))
    vq, vs = quantize_kv(pc.v.reshape(-1, 1, nkv, dh))
    q = jax.random.normal(jax.random.fold_in(key, 9), (B, H, dh))
    bt, ln = pc.device_tables()
    out = paged_attention(
        q, _in_stack(kq.reshape(pc.k.shape).astype(jnp.float32), 0),
        _in_stack(vq.reshape(pc.v.shape).astype(jnp.float32), 0), bt, ln,
        jnp.int32(0), _in_stack(ks.reshape(*pc.k.shape[:2], nkv), 0),
        _in_stack(vs.reshape(*pc.v.shape[:2], nkv), 0), interpret=True)
    want = _oracle_from_gather(pc, q)
    np.testing.assert_allclose(np.asarray(out), want, rtol=0.05, atol=0.05)


# ======================================================================
# in-place writes into the layer-stacked store
# ======================================================================

@pytest.mark.parametrize("kv_int8", [False, True])
def test_stacked_store_writes_touch_only_their_layer(small_model, kv_int8):
    """A decode append and a chunk append at [layer, page, offset] change
    only that layer's mapped pages: every other layer's pages stay
    bit-identical, and so do the written layer's other pages. Dead lanes
    and unmapped entries write nowhere."""
    cfg, params = small_model
    if kv_int8:
        cfg = cfg.replace(kv_cache_dtype="int8")
    L, P, ps, layer = 4, 6, 16, 2
    one = jax.tree.map(lambda a: a[0],
                       MD.init_paged_cache(cfg, P, ps)[0])
    key = jax.random.PRNGKey(4)
    store = {nm: (jax.random.normal(jax.random.fold_in(key, i),
                                    (L, *a.shape)) * 50).astype(a.dtype)
             for i, (nm, a) in enumerate(sorted(one.items()))}
    p = jax.tree.map(lambda a: a[layer], params["segs"][0])["attn"]
    d = cfg.d_model
    bt = jnp.asarray([[3, 1, -1, -1], [0, -1, -1, -1], [5, 2, -1, -1]],
                     jnp.int32)
    positions = jnp.asarray([17, 40, 5])     # row 1: unmapped entry
    live = jnp.asarray([True, True, False])  # row 2: dead lane
    x = jax.random.normal(key, (3, 1, d)).astype(cfg.compute_dtype)
    _, dec = A.gqa_decode_paged(cfg, p, x, positions, store,
                                jnp.int32(layer), bt, live=live, impl="xla")
    xc = jax.random.normal(key, (1, 8, d)).astype(cfg.compute_dtype)
    _, chk = A.gqa_chunk_append(cfg, p, xc, 24 + jnp.arange(8),
                                jnp.arange(8) < 6, jnp.int32(2), store,
                                block_table=bt, layer=jnp.int32(layer))
    # decode: row 0 lands at page 1, offset 1; chunk: lane 2's valid rows
    # (positions 24..29) at page 2, offsets 8..13
    for new, pg, offs in ((dec, 1, [1]), (chk, 2, list(range(8, 14)))):
        keep = np.ones((L, P, ps), bool)
        keep[layer, pg, offs] = False
        for nm in store:
            np.testing.assert_array_equal(np.asarray(store[nm])[keep],
                                          np.asarray(new[nm])[keep],
                                          err_msg=nm)
        assert (np.asarray(store["k"])[layer, pg, offs]
                != np.asarray(new["k"])[layer, pg, offs]).any(-1).any(-1).all()


# ======================================================================
# fused-loop equivalence: dense vs paged
# ======================================================================

REQS = [("alpha prompt", 20), ("b", 3), ("c c c", 3), ("dddd", 11),
        ("e", 7)]


def _run_engine(cfg, params, reqs, **kw):
    eng = InferenceEngine(cfg, params, n_slots=2, max_len=64, **kw)
    tok = ByteTokenizer()
    for prompt, mnt in reqs:
        eng.submit(tok.encode(prompt), max_new_tokens=mnt)
    return eng, eng.run_to_completion()


@pytest.mark.parametrize("decode_block", [1, 8])
def test_fused_loop_dense_vs_paged_identical(small_model, decode_block):
    """Switching the KV layout must not change one emitted token, the
    finish order, or the token accounting — at K=1 and K>1."""
    cfg, params = small_model
    ed, fd = _run_engine(cfg, params, REQS, decode_block=decode_block)
    ep, fp = _run_engine(cfg, params, REQS, decode_block=decode_block,
                         paged=True, page_size=16)
    assert [f.rid for f in fd] == [f.rid for f in fp]
    for a, b in zip(fd, fp):
        assert a.token_ids == b.token_ids
        assert (a.prompt_tokens, a.gen_tokens) == (b.prompt_tokens,
                                                   b.gen_tokens)
    # one device_get per block in BOTH layouts: the block-table push is
    # host->device only, so the sync count cannot differ
    assert ed.decode_syncs == ep.decode_syncs
    # everything released at the end: memory followed live tokens down
    assert ep.pages.pages_in_use() == 0
    assert ep._committed == 0


def test_fused_loop_dense_vs_paged_int8(small_model):
    cfg, params = small_model
    _, fd = _run_engine(cfg, params, REQS, decode_block=8, kv_int8=True)
    _, fp = _run_engine(cfg, params, REQS, decode_block=8, kv_int8=True,
                        paged=True, page_size=16)
    for a, b in zip(fd, fp):
        assert a.token_ids == b.token_ids


@pytest.mark.pallas
def test_fused_loop_pallas_interpret_matches_ref(small_model):
    """The engine driving the real kernel (interpret mode) emits the same
    tokens as the XLA reference path."""
    cfg, params = small_model
    reqs = REQS[:3]
    _, fx = _run_engine(cfg, params, reqs, decode_block=4, paged=True,
                        page_size=16, paged_impl="xla")
    _, fk = _run_engine(cfg, params, reqs, decode_block=4, paged=True,
                        page_size=16, paged_impl="pallas_interpret")
    for a, b in zip(fx, fk):
        assert a.token_ids == b.token_ids


# ======================================================================
# page-budget admission + telemetry
# ======================================================================

def test_page_budget_gates_admission_not_completion(small_model):
    """With pages for ~2 requests but 4 free slots, concurrency tracks the
    page budget; every request still completes, FIFO."""
    cfg, params = small_model
    tok = ByteTokenizer()
    eng2 = InferenceEngine(cfg, params, n_slots=4, max_len=64, paged=True,
                           page_size=16, n_pages=4, eos_id=-1)
    # prompt 3 + 20 new = 23 tokens -> 2-page reservation each; the 4-page
    # budget admits exactly two at a time
    rids = [eng2.submit(tok.encode("pp"), max_new_tokens=20)
            for _ in range(4)]
    eng2.run_to_completion()
    assert sorted(f.rid for f in eng2.finished) == sorted(rids)
    assert all(f.gen_tokens == 20 for f in eng2.finished)
    # the engine-tracked high-water mark (sampled at maximal residency,
    # before same-step finishes free slots): budget-gated, not slot-gated
    assert eng2.peak_concurrent == 2
    assert eng2.pages.pages_in_use() == 0


def test_unservable_page_budget_rejected_at_submit(small_model):
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_slots=2, max_len=64, paged=True,
                          page_size=16, n_pages=2)
    tok = ByteTokenizer()
    with pytest.raises(ValueError, match="page budget"):
        eng.submit(tok.encode("x" * 40), max_new_tokens=20)  # needs 4 pages
    eng.submit(tok.encode("ok"), max_new_tokens=8)           # 1 page: fine
    assert len(eng.run_to_completion()) == 1


def test_kv_memory_scales_with_live_tokens(small_model):
    """The acceptance property: measured pages_in_use x page_bytes tracks
    live tokens, while the dense layout charges n_slots x max_len always."""
    cfg, params = small_model
    tok = ByteTokenizer()
    eng = InferenceEngine(cfg, params, n_slots=4, max_len=64, paged=True,
                          page_size=16, eos_id=-1)
    dense = InferenceEngine(cfg, params, n_slots=4, max_len=64)
    assert eng.kv_stats()["kv_bytes_in_use"] == 0
    assert dense.kv_stats()["kv_bytes_in_use"] == \
        dense.kv_stats()["kv_bytes_capacity"]
    eng.submit(tok.encode("hello"), max_new_tokens=40)
    eng.step()                       # one decode block: still mid-flight
    s1 = eng.kv_stats()
    assert 0 < s1["kv_bytes_in_use"] < s1["kv_bytes_capacity"]
    assert s1["pages_in_use"] == eng.pages.pages_needed(
        int(eng.positions[0]) + 1)  # prompt + in-flight appends, 1 slot
    eng.run_to_completion()
    assert eng.kv_stats()["kv_bytes_in_use"] == 0


def test_drain_slots_releases_pages(small_model):
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_slots=2, max_len=64, paged=True,
                          page_size=16, eos_id=-1)
    tok = ByteTokenizer()
    for i in range(2):
        eng.submit(tok.encode(f"req {i}"), max_new_tokens=20)
    eng.step()
    assert eng.pages.pages_in_use() > 0
    drained = eng.drain_slots()
    assert len(drained) == 2
    assert eng.pages.pages_in_use() == 0 and eng._committed == 0


def test_int8_profile_halves_modeled_decode_kv_bytes():
    """engine flag -> EnergyModel roofline: the int8 profile's modeled
    decode KV bytes/token are ~2x lower, and that flows into measure()."""
    em = EnergyModel(A100_40GB)
    m8 = LLAMA2_13B.with_int8_kv()
    ratio = (em.decode_kv_bytes_per_token(LLAMA2_13B, 512)
             / em.decode_kv_bytes_per_token(m8, 512))
    assert 1.8 < ratio < 2.1
    kwh, _ = em.measure(LLAMA2_13B, 128, 64)
    kwh8, _ = em.measure(m8, 128, 64)
    assert kwh8 < kwh
