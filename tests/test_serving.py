"""Serving stack: engine, continuous batching, failover, paged cache,
tokenizer round-trips, sampler."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.configs import reduced
from repro.core.directives import DirectiveSet
from repro.models import model as MD
from repro.serving import (ByteTokenizer, CarbonAwareScheduler,
                           InferenceEngine, SamplingParams, ServeRequest)
from repro.serving.kv_cache import PagedKVCache
from repro.serving.sampler import sample_logits, sample_logits_batched


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced("granite_3_2b").replace(vocab_size=512)
    params = MD.init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_serves_all_requests(small_model):
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_slots=3, max_len=64)
    tok = ByteTokenizer()
    rids = [eng.submit(tok.encode(f"hi {i}"), max_new_tokens=8)
            for i in range(7)]
    fin = eng.run_to_completion()
    assert sorted(f.rid for f in fin) == sorted(rids)
    for f in fin:
        assert 1 <= f.gen_tokens <= 8
        assert f.ttft_s >= 0 and f.latency_s >= f.ttft_s


def test_engine_continuous_batching_overlaps(small_model):
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_slots=2, max_len=64)
    tok = ByteTokenizer()
    eng.submit(tok.encode("a"), max_new_tokens=20)
    eng.submit(tok.encode("b"), max_new_tokens=3)
    eng.submit(tok.encode("c"), max_new_tokens=3)
    fin = eng.run_to_completion()
    assert len(fin) == 3   # short requests slot in while the long one runs


def test_engine_deterministic_greedy(small_model):
    cfg, params = small_model
    tok = ByteTokenizer()
    outs = []
    for _ in range(2):
        eng = InferenceEngine(cfg, params, n_slots=1, max_len=64)
        eng.submit(tok.encode("determinism test"), max_new_tokens=10)
        outs.append(tuple(eng.run_to_completion()[0].token_ids))
    assert outs[0] == outs[1]


def test_scheduler_failover_preserves_requests(small_model):
    cfg, params = small_model
    e1 = InferenceEngine(cfg, params, n_slots=2, max_len=64)
    e2 = InferenceEngine(cfg, params, n_slots=2, max_len=64)
    sched = CarbonAwareScheduler([e1, e2], DirectiveSet(), level_fn=lambda: 1)
    for i in range(6):
        # budget outlasts one fused decode block so work is still in
        # flight on the replica when it fails
        sched.submit(ServeRequest(0, f"q{i}", max_new_tokens=40))
    sched.step()
    requeued = sched.fail_replica(0)
    assert requeued >= 1
    fin = sched.run()
    assert len({f.rid for f in fin}) >= 6    # nothing lost
    assert all(f.directive_level == 1 for f in fin)


def test_scheduler_failover_does_not_rewrap_prompt(small_model):
    """A requeued request's prompt is already directive-rendered; dispatch
    must not nest it in another ChatML wrapper with a fresh directive."""
    cfg, params = small_model
    tok = ByteTokenizer()

    def baseline():
        eng = InferenceEngine(cfg, params, n_slots=1, max_len=64)
        s = CarbonAwareScheduler([eng], DirectiveSet(), level_fn=lambda: 2)
        s.submit(ServeRequest(0, "hello", max_new_tokens=40))
        return s.run()[0]

    ref = baseline()
    e1 = InferenceEngine(cfg, params, n_slots=1, max_len=64)
    e2 = InferenceEngine(cfg, params, n_slots=1, max_len=64)
    sched = CarbonAwareScheduler([e1, e2], DirectiveSet(), level_fn=lambda: 2)
    sched.submit(ServeRequest(0, "hello", max_new_tokens=40))
    sched.step()                       # prefills on e1, still in flight
    assert sched.fail_replica(0) == 1
    fin = sched.run()[0]
    assert fin.prompt_tokens == ref.prompt_tokens   # no nested re-wrap
    assert fin.directive_level == ref.directive_level == 2


def test_serve_request_sampling_default_not_shared():
    """Regression: a class-level ``SamplingParams()`` default was one
    shared instance across every request."""
    a, b = ServeRequest(0, "a"), ServeRequest(0, "b")
    assert a.sampling is not b.sampling


def test_scheduler_failover_resubmits_token_ids_verbatim(small_model):
    """Regression: failover used to decode() the prompt ids and re-encode
    them — a lossy round trip. The requeued request must carry the ORIGINAL
    token ids and dispatch must submit them unchanged."""
    cfg, params = small_model
    tok = ByteTokenizer()
    # ids that do NOT survive a decode/encode round trip (interior BOS
    # renders as nothing)
    ids = [ByteTokenizer.BOS, 104, 105, ByteTokenizer.BOS, 106]
    assert tok.encode(tok.decode(ids), bos=True) != ids
    e1 = InferenceEngine(cfg, params, n_slots=1, max_len=64)
    e2 = InferenceEngine(cfg, params, n_slots=1, max_len=64)
    sched = CarbonAwareScheduler([e1, e2], DirectiveSet(), level_fn=lambda: 1)
    sched.submit(ServeRequest(0, "raw-ids", max_new_tokens=40,
                              prompt_token_ids=ids, directive_level=1))
    sched.step()                       # prefills on e1, still in flight
    assert e1.slots[0] is not None and e1.slots[0].prompt_ids == ids
    assert sched.fail_replica(0) == 1
    assert sched.pending[0].prompt_token_ids == ids
    sched.step()                       # redispatches onto e2
    assert e2.slots[0] is not None and e2.slots[0].prompt_ids == ids
    fin = sched.run()
    assert len(fin) == 1
    assert fin[0].prompt_tokens == len(ids)
    assert fin[0].directive_level == 1


def test_scheduler_per_level_token_budgets(small_model):
    """max_new_by_level: the drawn directive level selects the generation
    budget at dispatch time (the serving-side effect of a brevity
    directive on a model that cannot follow instructions)."""
    cfg, params = small_model
    budgets = [12, 6, 3]
    for lvl in range(3):
        eng = InferenceEngine(cfg, params, n_slots=1, max_len=64, eos_id=-1)
        sched = CarbonAwareScheduler([eng], DirectiveSet(),
                                     level_fn=lambda lvl=lvl: lvl)
        sched.submit(ServeRequest(0, "budget", max_new_by_level=budgets))
        fin = sched.run()
        assert fin[0].directive_level == lvl
        assert fin[0].gen_tokens == budgets[lvl]


def test_engine_attributes_decode_seconds_per_request(small_model):
    """Per-request decode-only telemetry: warm decode blocks charge each
    live slot per executed step; totals reconcile with the engine-level
    decode clock."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_slots=2, max_len=64, eos_id=-1,
                          decode_block=4)
    tok = ByteTokenizer()
    for i in range(4):
        eng.submit(tok.encode(f"warm {i}"), max_new_tokens=8)
    eng.run_to_completion()            # warm: compiles charge 0.0
    eng.finished = []
    for i in range(4):
        eng.submit(tok.encode(f"timed {i}"), max_new_tokens=8)
    wall = 0.0
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        wall += eng.last_decode_s      # decode-only clock, this dispatch
    fin = eng.finished
    assert len(fin) == 4
    assert all(f.decode_s > 0 for f in fin)
    # partitioned attribution: per-request decode seconds sum to the
    # device's decode wall time (dead tail steps included)
    assert sum(f.decode_s for f in fin) == pytest.approx(wall, rel=1e-6)
    # requests co-occupied every block in equal shares
    assert max(f.decode_s for f in fin) < 10 * min(f.decode_s for f in fin)


def test_scheduler_rejects_unservable_without_losing_others(small_model):
    """A request whose budget no engine can hold is parked in .rejected
    with the reason; the rest of the batch is unaffected."""
    cfg, params = small_model
    eng = InferenceEngine(cfg, params, n_slots=2, max_len=64)
    sched = CarbonAwareScheduler([eng], DirectiveSet())
    sched.submit(ServeRequest(0, "fine", max_new_tokens=6))
    sched.submit(ServeRequest(0, "impossible", max_new_tokens=64))
    sched.submit(ServeRequest(0, "also fine", max_new_tokens=6))
    fin = sched.run()
    assert len(fin) == 2
    assert len(sched.rejected) == 1
    req, reason = sched.rejected[0]
    assert req.max_new_tokens == 64 and "max_new_tokens" in reason


def test_scheduler_elastic_scale_up(small_model):
    cfg, params = small_model
    e1 = InferenceEngine(cfg, params, n_slots=1, max_len=64)
    sched = CarbonAwareScheduler([e1], DirectiveSet())
    for i in range(4):
        sched.submit(ServeRequest(0, f"q{i}", max_new_tokens=4))
    sched.step()
    sched.add_replica(InferenceEngine(cfg, params, n_slots=2, max_len=64))
    fin = sched.run()
    assert len(fin) == 4


def test_paged_cache_alloc_free_cycle():
    pc = PagedKVCache(n_pages=6, page_size=8, n_kv=1, head_dim=4,
                      n_slots=3, max_len=32)
    k = jax.random.normal(jax.random.PRNGKey(0), (20, 1, 4))
    pc.write_prompt(0, k, k)
    assert pc.pages_in_use() == 3
    pc.write_prompt(1, k[:8], k[:8])
    assert pc.pages_in_use() == 4
    gk, _ = pc.gather(0)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(k), rtol=1e-6)
    pc.release(0)
    assert pc.pages_in_use() == 1
    with pytest.raises(MemoryError):
        big = jax.random.normal(jax.random.PRNGKey(1), (33, 1, 4))
        pc.write_prompt(2, big, big)   # > max_len pages available? exhaust
    pc.release(1)


@given(st.text(max_size=60))
def test_tokenizer_roundtrip(s):
    tok = ByteTokenizer()
    assert tok.decode(tok.encode(s)) == s


def test_tokenizer_specials():
    tok = ByteTokenizer()
    ids = tok.encode("<|user|>hi<|end|>")
    assert ids[0] == ByteTokenizer.USR and ids[-1] == ByteTokenizer.END
    assert tok.decode(ids) == "<|user|>hi<|end|>"


def test_sampler_modes():
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]] * 4)
    greedy = sample_logits(logits, key, SamplingParams())
    assert (np.asarray(greedy) == 1).all()
    topk = sample_logits(jnp.tile(logits, (64, 1))[:64], key,
                         SamplingParams(temperature=1.0, top_k=2))
    assert set(np.asarray(topk)) <= {1, 2}
    topp = sample_logits(jnp.tile(logits, (64, 1))[:64], key,
                         SamplingParams(temperature=1.0, top_p=0.6))
    assert set(np.asarray(topp)) <= {1}


def test_sampler_greedy_deterministic():
    logits = jax.random.normal(jax.random.PRNGKey(3), (8, 128))
    outs = [sample_logits(logits, jax.random.PRNGKey(k), SamplingParams())
            for k in range(4)]
    for o in outs[1:]:   # greedy ignores the key entirely
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(o))
    np.testing.assert_array_equal(np.asarray(outs[0]),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_sampler_top_k_masks_exactly_k():
    V, k = 64, 5
    logits = jax.random.normal(jax.random.PRNGKey(7), (16, V))
    # draw many samples: only the k largest logits of each row may appear
    draws = np.asarray(jnp.stack([
        sample_logits(logits, jax.random.PRNGKey(s),
                      SamplingParams(temperature=1.0, top_k=k))
        for s in range(200)]))
    top = np.argsort(np.asarray(logits), axis=-1)[:, -k:]
    for row in range(logits.shape[0]):
        seen = set(draws[:, row].tolist())
        assert seen <= set(top[row].tolist())
    # an un-masked token CAN appear given enough draws (k-th largest allowed)
    flat_top_counts = sum(len(set(draws[:, r])) for r in range(16))
    assert flat_top_counts > 16    # more than just the argmax survives


def test_sampler_top_p_smallest_nucleus():
    # construct a row whose nucleus is known exactly
    probs = np.array([0.55, 0.25, 0.12, 0.05, 0.03])
    logits = jnp.asarray(np.log(probs)[None, :].repeat(64, 0))
    # p=0.5: the single largest token already covers it
    d1 = sample_logits(logits, jax.random.PRNGKey(0),
                       SamplingParams(temperature=1.0, top_p=0.5))
    assert set(np.asarray(d1)) == {0}
    # p=0.7: {0.55, 0.25} is the smallest set with mass >= 0.7
    d2 = np.concatenate([np.asarray(sample_logits(
        logits, jax.random.PRNGKey(s),
        SamplingParams(temperature=1.0, top_p=0.7))) for s in range(50)])
    assert set(d2.tolist()) <= {0, 1}
    assert 1 in set(d2.tolist())   # the boundary token stays in the nucleus
    # p=0: degenerate nucleus collapses to the single top token
    d3 = sample_logits(logits, jax.random.PRNGKey(1),
                       SamplingParams(temperature=1.0, top_p=0.0))
    assert set(np.asarray(d3).tolist()) == {0}


def test_sampler_batched_matches_per_slot_loop():
    """The fused per-slot-params path must be token-for-token identical to
    sampling each slot on its own with the slot-folded key (the discipline
    the pre-fusion engine loop used)."""
    key = jax.random.PRNGKey(11)
    B, V = 6, 96
    logits = jax.random.normal(jax.random.PRNGKey(5), (B, V)) * 3.0
    params = [SamplingParams(),                                   # greedy
              SamplingParams(temperature=0.7),
              SamplingParams(temperature=1.3, top_k=10),
              SamplingParams(temperature=0.9, top_p=0.8),
              SamplingParams(temperature=1.1, top_k=7, top_p=0.9),
              SamplingParams()]                                   # greedy
    batched = np.asarray(sample_logits_batched(
        logits, key,
        jnp.asarray([p.temperature for p in params], jnp.float32),
        jnp.asarray([p.top_k for p in params], jnp.int32),
        jnp.asarray([p.top_p for p in params], jnp.float32)))
    for i, p in enumerate(params):
        ref = int(sample_logits(logits[i:i + 1],
                                jax.random.fold_in(key, i), p)[0])
        assert batched[i] == ref, f"slot {i} ({p}) diverged"


def test_sampler_batched_mixed_greedy_and_sampled():
    logits = jnp.asarray([[0.0, 9.0, 1.0, -2.0]] * 4)
    out = np.asarray(sample_logits_batched(
        logits, jax.random.PRNGKey(0),
        jnp.asarray([0.0, 1.0, 0.0, 1.0]),
        jnp.asarray([0, 2, 0, 0], jnp.int32),
        jnp.asarray([1.0, 1.0, 1.0, 0.6])))
    assert out[0] == 1 and out[2] == 1          # greedy rows
    assert out[1] in (1, 2) and out[3] == 1     # top-k=2 / top-p=0.6 rows


def test_launcher_main_serves_in_process():
    from repro.launch import serve
    gw = serve.main(["--arch", "granite_3_2b", "--gateway", "--paged",
                     "--page-size", "16", "--max-len", "128", "--hours", "1",
                     "--requests", "2", "--replicas", "1", "--max-new", "8"])
    assert gw.stats.requests == 2 and gw.stats.rejected == 0
    assert gw.pools[0].scheduler.engines[0].max_len == 128


def test_compile_cache_dir_from_env_or_fixed_path(monkeypatch):
    from repro.launch import serve
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert serve.setup_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # left to jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert serve.setup_compile_cache() == ""           # off on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        want = str(serve.COMPILE_CACHE_DIR)
        assert serve.setup_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        repo = pathlib.Path(__file__).resolve().parents[1]
        assert serve.COMPILE_CACHE_DIR == repo / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
