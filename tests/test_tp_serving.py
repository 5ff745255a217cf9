"""Tensor-parallel serving equivalence (DESIGN.md §14).

A tp-sharded engine must be a pure *placement* change: the fused decode
programs are unchanged SPMD, so tp=1 and tp>1 must emit token-identical
streams — greedy and seeded-sampled, dense and paged, fp32 and int8 KV —
and an evicted sharded request must requeue over the verbatim-token path
exactly like an unsharded one.

These tests need real multi-device placement, so they skip on the tier-1
single-device run and execute under scripts/multidevice.sh, which forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import jax
import pytest

from repro.configs import reduced
from repro.models import model as MD
from repro.serving.engine import InferenceEngine
from repro.serving.sampler import SamplingParams as SP


def _needs(n):
    return pytest.mark.skipif(
        jax.device_count() < n,
        reason=f"needs {n} devices (scripts/multidevice.sh forces 8 host "
               f"devices; tier-1 runs single-device)")


PROMPTS = ["hello sharded world", "carbon aware decode", "ab"]
SAMPLED = SP(temperature=0.9, top_k=40, top_p=0.95)


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced("granite_3_2b").replace(vocab_size=512)
    params = MD.init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(small_model, tp, *, paged=False, kv_int8=False):
    cfg, params = small_model
    return InferenceEngine(cfg, params, n_slots=4, max_len=64, eos_id=-1,
                           seed=7, decode_block=8, paged=paged,
                           page_size=16, kv_int8=kv_int8, tp_degree=tp)


def _decode_all(eng, *, sampling=None, max_new=10):
    for p in PROMPTS:
        eng.submit(eng.tok.encode(p), max_new_tokens=max_new,
                   sampling=sampling)
    eng.run_to_completion()
    return {f.rid: list(f.token_ids) for f in eng.finished}


@_needs(2)
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("kv_int8", [False, True], ids=["fp32", "int8"])
@pytest.mark.parametrize("sampling", [None, SAMPLED],
                         ids=["greedy", "sampled"])
def test_tp2_token_identical(small_model, paged, kv_int8, sampling):
    ref = _decode_all(
        _engine(small_model, 1, paged=paged, kv_int8=kv_int8),
        sampling=sampling)
    e2 = _engine(small_model, 2, paged=paged, kv_int8=kv_int8)
    got = _decode_all(e2, sampling=sampling)
    assert got == ref
    # sharded programs are minted under mesh-keyed names: a tp=2 bucket
    # can never collide with a tp=1 compilation of the same shape
    assert e2.entry_points and all(
        name.endswith("_tp2") for name in e2.entry_points)


@_needs(4)
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("sampling", [None, SAMPLED],
                         ids=["greedy", "sampled"])
def test_tp4_token_identical(small_model, paged, sampling):
    # tp=4 over 2 KV heads: the KV store's head axis does not divide, so
    # launch/sharding.py's _guard keeps it replicated while q-heads and
    # the MLP still shard 4 ways — tokens must be unchanged either way
    ref = _decode_all(_engine(small_model, 1, paged=paged),
                      sampling=sampling)
    got = _decode_all(_engine(small_model, 4, paged=paged),
                      sampling=sampling)
    assert got == ref


@_needs(4)
@pytest.mark.parametrize("tp,kv_int8", [(2, False), (4, True)],
                         ids=["tp2-fp32", "tp4-int8"])
def test_tp_pallas_paged_kernel_token_identical(small_model, tp, kv_int8):
    # the Pallas paged kernel cannot be partitioned by GSPMD, so a tp
    # engine runs it under shard_map (kernels/ops.py): tp=2 gives each
    # device one of the 2 KV heads, tp=4 does not divide them and every
    # device attends over both. Interpret mode runs the kernel's own code.
    cfg, params = small_model

    def run(t):
        eng = InferenceEngine(cfg, params, n_slots=4, max_len=64, eos_id=-1,
                              seed=7, paged=True, page_size=16,
                              kv_int8=kv_int8, tp_degree=t, prefill_chunk=8,
                              paged_impl="pallas_interpret")
        return _decode_all(eng)

    assert run(tp) == run(1)


@_needs(2)
def test_tp_engine_reports_degree(small_model):
    eng = _engine(small_model, 2)
    assert eng.tp_degree == 2
    assert eng.shard_spec is not None
    assert eng.shard_spec.tp_degree == 2
    single = _engine(small_model, 1)
    assert single.tp_degree == 1 and single.shard_spec is None


@_needs(2)
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_tp2_evict_requeues_verbatim(small_model, paged):
    """Evicting a mid-decode request from a sharded engine and
    resubmitting it regenerates the exact token stream (the migration
    contract: prompt ids are verbatim, redo restarts identically)."""
    ref = _decode_all(_engine(small_model, 1, paged=paged))

    eng = _engine(small_model, 2, paged=paged)
    rids = [eng.submit(eng.tok.encode(p), max_new_tokens=10)
            for p in PROMPTS]
    eng.step()                      # all live, partway through decode
    victim = rids[0]
    st = eng.evict(victim)
    assert st is not None and st.rid == victim
    assert st.prompt_ids == eng.tok.encode(PROMPTS[0])  # verbatim
    eng.run_to_completion()
    # requeue on the same sharded engine with the verbatim prompt
    eng.submit(st.prompt_ids, max_new_tokens=st.max_new_tokens,
               sampling=st.sampling, rid=st.rid)
    eng.run_to_completion()
    got = {f.rid: list(f.token_ids) for f in eng.finished}
    assert got == ref
