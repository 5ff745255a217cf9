"""The serving path's spans, marks and device scopes, read back from a
real profiler trace of a tiny gateway on the CPU (no timing asserted)."""
import gc
import glob

import jax
import numpy as np
import pytest

from repro.configs import reduced
from repro.core import A100_40GB, CarbonIntensityProvider, EnergyModel
from repro.core.policies import SproutPolicy
from repro.models import model as MD
from repro.serving import (CarbonAwareScheduler, InferenceEngine,
                           ServeRequest, SproutGateway, spans)
from repro.serving.sampler import SamplingParams

# span -> the span it runs inside (None: outermost on the serving thread)
PARENT = {
    spans.GATEWAY_SUBMIT: None,
    spans.GATEWAY_STEP: None,
    spans.SCHEDULER_DISPATCH: spans.GATEWAY_STEP,
    spans.ENGINE_STEP: spans.GATEWAY_STEP,
    spans.ENGINE_ADMIT: spans.ENGINE_STEP,
    spans.ENGINE_PREFILL: spans.ENGINE_ADMIT,
    spans.ENGINE_PREPARE: spans.ENGINE_STEP,
    spans.ENGINE_DISPATCH: spans.ENGINE_STEP,
    spans.ENGINE_FETCH: spans.ENGINE_STEP,
    spans.ENGINE_EMIT: spans.ENGINE_STEP,
    spans.GATEWAY_ACCOUNT: spans.GATEWAY_STEP,
}


def _engine(cfg, params):
    return InferenceEngine(cfg, params, n_slots=4, max_len=128, eos_id=-1,
                           decode_block=4, paged=True, page_size=16,
                           n_pages=48, prefill_chunk=16, prefix_cache=True)


def _gateway(eng):
    prov = CarbonIntensityProvider("CA", "jun")
    policy = SproutPolicy(k0_min=prov.k_min, k0_max=prov.k_max,
                          k1=A100_40GB.embodied_gco2 / A100_40GB.lifetime_s)
    gw = SproutGateway([(prov, CarbonAwareScheduler([eng]))], policy=policy,
                       energy=EnergyModel(A100_40GB), hw=A100_40GB, seed=5)
    gw.tick(0.0)
    return gw


def _request(text, greedy=True, budget=12):
    return ServeRequest(0, text, max_new_tokens=budget,
                        max_new_by_level=[budget, budget // 2, budget // 3],
                        sampling=SamplingParams(0.0 if greedy else 0.7, 0,
                                                1.0 if greedy else 0.9))


def _events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((line.name, e.name, e.start_ns,
                            e.start_ns + e.duration_ns, dict(e.stats))
                           for e in line.events
                           if e.name.startswith("sprout."))
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two prompts on an idle engine (whole-prompt prefill), a third
    streamed in as chunks while they decode, a fourth sampled; all under
    one profiler trace, with one full collection inside it."""
    cfg = reduced("granite_3_2b").replace(vocab_size=512)
    params = MD.init_model(cfg, jax.random.PRNGKey(0))
    eng = _engine(cfg, params)
    gw = _gateway(eng)
    finished = {}
    gw.on_finish = lambda _key, fin: finished.__setitem__(fin.rid, fin)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    syncs0 = eng.decode_syncs
    for i in range(2):
        gw.submit(_request(f"first wave {i}", budget=60))
    gw.step()
    gw.submit(_request("a later prompt long enough to span two chunks of "
                       "sixteen tokens each, and then some more"))
    gw.submit(_request("a sampled one", greedy=False))
    gc.collect()
    for _ in range(200):
        if not any(p.load() for p in gw.pools):
            break
        gw.step()
    jax.profiler.stop_trace()
    return {"eng": eng, "events": _events(trace_dir), "finished": finished,
            "syncs": eng.decode_syncs - syncs0}


def _named(events, name):
    return [e for e in events if e[1] == name]


def test_every_span_appears_nested_as_listed(served):
    ev = served["events"]
    for name, parent in PARENT.items():
        mine = _named(ev, name)
        assert mine, f"no {name} span"
        if parent is None:
            continue
        outer = _named(ev, parent)
        for line, _, s, e, _ in mine:
            assert any(ol == line and os <= s and e <= oe
                       for ol, _, os, oe, _ in outer), (name, parent)


def test_one_dispatch_and_one_fetch_per_host_sync(served):
    ev = served["events"]
    assert served["syncs"] > 0
    assert len(_named(ev, spans.ENGINE_DISPATCH)) == served["syncs"]
    assert len(_named(ev, spans.ENGINE_FETCH)) == served["syncs"]


def test_cold_marks_exactly_each_variants_first_dispatch(served):
    seen = set()
    colds = 0
    for *_, st in sorted(_named(served["events"], spans.ENGINE_DISPATCH),
                         key=lambda e: e[2]):
        key = (st["bs"], st["k"], st["mode"], st["chunk"])
        assert st["cold"] == int(key not in seen), key
        colds += st["cold"]
        seen.add(key)
    fused = [n for n in served["eng"].entry_points
             if n.startswith(("decode_", "mixed_"))]
    assert colds == len(fused)
    assert any(c > 0 for *_, c in sorted(seen))   # a chunked dispatch ran


def test_stats_on_steps_admission_and_emit(served):
    ev = served["events"]
    assert {e[4]["bs"] for e in _named(ev, spans.ENGINE_PREFILL)} == {2}
    assert all(e[4]["lanes"] >= 0 for e in _named(ev, spans.ENGINE_STEP))
    assert all(e[4]["queued"] >= 0 for e in _named(ev, spans.ENGINE_ADMIT))
    assert sum(e[4]["requests"]
               for e in _named(ev, spans.SCHEDULER_DISPATCH)) == 4
    assert sum(e[4]["requests"]
               for e in _named(ev, spans.GATEWAY_ACCOUNT)) == 4
    # every token but the two the whole-prompt prefill drew comes out of a
    # fused block, chunked requests' first tokens included
    emitted = sum(e[4]["tokens"] for e in _named(ev, spans.ENGINE_EMIT))
    assert emitted == sum(f.gen_tokens
                          for f in served["finished"].values()) - 2


def test_first_token_marks_split_ttft(served):
    marks = _named(served["events"], spans.FIRST_TOKEN)
    fins = served["finished"]
    assert len(fins) == 4
    assert sorted(m[4]["rid"] for m in marks) == sorted(fins)
    for *_, st in marks:
        fin = fins[st["rid"]]
        assert st["wait_us"] >= 0 and st["prefill_us"] >= 0
        assert abs(st["wait_us"] + st["prefill_us"] - 1e6 * fin.ttft_s) \
            < 1e3


def test_marks_sit_where_the_first_token_is_set(served):
    ev = served["events"]
    inside = _named(ev, spans.ENGINE_PREFILL) + _named(ev, spans.ENGINE_EMIT)
    for line, _, s, e, _ in _named(ev, spans.FIRST_TOKEN):
        assert any(ol == line and os <= s and e <= oe
                   for ol, _, os, oe, _ in inside)


def test_a_collection_shows_as_a_gc_span(served):
    gcs = _named(served["events"], spans.GC)
    assert 2 in {e[4]["generation"] for e in gcs}
    assert all(e[3] >= e[2] for e in gcs)


def test_fused_program_carries_every_device_scope():
    cfg = reduced("granite_3_2b").replace(vocab_size=512)
    params = MD.init_model(cfg, jax.random.PRNGKey(0))
    eng = _engine(cfg, params)
    bs, k, c = 4, 2, eng.prefill_chunk
    fn, warm = eng._fused_for(k, "full", bs, c)
    assert not warm
    z = lambda dt: jax.numpy.zeros(bs, dt)
    state = {"last": z(np.int32), "pos": z(np.int32), "live": z(bool),
             "gc": z(np.int32), "max_new": z(np.int32) + 1,
             "temp": z(np.float32), "topk": z(np.int32),
             "topp": z(np.float32) + 1, "key": jax.random.PRNGKey(0),
             "rows": jax.numpy.arange(bs, dtype=np.int32), "ok": z(bool),
             "chunk_lane": jax.numpy.asarray(0, np.int32)}
    chunk = (jax.numpy.zeros((k, c), np.int32), jax.numpy.zeros(k, np.int32),
             jax.numpy.zeros(k, np.int32), jax.numpy.zeros(k, bool))
    bt = jax.numpy.full((bs, eng.pages.max_pages), -1, np.int32)
    hlo = fn.lower(params, eng.cache, bt, state, chunk).compile().as_text()
    op_names = [ln.split('op_name="', 1)[1].split('"', 1)[0]
                for ln in hlo.splitlines() if 'op_name="' in ln]
    for scope in spans.DEVICE_SCOPES:
        assert any(f"/{scope}/" in n for n in op_names), scope
