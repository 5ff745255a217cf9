"""Names of the serving path's host spans and instant marks.

Every span is a ``jax.profiler.TraceAnnotation``: on the profiler's clock,
beside the device planes of the same trace, and recorded only while a
profiler trace is active; off, a span is one small object made and
dropped. There is no other switch. Each name starts with ``sprout.``; PERF.md §3 lists each
span, mark and stat with the metric or operator use it serves.

The device-side names (``jax.named_scope`` in the fused programs) are in
``DEVICE_SCOPES``; ``models/`` spells them out itself, since it does not
import ``serving/``.

Importing this module installs one ``gc.callbacks`` hook that records
every garbage collection as a ``sprout.gc`` span (stat ``generation``)
while a trace is active.
"""
from __future__ import annotations

import gc
import threading

import jax

# host spans, nested on the serving thread as listed
GATEWAY_SUBMIT = "sprout.gateway.submit"
GATEWAY_STEP = "sprout.gateway.step"
SCHEDULER_DISPATCH = "sprout.scheduler.dispatch"   # stat requests
ENGINE_STEP = "sprout.engine.step"                 # stat lanes
ENGINE_ADMIT = "sprout.engine.admit"               # stat queued
ENGINE_PREFILL = "sprout.engine.prefill"           # stats bs, plen
ENGINE_PREPARE = "sprout.engine.prepare"
ENGINE_DISPATCH = "sprout.engine.dispatch"         # stats bs, k, mode, chunk, cold
ENGINE_FETCH = "sprout.engine.fetch"
ENGINE_EMIT = "sprout.engine.emit"                 # stats tokens, finished
GATEWAY_ACCOUNT = "sprout.gateway.account"         # stat requests
GC = "sprout.gc"                                   # stat generation

# instant marks
FIRST_TOKEN = "sprout.request.first_token"         # stats rid, wait_us, prefill_us

# device scopes of the fused programs (``jax.named_scope``)
SAMPLER = "sprout.sampler"
PREFILL_CHUNK = "sprout.prefill_chunk"
DEVICE_SCOPES = ("sprout.kv_write", "sprout.attention", "sprout.mlp",
                 "sprout.lm_head", SAMPLER, PREFILL_CHUNK)

_Annotation = jax.profiler.TraceAnnotation


def span(name: str, **stats) -> _Annotation:
    """A host span over a ``with`` block; ``stats`` become the event's
    stats (more can be added inside with ``set_metadata``)."""
    return _Annotation(name, **stats)


def mark(name: str, **stats) -> None:
    """An instant event: entered and left at once."""
    if _Annotation.is_enabled():
        with _Annotation(name, **stats):
            pass


_open_gc: dict = {}


def _on_gc(phase: str, info: dict) -> None:
    tid = threading.get_ident()
    if phase == "start":
        if _Annotation.is_enabled():
            ann = _Annotation(GC, generation=info["generation"])
            ann.__enter__()
            _open_gc[tid] = ann
    else:
        ann = _open_gc.pop(tid, None)
        if ann is not None:
            ann.__exit__(None, None, None)


gc.callbacks.append(_on_gc)
