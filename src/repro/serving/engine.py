"""Slot-based continuous-batching inference engine (JetStream-style).

TPU adaptation of vLLM's continuous batching: a fixed decode batch of
``n_slots``; each slot owns a linear KV region of ``max_len`` tokens.
Queued requests are prefilled in bucketed batches across all free slots and
*inserted* into those slots with a single donated tree-level cache update.
Finished slots are freed and refilled from the queue. Prefill-compute and
decode-compute are separate jitted programs, so decode latency is never
blocked on prefill compilation.

The decode hot path is **device-resident**: per-slot positions, last
tokens, live mask, generation counters and stacked sampling parameters
(temperature / top-k / top-p arrays) live inside one jitted program that
runs up to ``decode_block`` decode+sample steps under ``jax.lax.scan``
before the host looks at anything. Sampling is fused into the decode step
(``models.model.decode_sample_step`` + ``sampler.sample_logits_batched``),
so greedy and sampled slots coexist in one batch with no per-slot Python
re-sampling, and the engine performs exactly one ``jax.device_get`` per
block of up to ``decode_block`` decoded tokens. The block length shrinks
to the soonest deterministic finish (length caps), so a freed slot is
refilled — and prefill runs — at the earliest step it can matter; EOS
inside a block just masks the slot until the block ends.

Two serving-shape mechanisms sit on top of that loop (DESIGN.md §3):

**Batch-bucketed entry points** (SHARK-Engine style ``decode_bs{N}``
function tables): each dispatch selects a compiled program sized to the
power-of-two ceiling of *live occupancy* instead of always paying
``n_slots``-batch FLOPs. The bucketed program gathers the participating
rows of every cache leaf (axis 1) on entry and scatters them back on
exit — one gather/scatter pair per block, amortized over up to
``decode_block`` steps — and folds the sampler PRNG by *slot id* so drawn
tokens are invariant to which bucket served a row. At full occupancy the
un-gathered identity program runs, byte-identical to the fixed-batch
world. Paged caches need no gather at all (the page store is shared; only
the tiny block table is row-selected host-side). MoE stacks pin
``bs = n_slots``: expert-capacity routing is batch-shape-dependent, so
bucketing would perturb their token streams.

**Chunked prefill** (Sarathi/vLLM continuous batching): with
``prefill_chunk > 0`` and live decode lanes, an arriving request is NOT
prefilled in a whole-prompt stall. It is admitted as a *chunk task*: each
scan step of the fused program processes the mixed batch of decode lanes
plus at most one ``prefill_chunk``-token prompt chunk for the admitted
lane (``models.model.prefill_chunk_step``), so live lanes keep emitting
tokens while the newcomer's KV fills in. The final chunk samples the
request's first token *inside the scan* and flips the lane live — first
tokens arrive in-band through the same one-``device_get``-per-block
fetch. Idle engines (no live lanes) keep the batched whole-prompt prefill
path, which is strictly faster when there is nothing to stall.

The KV cache has two layouts (DESIGN.md §3). The default dense layout
gives each slot a linear ``max_len`` region, so memory is
``n_slots x max_len`` regardless of what the slots hold. ``paged=True``
switches the same fused loop onto the block-table paged store: prompt K/V
is bulk-written into pages at prefill, in-loop appends write through a
device block table (dead lanes redirected to a dropped out-of-bounds
page), and decode attention runs the Pallas paged kernel
(kernels/paged_attention.py) or its XLA reference per ``paged_impl``.
Memory then scales with *live tokens*, and admission is governed by a
page budget: a request occupies a slot only while its worst-case page
reservation — derived from its (directive-level-selected) token budget —
fits, so brief-directive traffic packs more concurrent requests into the
same HBM. ``kv_int8=True`` stores pages as int8 with per-token-per-head
scales, halving decode HBM traffic end to end.

``prefix_cache=True`` (paged only) additionally turns the page store into
a **radix prefix cache** (DESIGN.md §13): full prompt pages are
content-hashed and shared across requests. Admission consults the index
first — a hit maps the cached pages into the new slot's block table (zero
prefill FLOPs and zero new pages for the shared span) and the request is
admitted as a chunk task whose prompt streaming *starts at the first
uncached token*; divergent appends into a shared page go through
copy-on-write before the write lands, so the fused scan programs are
untouched and token streams stay bit-identical to the cache-off engine
under greedy sampling. Admission's reservation is prefix-aware
(``_pages_for`` subtracts adopted pages, plus one for a potential COW)
and the gate counts *pinned* shared pages so a page is paid for once,
never per adopter.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as MD
from repro.models.common import ModelConfig
from repro.serving import spans
from repro.serving.kv_cache import PageAllocator
from repro.serving.sampler import (SamplingParams, greedy_sample,
                                   sample_logits_batched,
                                   sample_temperature_only)
from repro.serving.tokenizer import ByteTokenizer


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _mark_first_token(st: "RequestState") -> None:
    """TTFT split at admission: queue wait (scheduler submit to slot) and
    prefill (slot to first token), host microseconds; they sum to
    ``FinishedRequest.ttft_s``."""
    spans.mark(spans.FIRST_TOKEN, rid=st.rid,
               wait_us=1e6 * (st.t_admit - st.t_submit),
               prefill_us=1e6 * (st.t_first_token - st.t_admit))


@dataclasses.dataclass
class RequestState:
    rid: int
    prompt_ids: List[int]
    max_new_tokens: int
    sampling: SamplingParams
    directive_level: int = 0
    slot: int = -1
    generated: List[int] = dataclasses.field(default_factory=list)
    prompt_len: int = 0
    done: bool = False
    # t_submit is the ORIGINAL submission time: preserved across failover
    # requeue and cross-pool migration (engine.submit accepts it), so
    # latency — and therefore deadline attainment — is measured end to end
    # including any redo, not per-engine
    t_submit: float = 0.0
    # when the request last took a slot (monotonic): the first-token mark
    # splits TTFT at it into queue wait and prefill
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    # SLO identity: the tenant service class, dispatch priority, and the
    # absolute completion deadline (monotonic clock; inf = no deadline).
    # Carried through requeue/migration unchanged and reported on the
    # FinishedRequest.
    tenant: str = ""
    deadline_at: float = float("inf")
    priority: int = 1
    # fault-recovery bookkeeping (DESIGN.md §12): how many fault-caused
    # requeues this request has survived, and what the last fault was
    # ("decode.nonfinite", "replica.crash", ...). Carried through the
    # verbatim requeue path and reported on the FinishedRequest so the
    # chaos suite can assert bounded retries end to end.
    retries: int = 0
    last_fault: str = ""
    # decode-only device seconds attributed to THIS request: each warm
    # decode block's wall time is partitioned per step across the slots
    # that decoded in it, so summed attribution equals device time (the
    # property energy accounting needs); compile dispatches charge nothing
    decode_s: float = 0.0
    # paged admission: the exact page count this request was charged at
    # admission. With prefix-aware reservations the charge depends on
    # cache state at admission time, so every release site must repay
    # this stored amount — a recompute would drift (DESIGN.md §13)
    reserved_pages: int = 0
    # prompt tokens served from the radix prefix cache (prefill skipped);
    # reported on FinishedRequest so Eq. 1 accounting can credit them
    cached_tokens: int = 0


@dataclasses.dataclass
class _ChunkTask:
    """An admitted-but-still-prefilling request: its prompt streams into
    the fused scan ``chunk`` tokens per step while other lanes decode.
    ``next`` is the first prompt position not yet dispatched — it starts
    at the first *uncached* token when a prefix hit adopted pages, so the
    shared span is never recomputed."""
    slot: int
    ids: List[int]
    plen: int
    next: int = 0
    chunk: int = 0


@dataclasses.dataclass
class FinishedRequest:
    rid: int
    token_ids: List[int]
    text: str
    prompt_tokens: int
    gen_tokens: int
    ttft_s: float
    latency_s: float
    directive_level: int
    decode_s: float = 0.0   # decode-only seconds attributed to this request
    tenant: str = ""        # SLO service class ("" = untagged)
    deadline_at: float = float("inf")   # absolute deadline (monotonic)
    t_done: float = 0.0     # finish time (monotonic) for attainment checks
    retries: int = 0        # fault-caused requeues survived (DESIGN.md §12)
    cached_tokens: int = 0  # prompt tokens served from the prefix cache

    @property
    def slo_met(self) -> bool:
        """Did this request finish by its deadline? (True when untagged.)"""
        return self.t_done <= self.deadline_at


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 4,
                 max_len: int = 256, eos_id: int = ByteTokenizer.EOS,
                 tokenizer: Optional[ByteTokenizer] = None, seed: int = 0,
                 decode_block: int = 8, paged: bool = False,
                 page_size: int = 32, n_pages: Optional[int] = None,
                 kv_int8: bool = False, paged_impl: str = "auto",
                 prefill_chunk: int = 0, prefix_cache: bool = False,
                 tp_degree: int = 1):
        assert cfg.family in ("dense", "moe", "hybrid", "ssm", "vlm"), \
            f"serving engine drives decoder-style models, got {cfg.family}"
        assert decode_block >= 1
        if kv_int8:
            # params are dtype-independent of the cache; only cache init and
            # the decode read/write paths consult kv_cache_dtype
            cfg = cfg.replace(kv_cache_dtype="int8")
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.decode_block = decode_block
        self.paged = paged
        self.paged_impl = paged_impl
        self.prefill_chunk = prefill_chunk
        # chunked prefill serves the same stacks as paged decode; anything
        # else silently keeps the whole-prompt path (callers need not care)
        self._chunked_ok = (prefill_chunk > 0
                            and MD.chunked_prefill_supported(cfg))
        # the radix prefix cache rides the paged store, and a prefix hit
        # is always served through the chunk program (the uncached suffix
        # must attend over adopted pages) — so it requires both; anything
        # else silently keeps plain paging
        self._prefix_ok = (paged and prefix_cache
                           and MD.chunked_prefill_supported(cfg))
        self.prefix_cache = self._prefix_ok
        self.prefill_tokens_computed = 0   # prompt tokens actually prefilled
        self.prefill_tokens_cached = 0     # prompt tokens served from cache
        # batch bucketing changes the decode batch shape; MoE expert
        # capacity is batch-shape-dependent, so MoE stacks pin bs=n_slots
        self._bucketing = cfg.n_experts == 0
        self._task: Optional[_ChunkTask] = None
        self.tok = tokenizer or ByteTokenizer()
        self.key = jax.random.PRNGKey(seed)

        if paged:
            assert MD.paged_supported(cfg), \
                f"paged decode unsupported for {cfg.name}"
            max_pages = (max_len + page_size - 1) // page_size
            # default budget: the dense layout's worst-case footprint, so
            # paged-vs-dense comparisons start from equal HBM
            n_pages = n_pages if n_pages is not None else n_slots * max_pages
            self.pages = PageAllocator(n_pages=n_pages, page_size=page_size,
                                       n_slots=n_slots, max_len=max_len,
                                       prefix_cache=self._prefix_ok,
                                       kv_salt=cfg.kv_cache_dtype)
            # page-budget admission state: sum of slotted requests'
            # admission-time reservations. The exact charge is stored on
            # the state (RequestState.reserved_pages) because prefix-aware
            # reservations depend on cache contents at admission — a
            # release-time recompute would drift the ledger. The standing
            # invariant is _committed + pages.pinned <= n_pages: every
            # page a slotted request can ever demand is covered by its own
            # reservation or already active-and-unowned (pinned), so
            # mid-decode page growth can never hit MemoryError
            self.cache = MD.init_paged_cache(cfg, n_pages, page_size)
            self._committed = 0
        else:
            self.pages = None
            self.cache = MD.init_cache(cfg, n_slots, max_len)
        # Tensor-parallel decode (DESIGN.md §14): shard params and the KV
        # store over the mesh "model" axis and let SPMD propagation carry
        # the shardings through the unchanged fused programs. Only the
        # *placement* changes — params/cache are device_put under the
        # ShardSpec and entry-point names gain a _tp{T} suffix so bucketed
        # programs compiled for different meshes never collide. tp_degree=1
        # is byte-identical to the pre-TP engine (no mesh is built).
        assert tp_degree >= 1
        self.tp_degree = tp_degree
        if tp_degree > 1:
            from repro.launch.mesh import make_tp_mesh
            from repro.launch.sharding import serving_shard_spec
            mesh = make_tp_mesh(tp_degree)
            self.shard_spec = serving_shard_spec(
                cfg, mesh, self.params, self.cache, paged=paged)
            self.params = jax.device_put(self.params, self.shard_spec.params)
            self.cache = jax.device_put(self.cache, self.shard_spec.cache)
        else:
            self.shard_spec = None
        self._tp_suffix = self.shard_spec.suffix if self.shard_spec else ""
        self.slots: List[Optional[RequestState]] = [None] * n_slots
        # host mirrors of the device decode state (scheduling decisions
        # only; pushed to device per block, refreshed from the block fetch)
        self.positions = np.zeros(n_slots, np.int64)   # next position per slot
        self.last_token = np.zeros(n_slots, np.int64)
        self.live = np.zeros(n_slots, bool)
        self.gen_count = np.zeros(n_slots, np.int64)
        self.max_new = np.ones(n_slots, np.int64)
        self.temp = np.zeros(n_slots, np.float32)
        self.top_k = np.zeros(n_slots, np.int64)
        self.top_p = np.ones(n_slots, np.float32)
        self.queue: List[RequestState] = []
        self.finished: List[FinishedRequest] = []
        # lanes quarantined for non-finite logits (or poisoned by fault
        # injection): the scheduler harvests these each step and requeues
        # them over the verbatim-token path under its retry budget
        self.faulted: List[RequestState] = []
        # high-water marks, sampled at maximal residency inside step() —
        # after prefill admission / page growth, BEFORE same-step finishes
        # release slots and pages (a post-step observer would undercount
        # requests that are admitted and complete within one block)
        self.peak_concurrent = 0
        self.peak_pages_in_use = 0
        self.steps = 0
        self.decode_tokens = 0
        self.decode_syncs = 0          # host round trips on the decode path
        self.last_decode_s = 0.0       # decode-only wall time, last dispatch
        self.chunk_steps = 0           # prompt chunks streamed into the scan
        self.pages_grown_chunked = 0   # pages mapped per-chunk, not at insert
        self._next_rid = 1000

        def _prefill(params, tokens, lengths):
            logits, cache, _ = MD.prefill(cfg, params, tokens,
                                          max_len=self.max_len,
                                          lengths=lengths)
            # last valid position's logits
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
            return last, cache

        self._prefill_jit = jax.jit(_prefill)   # retraces per (nb, plen)

        def _insert(batch_cache, one_cache, slots):
            # one tree-level donated update for the whole layer stack:
            # every cache leaf is (n_layers, batch, ...), so scattering the
            # prefill rows into their slots along axis 1 covers all layers
            # of all segments in a single program
            return jax.tree.map(
                lambda full, one: full.at[:, slots].set(one.astype(full.dtype)),
                batch_cache, one_cache)

        self._insert_jit = jax.jit(_insert, donate_argnums=(0,))

        def _paged_insert(cache, one_cache, page_ids, offs):
            # bulk-write prompt K/V into pages: one scatter per tree leaf
            # for the whole bucketed prefill batch. page_ids/offs are
            # (nb, T) with over-length / pad entries pointing at the
            # out-of-bounds page id (scatter drops them). kpos has no paged
            # counterpart — validity is positional (index < length).
            T = page_ids.shape[1]
            out = []
            for seg_full, seg_one in zip(cache, one_cache):
                d = dict(seg_full)
                for name in seg_full:
                    d[name] = seg_full[name].at[:, page_ids, offs].set(
                        seg_one[name][:, :, :T].astype(seg_full[name].dtype))
                out.append(d)
            return out

        self._paged_insert_jit = jax.jit(_paged_insert, donate_argnums=(0,))

        def _fill_slot(cache, slot, value):
            # constant-fill one dense lane's float leaves (poison = NaN,
            # scrub = 0.0 — both values are traced operands, so the two
            # uses share ONE compiled program). Int leaves (kpos, int8
            # K/V) are left alone: non-finiteness rides the float scales.
            return jax.tree.map(
                lambda a: (a.at[:, slot].set(value.astype(a.dtype))
                           if jnp.issubdtype(a.dtype, jnp.floating) else a),
                cache)

        self._fill_slot_jit = jax.jit(_fill_slot, donate_argnums=(0,))

        def _fill_pages(cache, page_ids, value):
            # constant-fill the given pages across every layer/segment of
            # the paged store; out-of-bounds ids (unmapped table entries,
            # sanitized host-side) scatter-drop. Float leaves only.
            out = []
            for seg in cache:
                d = dict(seg)
                for nm in seg:
                    if jnp.issubdtype(seg[nm].dtype, jnp.floating):
                        d[nm] = seg[nm].at[:, page_ids].set(
                            value.astype(seg[nm].dtype))
                out.append(d)
            return out

        self._fill_pages_jit = jax.jit(_fill_pages, donate_argnums=(0,))

        def _copy_page(cache, src, dst):
            # copy-on-write support (DESIGN.md §13): duplicate one page's
            # contents onto a fresh page across every layer/segment and
            # EVERY leaf — int8 K/V and their scales included, the copy
            # must be bit-exact — before a divergent write lands in it
            out = []
            for seg in cache:
                d = dict(seg)
                for nm in seg:
                    d[nm] = seg[nm].at[:, dst].set(seg[nm][:, src])
                out.append(d)
            return out

        self._copy_page_jit = jax.jit(_copy_page, donate_argnums=(0,))
        # compiled entry-point table (SHARK-Engine style function tables):
        # "decode_bs{N}_k{K}_{mode}" / "mixed_bs{N}_k{K}_c{C}_{mode}" fused
        # programs plus "prefill_bs{N}_p{P}" whole-prompt shapes. The bench
        # warmup drives every variant it will measure and asserts the table
        # does not grow inside a measured window (warm paths only).
        self.entry_points: Dict[str, Callable] = {}

    # ------------------------------------------------------------------
    def submit(self, prompt_ids: List[int], *, max_new_tokens: int = 64,
               sampling: Optional[SamplingParams] = None,
               directive_level: int = 0, rid: Optional[int] = None,
               tenant: str = "", deadline_at: float = float("inf"),
               priority: int = 1, t_submit: Optional[float] = None,
               retries: int = 0, last_fault: str = "") -> int:
        # fresh default per call — a def-time SamplingParams() default would
        # be one shared instance across every default-submitted request
        sampling = sampling if sampling is not None else SamplingParams()
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if max_new_tokens + 1 >= self.max_len:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} leaves no room for a prompt "
                f"in a max_len={self.max_len} KV region; need "
                f"max_new_tokens + 1 < max_len")
        if not prompt_ids:
            raise ValueError("empty prompt")
        if self.paged:
            need = self._pages_for(
                len(prompt_ids[: self.max_len - max_new_tokens - 1]),
                max_new_tokens)
            if need > self.pages.n_pages:
                raise ValueError(
                    f"request needs {need} pages > page budget "
                    f"{self.pages.n_pages} (page_size="
                    f"{self.pages.page_size})")
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        st = RequestState(rid, list(prompt_ids), max_new_tokens, sampling,
                          directive_level,
                          t_submit=(time.monotonic() if t_submit is None
                                    else t_submit),
                          tenant=tenant, deadline_at=deadline_at,
                          priority=priority, retries=retries,
                          last_fault=last_fault)
        self.queue.append(st)
        return rid

    # ------------------------------------------------------------------
    def load(self) -> int:
        """In-flight work: queued requests + occupied slots. The load
        signal shared by scheduler dispatch and gateway routing."""
        return len(self.queue) + sum(s is not None for s in self.slots)

    @property
    def chunked_admission(self) -> bool:
        """True when this engine admits new work by streaming prompt
        chunks into the live decode scan (no whole-prompt stall): the
        signal the gateway's predicted-completion model and the
        scheduler's dispatch ordering key on."""
        return self._chunked_ok

    # ------------------------------------------------------------------
    @staticmethod
    def _bucket(n: int) -> int:
        return max(16, _next_pow2(n))

    def _slot_cap(self, prompt_len: int, max_new: int) -> int:
        """Most tokens a request can ever write into its KV region (prompt
        + generated-minus-last, under the max_len-2 position cap), plus one
        page-rounding-safe token of slack. The SINGLE cap expression both
        the admission reservation and per-block page growth derive from —
        the no-MemoryError-mid-decode invariant is that growth never
        exceeds the reservation, i.e. this function."""
        return min(prompt_len + max_new, self.max_len - 1)

    def _pages_for(self, prompt_len: int, max_new: int,
                   cached_pages: int = 0, cow_pages: int = 0) -> int:
        """Worst-case page reservation for a request — the admission unit.
        Directive-aware by construction: ``max_new`` is the budget the
        drawn directive level selected, so L2-brief requests reserve few
        pages and more of them fit a fixed page budget. Prefix-aware on
        top (DESIGN.md §13): adopted cached pages cost nothing new and are
        subtracted; a fully-cached page-aligned prompt adds ``cow_pages``
        (one) back for the copy-on-write its 1-token recompute triggers."""
        return (self.pages.pages_needed(self._slot_cap(prompt_len, max_new))
                - cached_pages + cow_pages)

    def _try_prefill(self) -> None:
        """Fill free slots from the queue, batching prefill per padded
        bucket length instead of strictly batch-1. In paged mode a request
        is admitted only while its worst-case page reservation fits the
        remaining budget (FIFO — admission never reorders the queue), so
        concurrency is bounded by live-token demand, not slot count.

        With chunked prefill enabled and decode lanes live, admission goes
        through the chunk task instead: one request at a time streams its
        prompt into the fused scan and the live lanes never stall. The
        whole-prompt path below only runs on an otherwise-idle engine,
        where a batched prefill is strictly faster than chunking."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free or not self.queue:
            return
        if self._prefix_ok:
            # a prefix HIT is admitted through the chunk task even on an
            # otherwise-idle engine: the uncached suffix must attend over
            # the adopted pages, which only the chunk program's
            # block-table reads can do — the whole-prompt batch prefill
            # recomputes from position 0 and would forfeit the hit. FIFO:
            # if the single task lane is busy, the head waits
            head = self.queue[0]
            ids0 = head.prompt_ids[: self.max_len - head.max_new_tokens - 1]
            if self.pages.match_prefix(ids0)[0] > 0:
                if self._task is None:
                    self._admit_chunk_task(free[0])
                return
        if self._chunked_ok and self.live.any():
            if self._task is None:
                self._admit_chunk_task(free[0])
            return
        taken: List[Tuple[int, RequestState, List[int]]] = []
        for slot in free:
            if not self.queue:
                break
            st = self.queue[0]
            # submit() guarantees max_len - max_new_tokens - 1 >= 1, so the
            # truncated prompt is never empty
            ids = st.prompt_ids[: self.max_len - st.max_new_tokens - 1]
            if self._prefix_ok and self.pages.match_prefix(ids)[0] > 0:
                break     # hits admit via the chunk task on a later call
            if self.paged:
                need = self._pages_for(len(ids), st.max_new_tokens)
                # pinned = shared pages charged to no reservation; they
                # are as occupied as committed ones (zero for plain paged
                # engines, so the historical gate is unchanged)
                if (self._committed + need + self.pages.pinned
                        > self.pages.n_pages):
                    break              # wait for pages to free up
                self._committed += need
                st.reserved_pages = need
            self.queue.pop(0)
            st.prompt_len = len(ids)
            taken.append((slot, st, ids))
        groups: Dict[int, List[Tuple[int, RequestState, List[int]]]] = {}
        for slot, st, ids in taken:
            plen = min(self._bucket(len(ids)), self.max_len)
            groups.setdefault(plen, []).append((slot, st, ids))
        for plen, grp in groups.items():
            with spans.span(spans.ENGINE_PREFILL, bs=len(grp), plen=plen):
                self._prefill_group(plen, grp)

    def _prefill_group(self, plen: int,
                       grp: List[Tuple[int, RequestState, List[int]]]) -> None:
        t_admit = time.monotonic()
        for _, st, _ in grp:
            st.t_admit = t_admit
        # pad the batch to a power of two so prefill/insert trace at most
        # log2(n_slots)+1 shapes; pad rows scatter to slot index n_slots,
        # which is out of bounds and therefore dropped by the insert
        nb = len(grp)
        npad = _next_pow2(nb)
        toks = np.zeros((npad, plen), np.int32)
        lengths = np.ones(npad, np.int32)
        temps = np.zeros(npad, np.float32)
        topks = np.zeros(npad, np.int32)
        topps = np.ones(npad, np.float32)
        slots = np.full(npad, self.n_slots, np.int32)
        for b, (slot, st, ids) in enumerate(grp):
            toks[b, : len(ids)] = ids
            lengths[b] = len(ids)
            temps[b] = st.sampling.temperature
            topks[b] = st.sampling.top_k
            topps[b] = st.sampling.top_p
            slots[b] = slot
        prefill_fn = self.entry_points.setdefault(
            f"prefill_bs{npad}_p{plen}{self._tp_suffix}", self._prefill_jit)
        logits, one_cache = prefill_fn(
            self.params, jnp.asarray(toks), jnp.asarray(lengths))
        self.key, sk = jax.random.split(self.key)
        # sproutlint: allow(SPL001) — the one sanctioned sync per prefill
        # group; budget lives in repro.analysis.config.ALLOWLIST
        firsts = np.asarray(jax.device_get(sample_logits_batched(
            logits, sk, jnp.asarray(temps), jnp.asarray(topks),
            jnp.asarray(topps))))
        if self.paged:
            # map prompt tokens onto pages: allocate per slot (host), then
            # one donated scatter writes the whole bucket through the table
            ps = self.pages.page_size
            P = self.pages.n_pages
            page_ids = np.full((npad, plen), P, np.int32)    # OOB = dropped
            offs = np.zeros((npad, plen), np.int32)
            for b, (slot, st, ids) in enumerate(grp):
                self.pages.ensure_capacity(slot, len(ids))
                self.pages.lengths[slot] = len(ids)
                t = np.arange(len(ids))
                page_ids[b, : len(ids)] = \
                    self.pages.block_table[slot, t // ps]
                offs[b, : len(ids)] = t % ps
            self.cache = self._paged_insert_jit(
                self.cache, one_cache, jnp.asarray(page_ids),
                jnp.asarray(offs))
            if self._prefix_ok:
                # index the freshly written full prompt pages so later
                # requests sharing this prefix adopt instead of recompute
                for slot, st, ids in grp:
                    self.pages.register_prefix(slot, ids)
        else:
            self.cache = self._insert_jit(self.cache, one_cache,
                                          jnp.asarray(slots))
        for _, _, ids in grp:
            self.prefill_tokens_computed += len(ids)
        t_first = time.monotonic()
        for b, (slot, st, _) in enumerate(grp):
            first = int(firsts[b])
            st.slot = slot
            st.generated = [first]
            st.t_first_token = t_first
            _mark_first_token(st)
            self.slots[slot] = st
            self.positions[slot] = st.prompt_len
            self.last_token[slot] = first
            self.gen_count[slot] = 1
            self.max_new[slot] = st.max_new_tokens
            self.temp[slot] = st.sampling.temperature
            self.top_k[slot] = st.sampling.top_k
            self.top_p[slot] = st.sampling.top_p
            alive = (first != self.eos_id
                     and st.max_new_tokens > 1
                     and st.prompt_len + 1 < self.max_len - 1)
            self.live[slot] = alive
            if not alive:
                self._finish(slot)

    def _admit_chunk_task(self, slot: int) -> None:
        """Admit queue head into ``slot`` as a chunk task: host mirrors are
        pre-staged (positions at prompt_len, lane dead) and the prompt is
        streamed into the fused scan by subsequent ``step()`` dispatches.
        The lane flips live — and the first token emits — inside the scan
        when the final chunk lands."""
        st = self.queue[0]
        ids = st.prompt_ids[: self.max_len - st.max_new_tokens - 1]
        cached_tokens = 0
        adopted: List[int] = []
        newly_pinned = 0
        cow = 0
        if self._prefix_ok:
            m, pids, newly_pinned = self.pages.match_prefix(ids)
            if m > 0:
                # adopt every matched full page, but always leave >= 1
                # prompt token to compute: the final prompt token's logits
                # seed the first sampled token, so a fully cached
                # page-aligned prompt still streams a 1-token chunk —
                # whose KV write lands INSIDE the last shared page and
                # triggers the copy-on-write budgeted below
                cached_tokens = min(m * self.pages.page_size, len(ids) - 1)
                cow = 1 if m * self.pages.page_size > cached_tokens else 0
                adopted = pids
        if self.paged:
            need = self._pages_for(len(ids), st.max_new_tokens,
                                   cached_pages=len(adopted), cow_pages=cow)
            # shared pages are paid for exactly once: active-but-unowned
            # (pinned) pages, plus the cached pages THIS adoption would
            # pin, join the committed reservations on the left of the gate
            if (self._committed + need + self.pages.pinned + newly_pinned
                    > self.pages.n_pages):
                return             # wait for pages to free up (FIFO)
            self._committed += need
            st.reserved_pages = need
        self.queue.pop(0)
        st.t_admit = time.monotonic()
        st.prompt_len = len(ids)
        st.slot = slot
        st.generated = []
        if adopted:
            self.pages.adopt(slot, adopted)
            self.pages.lengths[slot] = cached_tokens
            st.cached_tokens = cached_tokens
            self.prefill_tokens_cached += cached_tokens
        self.prefill_tokens_computed += len(ids) - cached_tokens
        self.slots[slot] = st
        self.positions[slot] = len(ids)
        self.last_token[slot] = 0
        self.live[slot] = False
        self.gen_count[slot] = 0
        self.max_new[slot] = st.max_new_tokens
        self.temp[slot] = st.sampling.temperature
        self.top_k[slot] = st.sampling.top_k
        self.top_p[slot] = st.sampling.top_p
        # prefix engines admit through this path with prefill_chunk == 0:
        # they stream page_size-token chunks (one full page per scan step)
        self._task = _ChunkTask(slot=slot, ids=ids, plen=len(ids),
                                next=cached_tokens,
                                chunk=(self.prefill_chunk
                                       or self.pages.page_size))

    # ------------------------------------------------------------------
    def _finish(self, slot: int) -> None:
        st = self.slots[slot]
        assert st is not None
        st.done = True
        st.t_done = time.monotonic()
        gen = st.generated[:-1] if st.generated and st.generated[-1] == self.eos_id \
            else st.generated
        self.finished.append(FinishedRequest(
            st.rid, gen, self.tok.decode(gen), st.prompt_len, len(gen),
            st.t_first_token - st.t_submit, st.t_done - st.t_submit,
            st.directive_level, st.decode_s, st.tenant, st.deadline_at,
            st.t_done, st.retries, st.cached_tokens))
        self.slots[slot] = None
        self.live[slot] = False
        if self.paged:
            # decref, not free: shared pages survive their co-holders, and
            # the ledger repays exactly the admission-time charge
            self.pages.release(slot)
            self._committed -= st.reserved_pages

    # ------------------------------------------------------------------
    _SAMPLE_FNS = {"greedy": greedy_sample,
                   "temp": sample_temperature_only,
                   "full": sample_logits_batched}

    def _fused_for(self, k: int, mode: str, bs: int,
                   chunk_c: int) -> Tuple[Callable, bool]:
        """Jitted device-resident loop: k fused decode+sample steps over a
        ``bs``-row bucket, optionally interleaving one ``chunk_c``-token
        prompt chunk per step. Returns (entry point, was-already-warm).

        ``mode`` is a host-side static specialization over the bucket's
        sampling params: "greedy" compiles no sampler at all, "temp"
        (temperature only) skips the sort-based top-k/top-p threshold, and
        "full" carries the lot. All variants split the PRNG key per step
        and fold per slot id, so the key stream — and the drawn tokens for
        any slot a cheaper variant is valid for — are identical across
        them and invariant to which bucket served the slot.

        ``bs < n_slots`` compiles the *bucketed* program: every dense
        cache leaf is gathered on its slot axis (axis 1) once on entry and
        scattered back once on exit — pad rows carry slot id ``n_slots``,
        whose gather clamps harmlessly (the lane is forced dead) and whose
        scatter-back is out of bounds and dropped. At ``bs == n_slots``
        no gather is compiled: the identity program is the fixed-batch
        fused loop unchanged. Paged caches are never gathered (the page
        store is slot-agnostic; the host selects block-table rows).

        ``chunk_c > 0`` compiles the *mixed* program: each scan step
        additionally runs ``prefill_chunk_step`` for the task lane's next
        chunk (scan xs), and on the final chunk samples the request's
        first token in-scan, flips the lane live, and emits it in-band."""
        name = (f"decode_bs{bs}_k{k}_{mode}" if chunk_c == 0
                else f"mixed_bs{bs}_k{k}_c{chunk_c}_{mode}")
        name += self._tp_suffix
        warm = name in self.entry_points
        if not warm:
            cfg, eos_id, max_len = self.cfg, self.eos_id, self.max_len
            draw = self._SAMPLE_FNS[mode]

            def sample_fn(*args, **kwargs):
                with jax.named_scope(spans.SAMPLER):
                    return draw(*args, **kwargs)

            paged, paged_impl = self.paged, self.paged_impl
            bucketed = bs < self.n_slots
            has_chunk = chunk_c > 0

            def fused(params, cache, block_table, state, chunk):
                rows = state["rows"]          # (bs,) slot ids; pad = n_slots
                if bucketed and not paged:
                    part = jax.tree.map(lambda a: a[:, rows], cache)
                else:
                    part = cache
                fold = rows if bucketed else None

                def body(carry, xs):
                    part, st = carry
                    if has_chunk:
                        key, sk, ck = jax.random.split(st["key"], 3)
                    else:
                        key, sk = jax.random.split(st["key"])
                    nxt, part, rowok = MD.decode_sample_step(
                        cfg, params, st["last"][:, None], st["pos"], part,
                        sk, (st["temp"], st["topk"], st["topp"]),
                        sample_fn,
                        block_table=block_table if paged else None,
                        live=st["live"] if paged else None,
                        paged_impl=paged_impl, fold_ids=fold, with_ok=True)
                    # sticky per-lane health: once a LIVE lane's logits go
                    # non-finite the verdict stays False for the block (dead
                    # lanes' logits are scratch and don't count) — the host
                    # quarantines the lane from the existing block fetch
                    ok2 = st["ok"] & (rowok | ~st["live"])
                    nxt = jnp.where(st["live"], nxt, st["last"]).astype(jnp.int32)
                    pos2 = jnp.where(st["live"], st["pos"] + 1, st["pos"])
                    gc2 = jnp.where(st["live"], st["gc"] + 1, st["gc"])
                    # same finish rule as the host bookkeeping: EOS, token
                    # budget, or KV-region cap (prompt_len + gen >= max_len-1)
                    hit = ((nxt == eos_id) | (gc2 >= st["max_new"])
                           | (pos2 >= max_len - 2))
                    live2 = st["live"] & ~hit
                    emit_t, emit_v = nxt, st["live"]
                    if has_chunk:
                        ctoks, cpos0, clen, cfinal = xs
                        lane = st["chunk_lane"]
                        with jax.named_scope(spans.PREFILL_CHUNK):
                            logits, part = MD.prefill_chunk_step(
                                cfg, params, ctoks, cpos0, clen, lane, part,
                                block_table=block_table if paged else None)
                        # chunk-lane health: any dispatched chunk (clen > 0)
                        # with non-finite logits marks the lane bad — its
                        # half-written KV is garbage even before it samples
                        cok = (clen == 0) | jnp.isfinite(logits).all()
                        ok2 = ok2 & (cok | (jnp.arange(bs) != lane))
                        first = sample_fn(
                            logits[None], ck, st["temp"][lane][None],
                            st["topk"][lane][None], st["topp"][lane][None],
                            fold_ids=rows[lane][None])[0].astype(jnp.int32)
                        plen = cpos0 + clen
                        alive = ((first != eos_id)
                                 & (st["max_new"][lane] > 1)
                                 & (plen + 1 < max_len - 1))
                        upd = (jnp.arange(bs) == lane) & cfinal
                        nxt = jnp.where(upd, first, nxt)
                        pos2 = jnp.where(upd, plen, pos2)
                        gc2 = jnp.where(upd, 1, gc2)
                        live2 = jnp.where(upd, alive, live2)
                        emit_t = jnp.where(upd, first, emit_t)
                        emit_v = emit_v | upd
                    st2 = dict(st, key=key, last=nxt, pos=pos2, gc=gc2,
                               live=live2, ok=ok2)
                    return (part, st2), (emit_t, emit_v)

                (part, st), (toks, valid) = jax.lax.scan(
                    body, (part, state), chunk if has_chunk else None,
                    length=k, unroll=1 if has_chunk else min(k, 8))
                if bucketed and not paged:
                    cache = jax.tree.map(
                        lambda full, p_: full.at[:, rows].set(
                            p_.astype(full.dtype)),
                        cache, part)
                else:
                    cache = part
                return cache, toks, valid, st["live"], st["ok"]

            # the block table is a fresh tiny input per dispatch (the host
            # allocator owns it), so it is NOT donated; the cache is
            self.entry_points[name] = jax.jit(fused, donate_argnums=(1,))
        return self.entry_points[name], warm

    def _pick_k(self) -> int:
        """Block length: the power-of-two ceiling of the soonest
        *deterministic* finish (token budget / KV cap), capped at
        ``decode_block``. Steps past a slot's finish run dead (live-masked,
        nothing emitted), trading < rem wasted lockstep steps for fewer
        dispatches and at most log2(decode_block)+1 compiled variants;
        prefill of freed slots runs between blocks, so its delay is bounded
        by the same overshoot."""
        live_idx = np.nonzero(self.live)[0]
        rem = int(min(
            min(self.max_new[i] - self.gen_count[i],
                self.max_len - 1 - (self.positions[i] + 1))
            for i in live_idx))
        return min(self.decode_block, _next_pow2(max(1, rem)))

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One continuous-batching dispatch: refill free slots (chunk-task
        admission when lanes are live, bucketed batch prefill when idle),
        then run up to ``decode_block`` fused scan steps — each decoding
        every live lane and streaming at most one prompt chunk — in the
        compiled entry point bucketed to live occupancy. Returns the
        number of tokens emitted (0 if idle)."""
        with spans.span(spans.ENGINE_STEP, lanes=int(self.live.sum())):
            with spans.span(spans.ENGINE_ADMIT, queued=len(self.queue)):
                self._try_prefill()
            self.peak_concurrent = max(
                self.peak_concurrent, sum(s is not None for s in self.slots))
            if self.paged:
                self.peak_pages_in_use = max(self.peak_pages_in_use,
                                             self.pages.pages_in_use())
            task = self._task
            if not self.live.any() and task is None:
                return 0
            with spans.span(spans.ENGINE_PREPARE):
                # ----- bucket selection: live lanes plus the chunk-task lane -
                lanes = set(int(i) for i in np.nonzero(self.live)[0])
                if task is not None:
                    lanes.add(task.slot)
                rows = np.sort(np.fromiter(lanes, np.int64))
                bs = (min(self.n_slots, _next_pow2(len(rows)))
                      if self._bucketing else self.n_slots)
                if bs == self.n_slots:
                    rows_full = np.arange(self.n_slots, dtype=np.int64)
                else:
                    rows_full = np.full(bs, self.n_slots, np.int64)
                    rows_full[: len(rows)] = rows
                # ----- block length + per-step chunk arrays ------------------
                k = self._pick_k() if self.live.any() else self.decode_block
                chunk_c = 0
                chunk_xs = None
                finishing = False
                nxt_p = 0
                if task is not None:
                    chunk_c = task.chunk
                    rem = -(-(task.plen - task.next) // chunk_c)
                    # shrink the block toward the chunks actually left, so a
                    # short tail does not pay (and a fresh variant does not
                    # compile for) a full block of dead chunk steps
                    k = max(1, min(k, _next_pow2(rem)))
                    ctoks = np.zeros((k, chunk_c), np.int32)
                    cpos0 = np.zeros(k, np.int32)
                    clen = np.zeros(k, np.int32)
                    cfin = np.zeros(k, bool)
                    nxt_p = task.next
                    for s in range(k):
                        if nxt_p < task.plen:
                            n = min(chunk_c, task.plen - nxt_p)
                            ctoks[s, :n] = task.ids[nxt_p:nxt_p + n]
                            cpos0[s] = nxt_p
                            clen[s] = n
                            cfin[s] = nxt_p + n == task.plen
                            nxt_p += n
                            self.chunk_steps += 1
                    finishing = nxt_p == task.plen
                    chunk_xs = (jnp.asarray(ctoks), jnp.asarray(cpos0),
                                jnp.asarray(clen), jnp.asarray(cfin))
                # greedy rows (temp<=0) draw via argmax and ignore top-k/top-p,
                # so only the *sampled* rows' params decide how much sampler to
                # compile; the chunk lane counts — its first token draws
                # in-scan
                consider = np.zeros(self.n_slots, bool)
                consider[rows] = True
                drawn = consider & (self.temp > 0)
                if not drawn.any():
                    mode = "greedy"
                elif np.any((self.top_k[drawn] > 0)
                            | (self.top_p[drawn] < 1.0)):
                    mode = "full"
                else:
                    mode = "temp"
                block_table = None
                if self.paged:
                    if self._prefix_ok:
                        # copy-on-write (DESIGN.md §13): a write landing in a
                        # shared or adopted page remaps the lane onto a fresh
                        # page and duplicates the contents device-side BEFORE
                        # this block's appends. In practice only the
                        # fully-cached page-aligned prompt case fires (its
                        # 1-token recompute writes into the last adopted page);
                        # the admission reservation budgeted that page, so
                        # _alloc cannot fail. The live-lane sweep is a
                        # belt-and-braces invariant — decode appends always
                        # land past the shared span
                        targets = [(int(i), int(self.positions[i]))
                                   for i in np.nonzero(self.live)[0]]
                        if task is not None:
                            targets.append((task.slot, task.next))
                        for tslot, tpos in targets:
                            cw = self.pages.prepare_append(tslot, tpos)
                            if cw is not None:
                                self.cache = self._copy_page_jit(
                                    self.cache, jnp.asarray(cw[0], jnp.int32),
                                    jnp.asarray(cw[1], jnp.int32))
                    # grow each live slot's page map to cover this block's
                    # appends (bounded by the slot's own cap, so growth never
                    # exceeds the admission-time reservation and can never
                    # throw here)
                    for i in np.nonzero(self.live)[0]:
                        st = self.slots[i]
                        self.pages.ensure_capacity(
                            int(i), min(int(self.positions[i]) + k,
                                        self._slot_cap(st.prompt_len,
                                                       st.max_new_tokens)))
                    if task is not None:
                        # per-chunk page growth: map only what this block
                        # writes (prompt chunks, plus up to k decode appends
                        # after an in-block transition) instead of the whole
                        # prompt at once
                        st = self.slots[task.slot]
                        cap = self._slot_cap(st.prompt_len, st.max_new_tokens)
                        tgt = min(nxt_p + (k if finishing else 0), cap)
                        self.pages_grown_chunked += self.pages.ensure_capacity(
                            task.slot, tgt)
                    self.peak_pages_in_use = max(self.peak_pages_in_use,
                                                 self.pages.pages_in_use())
                    bt = np.full((bs, self.pages.max_pages), -1, np.int32)
                    real = rows_full < self.n_slots
                    bt[real] = self.pages.block_table[rows_full[real]]
                    block_table = jnp.asarray(bt)
                # ----- bucketed device state (host mirrors, gathered) --------
                self.key, bk = jax.random.split(self.key)
                real = rows_full < self.n_slots

                def gath(a, fill, dtype):
                    out = np.full(bs, fill, dtype)
                    out[real] = a[rows_full[real]]
                    return jnp.asarray(out)

                state = {
                    "last": gath(self.last_token, 0, np.int32),
                    "pos": gath(self.positions, 0, np.int32),
                    "live": gath(self.live, False, bool),
                    "gc": gath(self.gen_count, 0, np.int32),
                    "max_new": gath(self.max_new, 1, np.int32),
                    "temp": gath(self.temp, 0.0, np.float32),
                    "topk": gath(self.top_k, 0, np.int32),
                    "topp": gath(self.top_p, 1.0, np.float32),
                    "key": bk,
                    "rows": jnp.asarray(rows_full, jnp.int32),
                    # per-lane finiteness verdict, accumulated across the
                    # block's scan steps (sticky-False once a live lane's
                    # logits go bad)
                    "ok": jnp.ones(bs, bool),
                }
                if task is not None:
                    lane_pos = int(np.nonzero(rows_full == task.slot)[0][0])
                    state["chunk_lane"] = jnp.asarray(lane_pos, jnp.int32)
                fn, warm = self._fused_for(k, mode, bs, chunk_c)
            t_dec = time.monotonic()
            with spans.span(spans.ENGINE_DISPATCH, bs=bs, k=k, mode=mode,
                            chunk=chunk_c, cold=int(not warm)):
                # a tp engine traces under its mesh, so the paged kernel (which
                # GSPMD cannot partition) finds the mesh to shard_map over
                with (jax.set_mesh(self.shard_spec.mesh) if self.shard_spec
                      else contextlib.nullcontext()):
                    self.cache, toks, valid, live_dev, ok_dev = fn(
                        self.params, self.cache, block_table, state, chunk_xs)
            with spans.span(spans.ENGINE_FETCH):
                # sproutlint: allow(SPL001) — the single host<->device sync
                # for this block of <= k*bs tokens; the per-lane finiteness
                # verdict rides the SAME fetch (no extra sync for fault
                # detection); budget in analysis.config.ALLOWLIST
                toks, valid, live_final, ok_final = jax.device_get(
                    (toks, valid, live_dev, ok_dev))
            with spans.span(spans.ENGINE_EMIT) as emit:
                # decode-only wall time for this dispatch; 0.0 when this
                # variant just compiled, so the straggler detector never
                # samples a compile
                self.last_decode_s = ((time.monotonic() - t_dec) if warm
                                      else 0.0)
                self.decode_syncs += 1
                self.steps += k
                finish_order: List[Tuple[int, int]] = []
                n_decoded = 0
                # partition each step's share of the block wall time across the
                # slots live at that step, so per-request decode_s sums to the
                # device's decode wall time (compile dispatches report 0.0);
                # dead tail steps (block overshoot past the last finish) have
                # no live slot, so their time is spread over the block's
                # decoding slots pro rata — nothing goes unattributed
                dt_step = self.last_decode_s / k
                live_steps = valid.sum(axis=1)                       # (k,)
                share = dt_step / np.maximum(live_steps, 1)
                dead_s = dt_step * int((live_steps == 0).sum())
                total_valid = max(int(valid.sum()), 1)
                for b, i in enumerate(int(x) for x in rows_full):
                    if i >= self.n_slots:
                        continue
                    st = self.slots[i]
                    if st is None:
                        continue
                    col = valid[:, b]
                    news = [int(t) for t in toks[col, b]]
                    st.decode_s += float(share[col].sum()) \
                        + dead_s * len(news) / total_valid
                    if not ok_final[b]:
                        # non-finite logits: every token this lane emitted in
                        # the block is suspect. Record them on the state (the
                        # wasted-work ledger charges discarded tokens) and
                        # quarantine — no finish, no mirror advance; the
                        # requeue path resets generation from the verbatim
                        # prompt.
                        st.generated.extend(news)
                        self._quarantine(i, "decode.nonfinite")
                        continue
                    st.generated.extend(news)
                    n_decoded += len(news)
                    self.decode_tokens += len(news)
                    self.gen_count[i] += len(news)
                    self.positions[i] += len(news)
                    if self.paged:    # live tokens in pages == positions
                        self.pages.lengths[i] = self.positions[i]
                    if news:
                        self.last_token[i] = news[-1]
                    self.live[i] = bool(live_final[b])
                    # a dead lane finishes only if it emitted this block: the
                    # chunk-task lane sits occupied-but-dead (col all False)
                    # until its final chunk flips it live in-scan
                    if not live_final[b] and news:
                        finish_order.append((int(np.nonzero(col)[0][-1]), i))
                # a quarantine above may have torn the chunk task down with its
                # lane (self._task reset, pages released) — only advance the
                # task if it is still the one we dispatched
                if task is not None and self._task is task:
                    i = task.slot
                    task.next = nxt_p
                    if finishing:
                        # the first token emitted from inside the scan: it
                        # lands at the pre-staged position, so it is not a
                        # position advance
                        self.positions[i] -= 1
                        st = self.slots[i]
                        if st is not None and st.t_first_token == 0.0:
                            st.t_first_token = time.monotonic()
                            _mark_first_token(st)
                        if st is not None and self._prefix_ok:
                            # the whole prompt's KV is now written: index its
                            # full pages for future prefix hits (adopted pages
                            # are already indexed and skip; first registration
                            # wins)
                            self.pages.register_prefix(i, task.ids)
                        self._task = None
                    if self.paged:
                        self.pages.lengths[i] = (int(self.positions[i])
                                                 if finishing else task.next)
                # finish in (step-within-block, slot) order so completion order
                # is identical to single-step execution
                for _, i in sorted(finish_order):
                    self._finish(i)
                emit.set_metadata(tokens=n_decoded,
                                  finished=len(finish_order))
            return n_decoded

    # ------------------------------------------------------------------
    def run_to_completion(self, max_steps: int = 100000) -> List[FinishedRequest]:
        while (self.queue or any(s is not None for s in self.slots)) \
                and self.steps < max_steps:
            self.step()
        return self.finished

    # ------------------------------------------------------------------
    def drain_slots(self) -> List[RequestState]:
        """Preemption support: evict live requests for requeueing (their
        generation restarts on another replica — prefix tokens preserved)."""
        out = []
        for i, st in enumerate(self.slots):
            if st is not None:
                st.slot = -1
                out.append(st)
                self.slots[i] = None
                self.live[i] = False
                if self.paged:
                    self.pages.release(i)
                    self._committed -= st.reserved_pages
                st.reserved_pages = 0
                st.cached_tokens = 0
        # a mid-prefill chunk task is evicted with its slot; its prompt ids
        # are verbatim, so resubmission elsewhere restarts identically
        self._task = None
        return out

    # ------------------------------------------------------------------
    def evict(self, rid: int) -> Optional[RequestState]:
        """Pull ONE request out of the engine for migration/requeue.

        Queued requests are simply unqueued; a slotted request releases its
        slot — and, in paged mode, every page it holds plus its admission
        reservation — exactly as a full ``drain_slots`` would, but for a
        single rid. The caller owns the returned state (its prompt ids are
        verbatim, so a resubmission elsewhere regenerates identically under
        deterministic sampling); ``None`` if the rid is unknown or already
        finished. Generated-so-far tokens are discarded: the migration
        decision rule (serving/gateway.py MigrationPlanner) prices that
        redo cost in before evicting a decoding request.
        """
        for j, st in enumerate(self.queue):
            if st.rid == rid:
                return self.queue.pop(j)
        for i, st in enumerate(self.slots):
            if st is not None and st.rid == rid:
                st.slot = -1
                st.generated = []
                self.slots[i] = None
                self.live[i] = False
                if self.paged:
                    self.pages.release(i)
                    self._committed -= st.reserved_pages
                st.reserved_pages = 0
                st.cached_tokens = 0
                if self._task is not None and self._task.slot == i:
                    self._task = None
                return st
        return None

    # ------------------------------------------------------------------
    def _fill_lane(self, slot: int, value: float) -> None:
        """Constant-fill one lane's KV (float leaves) with ``value``.

        Paged mode fills only the pages the lane's block-table row maps;
        unmapped entries (-1) are sanitized to the out-of-bounds page id so
        the scatter drops them — a raw -1 would wrap to the LAST page and
        corrupt whichever request owns it. No host sync: the fill is a
        donated device program."""
        if self.paged:
            bt = self.pages.block_table[slot].astype(np.int32).copy()
            if self._prefix_ok:
                # never fill a page other lanes can read: shared/adopted
                # pages and index-retained pages are masked to OOB; only
                # this lane's exclusive private pages are touched
                bt[~self.pages.exclusive_pages(slot)] = -1
            bt[bt < 0] = self.pages.n_pages          # OOB = dropped
            self.cache = self._fill_pages_jit(
                self.cache, jnp.asarray(bt), jnp.float32(value))
        else:
            self.cache = self._fill_slot_jit(
                self.cache, jnp.asarray(slot, jnp.int32), jnp.float32(value))

    def poison_lane(self, slot: int) -> None:
        """Fault injection: corrupt a lane's KV with NaN so the next fused
        block's logits for that lane are *genuinely* non-finite (masked
        softmax keeps p=0 rows, but 0 * NaN = NaN through ``p @ v``). The
        in-scan finiteness verdict — not the injector — must then catch
        it, exercising the real detection path end to end."""
        self._fill_lane(slot, float("nan"))

    def _scrub_lane(self, slot: int) -> None:
        """Zero a quarantined lane's KV before its pages/slot are reused:
        NaN left behind would contaminate the next occupant through the
        same 0 * NaN propagation that made detection possible."""
        self._fill_lane(slot, 0.0)

    def _quarantine(self, slot: int, reason: str) -> None:
        """Pull a poisoned lane out of service: scrub its KV, release its
        pages and admission reservation, reset the host mirrors, and hand
        the request to ``self.faulted`` for the scheduler's bounded-retry
        requeue. The request's prompt ids are verbatim, so the redo
        regenerates bit-identical tokens under deterministic sampling."""
        st = self.slots[slot]
        assert st is not None
        st.slot = -1
        st.last_fault = reason
        if self._prefix_ok:
            # suspect content must never serve a future prefix hit: drop
            # this slot's OWNED pages from the radix index before the
            # scrub (adopted pages stay — COW guarantees the lane never
            # wrote them, so their content is not implicated)
            self.pages.invalidate_slot(slot)
        self._scrub_lane(slot)       # before release: needs the block table
        self.slots[slot] = None
        self.live[slot] = False
        self.positions[slot] = 0
        self.last_token[slot] = 0
        self.gen_count[slot] = 0
        if self._task is not None and self._task.slot == slot:
            self._task = None
        if self.paged:
            self.pages.release(slot)
            self._committed -= st.reserved_pages
        st.reserved_pages = 0
        st.cached_tokens = 0
        self.faulted.append(st)

    # ------------------------------------------------------------------
    def kv_stats(self) -> Dict[str, float]:
        """KV-memory telemetry (exported by scheduler/gateway summaries).

        Paged engines report allocator occupancy/fragmentation plus bytes
        actually mapped (pages_in_use x page_bytes, across every layer's
        store); dense engines report their fixed n_slots x max_len
        footprint for comparison under a common schema."""
        leaves = jax.tree_util.tree_leaves(self.cache)
        total_bytes = sum(
            x.size * x.dtype.itemsize for x in leaves)
        if not self.paged:
            live = int(sum(self.positions[i] for i, s in enumerate(self.slots)
                           if s is not None))
            return {"layout": "dense", "kv_bytes_capacity": total_bytes,
                    "kv_bytes_in_use": total_bytes, "live_tokens": live,
                    "pages_in_use": 0, "occupancy": 1.0,
                    "fragmentation": 0.0}
        rep = self.pages.report()
        page_bytes = sum(x.size * x.dtype.itemsize // self.pages.n_pages
                         for x in leaves)
        rep.update(layout="paged", page_bytes=page_bytes,
                   kv_bytes_capacity=total_bytes,
                   kv_bytes_in_use=rep["pages_in_use"] * page_bytes,
                   peak_pages_in_use=self.peak_pages_in_use,
                   peak_kv_bytes_in_use=self.peak_pages_in_use * page_bytes,
                   committed_pages=self._committed)
        if self._prefix_ok:
            rep.update(prefill_tokens_computed=self.prefill_tokens_computed,
                       prefill_tokens_cached=self.prefill_tokens_cached)
        return rep
