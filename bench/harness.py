"""One run of one cell: set-up, a measured window through the gateway's
own calls, a traced window on request, and the check against the
float32 reference the configuration names. ``bench/run.py`` is the
command; see PERF.md. A cell runs on ``chips`` devices, which equal its
engine's ``tp_degree``: the weights are drawn straight into the engine's
tensor-parallel placement.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import pathlib
import shutil
import statistics
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import devtrace, loadgen, peaks, spec, weights

ROOT = pathlib.Path(__file__).resolve().parents[1]
REGION, CARBON_TRACE = "CA", "jun"
TRACE_SECONDS = 3.0
# the window opens once warm-up traffic has run its time and nothing has
# compiled for QUIET_S seconds (a cold first run compiles the shapes the
# warm-up reaches); it opens regardless WARMUP_CAP_S later
QUIET_S, WARMUP_CAP_S = 2.0, 300.0
# a first fused program that compiles this long means a cold cache
COLD_COMPILE_S, PRECOMPILE_THREADS = 5.0, 6
# the longest prompt any mix sends, in tokens, is under this
PREFILL_WARM_MAX = 512
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(*a) -> None:
    print(*a, flush=True)


class CompileClock:
    """Counts the backend compiles (persistent-cache loads included) JAX
    reports, and sums their seconds (as ``chip_smoke.CompileClock``)."""

    def __init__(self) -> None:
        import jax.monitoring
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.count += 1


@dataclasses.dataclass
class Sent:
    i: int
    t_sched: float
    req: dict


@dataclasses.dataclass
class Run:
    """What a run observed; per-layer readers take their numbers from it."""
    cell: spec.Cell
    model: dict
    peaks: dict
    counts: object        # the configuration's counts module
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    submit_s: List[float] = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None
    trace_steps: List[int] = dataclasses.field(default_factory=list)
    trace_counts: Dict[str, object] = dataclasses.field(default_factory=dict)


# ----------------------------------------------------------------- set-up
def setup_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached and none evicted, so that only the first run of a
    cell in a checkout compiles; a cache directory or size cap set in the
    environment is overridden (a capped cache smaller than a cell's
    programs thrashes, and a shared one would mix two checkouts)."""
    import jax
    path = str(root / "bench" / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def model_config(cell: spec.Cell):
    from repro.models.common import ModelConfig
    return ModelConfig(**cell.config["model"])


def param_shardings(cfg, engine: dict, shapes):
    """The engine's tensor-parallel placement of the weights (None on one
    chip): ``serving_shard_spec`` on the mesh the engine builds."""
    if engine["tp_degree"] == 1:
        return None
    import jax
    from repro.launch.mesh import make_tp_mesh
    from repro.launch.sharding import serving_shard_spec
    from repro.models import model as MD
    cache = jax.eval_shape(lambda: MD.init_paged_cache(
        cfg, engine["n_pages"], engine["page_size"]))
    return serving_shard_spec(cfg, make_tp_mesh(engine["tp_degree"]),
                              shapes, cache, paged=True).params


def make_weights(cfg, seed: int, engine: dict):
    """Drawn on the device in one program, each leaf where the engine
    keeps it: no device ever holds the whole of a sharded leaf."""
    import jax
    from repro.models import model as MD
    shapes = jax.eval_shape(lambda k: MD.init_model(cfg, k),
                            jax.random.PRNGKey(0))
    params = weights.make_params(shapes, seed,
                                 param_shardings(cfg, engine, shapes))
    jax.block_until_ready(params)
    return params


def on_mesh(eng):
    """The engine's mesh as the current one, as its own dispatch sets it
    (a tensor-parallel engine's paged kernel finds the mesh there)."""
    import jax
    return jax.set_mesh(eng.shard_spec.mesh) if eng.shard_spec \
        else contextlib.nullcontext()


def make_gateway(cfg, params, cell: spec.Cell, seed: int, entry_points=None):
    """One gateway, one pool (CA, the ``jun`` trace), one scheduler over
    one engine; the clock is ticked once, installing SproutPolicy's
    uniform pre-telemetry directive mix, and never again."""
    import jax
    from repro.core import CarbonIntensityProvider, EnergyModel, hardware_for
    from repro.core.policies import SproutPolicy
    from repro.serving import (CarbonAwareScheduler, InferenceEngine,
                               SproutGateway)
    g = cell.config["engine"]
    eng = InferenceEngine(
        cfg, params, n_slots=g["n_slots"], max_len=g["max_len"], eos_id=-1,
        seed=seed & 0x7FFFFFFF, decode_block=g["decode_block"], paged=True,
        page_size=g["page_size"], n_pages=g["n_pages"],
        prefill_chunk=g["prefill_chunk"], prefix_cache=g["prefix_cache"],
        tp_degree=g["tp_degree"])
    if entry_points is not None:
        eng.entry_points = entry_points
    prov = CarbonIntensityProvider(REGION, CARBON_TRACE)
    hw = hardware_for(jax.devices()[0].device_kind)
    policy = SproutPolicy(k0_min=prov.k_min, k0_max=prov.k_max,
                          k1=hw.embodied_gco2 / hw.lifetime_s)
    # the gateway draws each request's directive level from the mix's
    # pool seed, so that output lengths do not change with the run seed
    gw = SproutGateway([(prov, CarbonAwareScheduler([eng]))], policy=policy,
                       energy=EnergyModel(hw), hw=hw,
                       seed=cell.mix["pool_seed"])
    gw.tick(0.0)
    return gw, eng


def sampler_mode(mix: dict) -> str:
    s = mix["sampling"]
    if s["temperature"] <= 0:
        return "greedy"
    return "full" if s.get("top_k", 0) > 0 or s.get("top_p", 1.0) < 1 \
        else "temp"


def max_prompt_tokens(mix: dict) -> int:
    from repro.core.directives import DirectiveSet
    from repro.serving.tokenizer import ByteTokenizer
    ds, tok = DirectiveSet(), ByteTokenizer()
    return mix["prompt_bytes_max"] + max(
        len(tok.encode(ds.apply("", lv), bos=True)) for lv in range(len(ds)))


def fused_shapes(eng, mix: dict):
    """Every (mode, bucket, block, chunk) entry point the cell's traffic
    can dispatch: batch buckets up to n_slots x decode blocks 1..K, and
    mixed blocks up to the longest prompt's chunk count, in the mix's
    sampler mode (and greedy, for small buckets, where a sampled mix
    carries greedy requests)."""
    pow2 = lambda n: [1 << i for i in range(n.bit_length()) if 1 << i <= n]
    buckets = sorted(set(b for b in pow2(eng.n_slots)) | {eng.n_slots})
    ks = pow2(eng.decode_block)
    c = eng.prefill_chunk
    chunks = -(-max_prompt_tokens(mix) // c)
    ks_mixed = [k for k in ks if k <= 1 << (chunks - 1).bit_length()]
    modes = {sampler_mode(mix): buckets}
    if modes.get("greedy") is None and mix.get("greedy_every", 0) > 0:
        # batches of greedy check requests alone: small buckets only
        modes["greedy"] = [b for b in buckets
                           if b <= mix["greedy_buckets_max"]]
    return ([(m, bs, k, 0) for m, bb in modes.items() for bs in bb
             for k in ks]
            + [(m, bs, k, c) for m, bb in modes.items() for bs in bb
               for k in ks_mixed])


def warm_fused(eng, shapes, clock: CompileClock) -> None:
    """Run each fused entry point once on an all-dead batch: no lane is
    live, every chunk is empty and the block table unmapped, so nothing is
    written; the programs are then compiled (or loaded) and warm. When the
    first one had to compile (a cold cache), the rest are compiled into
    the persistent cache first, several at a time, and then loaded."""
    import jax

    def run(call, lower: bool = False):
        fn, args = call
        # the arguments are made outside the mesh, as the engine makes its
        # own: arrays made under a mesh are placed on it and compile apart
        a = args(eng)
        with on_mesh(eng):
            return fn.lower(*a).compile() if lower else fn(*a)

    calls = [dead_call(eng, *shape) for shape in shapes]
    c0 = clock.seconds
    eng.cache, *_ = run(calls[0])
    jax.block_until_ready(eng.cache)
    if clock.seconds - c0 > COLD_COMPILE_S:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(PRECOMPILE_THREADS) as ex:
            list(ex.map(lambda call: run(call, lower=True), calls[1:]))
    for call in calls[1:]:
        eng.cache, *_ = run(call)
    jax.block_until_ready(eng.cache)


def dead_call(eng, mode: str, bs: int, k: int, c: int):
    """The fused entry point for a shape, and a function giving its
    all-dead arguments against the engine's current cache."""
    import jax
    import jax.numpy as jnp
    n = eng.n_slots
    fn, _ = eng._fused_for(k, mode, bs, c)
    rows = np.arange(n) if bs == n else np.full(bs, n)

    def args(eng):
        z = lambda dt: jnp.zeros(bs, dt)
        state = {"last": z(jnp.int32), "pos": z(jnp.int32),
                 "live": z(bool), "gc": z(jnp.int32),
                 "max_new": jnp.ones(bs, jnp.int32), "temp": z(jnp.float32),
                 "topk": z(jnp.int32), "topp": jnp.ones(bs, jnp.float32),
                 "key": jax.random.PRNGKey(0),
                 "rows": jnp.asarray(rows, jnp.int32),
                 "ok": jnp.ones(bs, bool)}
        chunk = None
        if c:
            state["chunk_lane"] = jnp.asarray(0, jnp.int32)
            chunk = (jnp.zeros((k, c), jnp.int32), jnp.zeros(k, jnp.int32),
                     jnp.zeros(k, jnp.int32), jnp.zeros(k, bool))
        bt = jnp.full((bs, eng.pages.max_pages), -1, jnp.int32)
        return eng.params, eng.cache, bt, state, chunk
    return fn, args


def warm_prefill(eng) -> None:
    """Whole-prompt prefill runs only on an idle engine, one prompt at a
    time (see the warm-up hold in ``serve``); warm its shapes for every
    prompt bucket up to ``max_len`` with throw-away prompts sent straight
    to the engine, so set-up does the same work whatever the seed."""
    lengths, n = [], 16
    while n // 2 < min(eng.max_len - 3, PREFILL_WARM_MAX):
        lengths.append(min(n, eng.max_len - 3))
        n *= 2
    for n in lengths:
        eng.submit([ord("a")] * n, max_new_tokens=2)
    eng.run_to_completion()
    eng.finished = []


# ----------------------------------------------------------------- serving
class Client:
    """Offers the cell's traffic through ``SproutGateway.submit`` and
    ``SproutGateway.step``, the calls ``run_hour`` makes, with no
    simulated-hour drain."""

    def __init__(self, gw, eng, traffic: loadgen.Traffic, run: Run):
        from repro.serving import ServeRequest
        from repro.serving.sampler import SamplingParams
        self.ServeRequest, self.SamplingParams = ServeRequest, SamplingParams
        self.gw, self.eng, self.traffic, self.run = gw, eng, traffic, run
        self.pool = gw.pools[0]
        self.sent: Dict[int, Sent] = {}
        self.finished: Dict[int, object] = {}
        self.t_fin: Dict[int, float] = {}
        gw.on_finish = self._on_finish
        self.i = 0
        self.lateness: List[float] = []
        self.steps: List[tuple] = []        # (t_start, t_end, tokens)
        self.tracing = False
        self.trace_ann = None
        self._seen_rids: set = set()
        self.admitted = 0

    def _on_finish(self, _key, fin) -> None:
        self.finished[fin.rid] = fin
        self.t_fin[fin.rid] = time.monotonic()

    def submit(self, t_sched: float, window: bool) -> None:
        r = self.traffic.request(self.i)
        req = self.ServeRequest(
            0, r["prompt"], max_new_tokens=r["budgets"][0],
            max_new_by_level=r["budgets"], task=r["task"],
            sampling=self.SamplingParams(r["temperature"], r["top_k"],
                                         r["top_p"]))
        t0 = time.perf_counter()
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation("bench.submit"):
                rid, _ = self.gw.submit(req)
        else:
            rid, _ = self.gw.submit(req)
        dt = time.perf_counter() - t0
        now = time.monotonic()
        if window:
            self.run.submit_s.append(dt)
            self.lateness.append(now - t_sched)
        self.sent[rid] = Sent(self.i, t_sched, r)
        self.i += 1

    def step(self, window: bool) -> int:
        eng = self.eng
        snap = None
        if self.tracing:
            snap = (eng.live.copy(), eng.positions.copy())
        t0 = time.monotonic()
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation("bench.step"):
                tokens = self.gw.step()
        else:
            tokens = self.gw.step()
        t1 = time.monotonic()
        new = [st for st in eng.slots
               if st is not None and st.rid not in self._seen_rids]
        for st in new:
            self._seen_rids.add(st.rid)
        if len(self._seen_rids) > 8 * eng.n_slots:
            self._seen_rids = {st.rid for st in eng.slots if st is not None}
        if window:
            self.admitted += len(new)
            self.steps.append((t0, t1, tokens))
        if snap is not None:
            self._count_traced(snap, new, tokens)
        return tokens

    def _count_traced(self, snap, new, tokens) -> None:
        live0, pos0 = snap
        n = np.where(live0, self.eng.positions - pos0, 0)
        keys = [np.arange(p + 1, p + k + 1) for p, k in zip(pos0, n) if k > 0]
        tc = self.run.trace_counts
        tc.setdefault("decode_keys", []).extend(keys)
        tc.setdefault("prompt_lens", []).extend(st.prompt_len for st in new)
        self.run.trace_steps.append(tokens)


def counters(eng) -> Dict[str, float]:
    return {"decode_tokens": eng.decode_tokens, "steps": eng.steps,
            "decode_syncs": eng.decode_syncs, "chunk_steps": eng.chunk_steps,
            "prefill_tokens_computed": eng.prefill_tokens_computed}


def serve(cell: spec.Cell, seed: int, seconds: float, trace: bool,
          clock: CompileClock, t_start: float, entry_points=None) -> dict:
    """Set-up and the measured window of one run. Returns what the check
    and the metrics need; the program's state is still alive."""
    import jax
    from repro.analysis import entry_point_snapshot

    mix = cell.mix
    run = Run(cell, cell.config["model"],
              peaks.peaks_for(jax.devices()[0].device_kind)
              if jax.devices()[0].platform == "tpu" else {},
              spec.counts(cell))
    cfg = model_config(cell)
    t0 = time.monotonic()
    params = make_weights(cfg, seed, cell.config["engine"])
    gw, eng = make_gateway(cfg, params, cell, seed, entry_points)
    t1 = time.monotonic()
    log(f"set-up: weights and engine {t1 - t0:.3f} s, "
        f"{t0 - t_start:.3f} s after start")
    shapes = fused_shapes(eng, mix)
    warm_fused(eng, shapes, clock)
    warm_prefill(eng)
    log(f"set-up: warmed {len(shapes)} fused entry points and prefill in "
        f"{time.monotonic() - t1:.3f} s ({clock.count} compiles or cache "
        f"loads so far, {clock.seconds:.3f} s)")
    traffic = loadgen.Traffic(mix, seed)
    drv = Client(gw, eng, traffic, run)
    kind = traffic.kind
    depth = mix["arrival"].get("depth", 0)
    t_begin = time.monotonic()
    t_win_sched = t_begin + mix["warmup_s"]
    t_win0 = t_win_end = None
    c0 = compiles0 = None
    window_rids: set = set()
    frozen = None
    trace_dir = cell.root / "bench" / "traces" / cell.name
    last_count, t_quiet = clock.count, t_begin
    while True:
        now = time.monotonic()
        if clock.count != last_count:
            last_count, t_quiet = clock.count, now
        in_window = t_win0 is not None and t_win_end is None
        if t_win0 is None and now >= t_win_sched and (
                now - t_quiet >= QUIET_S
                or now >= t_win_sched + WARMUP_CAP_S):
            # the window opens at a step boundary, every program warm
            t_win0 = now
            log(f"set-up: warm-up traffic {t_win0 - t_begin:.3f} s")
            setup_s = t_win0 - t_start
            c0, compiles0 = counters(eng), clock.count
            drv.admitted = 0
            frozen = entry_point_snapshot(eng)
            if trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(str(trace_dir))
                drv.trace_ann = jax.profiler.TraceAnnotation("bench.window")
                drv.trace_ann.__enter__()
                drv.tracing = True
                run.trace_counts["c0"] = counters(eng)
            in_window = True
        # in warm-up, an engine with no live lane takes one request at a
        # time, so whole-prompt prefill only ever runs one prompt and its
        # shapes do not depend on the seed; later ones stream in as chunks
        hold = t_win0 is None and not eng.live.any()
        if hold and drv.pool.load() > 0:
            pass
        elif kind == "poisson":
            while t_begin + traffic.offset(drv.i) <= now and not (
                    hold and drv.pool.load() > 0):
                t_sched = t_begin + traffic.offset(drv.i)
                win = (t_win0 is not None and t_win_end is None
                       and t_sched >= t_win0)
                if win:
                    window_rids.add(drv.i)
                drv.submit(t_sched, win)
        elif t_win_end is None:
            while drv.pool.load() < (1 if hold else eng.n_slots + depth):
                drv.submit(now, in_window)
        if drv.tracing and now - t_win0 >= min(TRACE_SECONDS, seconds):
            run.trace_counts["c1"] = counters(eng)
            drv.trace_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            drv.tracing = False
        if t_win_end is not None:
            pending = [rid for rid, s in drv.sent.items()
                       if s.i in window_rids and rid not in drv.finished]
            if kind != "poisson" or not pending \
                    or now >= t_win_end + mix["drain_s"]:
                break
        if drv.pool.load() == 0:
            if kind == "poisson":
                nxt = t_begin + traffic.offset(drv.i)
                time.sleep(max(0.0, min(nxt - now, 0.01)))
            continue
        drv.step(in_window)
        if in_window and time.monotonic() - t_win0 >= seconds:
            t_win_end = time.monotonic()
            run.counters = {k: counters(eng)[k] - c0[k] for k in c0}
            run.counters["admitted"] = drv.admitted
            run.counters["backlog_at_close"] = drv.pool.load()
            run.counters["window_compiles"] = clock.count - compiles0
            added = set(entry_point_snapshot(eng)) - set(frozen)
            if added:
                log(f"entry points added inside the window: {sorted(added)}")
                run.counters["window_compiles"] += len(added)
    if drv.tracing:                       # window shorter than the trace
        run.trace_counts["c1"] = counters(eng)
        drv.trace_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    if trace:
        run.trace = devtrace.normalize(str(trace_dir))
    rids = {s.i: rid for rid, s in drv.sent.items()}
    return {"run": run, "drv": drv, "gw": gw, "eng": eng, "params": params,
            "setup_s": setup_s, "t_win0": t_win0, "t_win_end": t_win_end,
            "window_rids": [rids[i] for i in sorted(window_rids)]}


# ----------------------------------------------------------------- metrics
def _p95(xs) -> float:
    if not xs:
        raise RuntimeError("no request of the window finished")
    return float(np.percentile(np.asarray(xs, np.float64), 95))


def request_times(s: dict) -> Dict[str, list]:
    """TTFT from the scheduled arrival, and time per output token, of the
    window's requests that finished."""
    drv = s["drv"]
    ttft, tpot = [], []
    for rid in s["window_rids"]:
        fin = drv.finished.get(rid)
        if fin is None:
            continue
        t_first = fin.t_done - fin.latency_s + fin.ttft_s
        ttft.append(t_first - drv.sent[rid].t_sched)
        if fin.gen_tokens > 1:
            tpot.append((fin.t_done - t_first) / (fin.gen_tokens - 1))
    return {"ttft": ttft, "tpot": tpot}


def end_to_end(cell: spec.Cell, s: dict) -> Dict[str, dict]:
    out = {}
    times = None
    for m in cell.end_to_end:
        name = m["name"]
        if name == "setup_s":
            v = s["setup_s"]
        elif name in ("ttft_p95_ms", "tpot_p95_ms"):
            times = times or request_times(s)
            v = 1e3 * _p95(times["ttft" if name.startswith("ttft")
                                 else "tpot"])
        elif name == "output_tok_s":
            t0, t1 = s["t_win0"], s["t_win_end"]
            v = sum(tok for a, b, tok in s["drv"].steps
                    if a >= t0 and b <= t1) / (t1 - t0)
        else:
            raise KeyError(f"no end-to-end metric {name!r} in the harness")
        out[name] = {"value": float(v), "unit": m["unit"]}
    return out


def per_layer(cell: spec.Cell, run: Run) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        v = spec.reader(cell.root, m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ----------------------------------------------------------------- check
def prompt_ids(sched, eng, fin, req: dict) -> List[int]:
    """The token ids the engine served for ``req`` at its drawn level:
    the directive rendered into ChatML by the scheduler's own
    DirectiveSet and tokenizer, cut as the engine cuts it."""
    text = sched.directives.apply(req["prompt"], fin.directive_level)
    ids = sched.tok.encode(text, bos=True)
    budget = req["budgets"][fin.directive_level]
    return ids[: eng.max_len - budget - 1]


def pick_sample(s: dict, seed: int, n: int) -> List[int]:
    """Greedy requests finished from the window: the longest, then others
    drawn from the seed."""
    drv = s["drv"]
    pool = [rid for rid in s["window_rids"] if rid in drv.finished
            and drv.sent[rid].req["greedy"]]
    if not s["window_rids"]:      # a backlog cell: what the window finished
        t0, t1 = s["t_win0"], s["t_win_end"]
        pool = [rid for rid, t in drv.t_fin.items()
                if t0 <= t <= t1 and drv.sent[rid].req["greedy"]]
    if not pool:
        return []
    pool.sort()
    longest = max(pool, key=lambda r: drv.finished[r].gen_tokens)
    rest = [r for r in pool if r != longest]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[j] for j in sorted(pick)]


def served_samples(s: dict, rids: List[int]):
    drv, eng = s["drv"], s["eng"]
    sched = s["gw"].pools[0].scheduler
    out = []
    for rid in rids:
        fin = drv.finished[rid]
        out.append((prompt_ids(sched, eng, fin, drv.sent[rid].req),
                    list(fin.token_ids), fin.prompt_tokens))
    return out


def logit_gaps(reference, model: dict, params, samples,
               quant: str = "") -> list:
    """Per sample, per served token: the ``reference`` class's best logit
    minus its logit of the token served (``quant``: of the token the
    control puts first)."""
    ref = reference(model, params)
    ctl = reference(model, params, quant=quant) if quant else None
    gaps = []
    for prompt, served, _ in samples:
        seq = prompt + served[:-1]
        rows = np.arange(len(prompt) - 1, len(seq))
        lg = ref.logits(seq, rows)
        pick = np.asarray(served) if ctl is None else \
            np.argmax(ctl.logits(seq, rows), -1)
        gaps.append(lg.max(-1) - lg[np.arange(len(rows)), pick])
    return gaps


def check(cell: spec.Cell, s: dict, seed: int,
          control: str = "") -> List[dict]:
    """The numbers compared, each with its limit. Frees the program's state
    before the reference runs. With ``control`` (``"fp8"``), the control's
    first token at each served position stands in the served token's
    place, as if the control had served it; the benchmark's own runs never
    do this (``bench/calibrate.py`` does)."""
    if "exact" not in s:
        s["exact"] = exact_counts(cell, s, seed)
    rids, samples = s["rids"], s["samples"]
    per = logit_gaps(spec.reference(cell), reference_model(cell),
                     s["params"], samples, control)
    drv = s["drv"]
    for rid, (p, served, _), g in zip(rids, samples, per):
        fin = drv.finished[rid]
        far = np.nonzero(g > 0.5)[0]
        who = f" ({control} control)" if control else ""
        log(f"check sample rid {rid}{who}: level {fin.directive_level}, "
            f"prompt {len(p)}, served {len(served)}, widest gap "
            f"{g.max():.6g} at "
            f"{int(g.argmax())}, {far.size} over 0.5"
            + (f" (first at {int(far[0])})" if far.size else ""))
    gaps = np.concatenate(per) if per else np.zeros(0)
    lim = cell.config["check"]
    return [
        {"name": "max_logit_gap", "value": float(gaps.max()) if gaps.size
         else math.inf, "limit": lim["max_logit_gap"]},
        {"name": "tokens_checked", "value": int(gaps.size),
         "limit": lim["min_tokens_checked"], "at_least": True},
    ] + s["exact"]


def exact_counts(cell: spec.Cell, s: dict, seed: int) -> List[dict]:
    """The counts held to 0, and the sample the reference checks (put in
    ``s``); then frees the program's state."""
    drv, eng = s["drv"], s["eng"]
    vocab = cell.config["model"]["vocab_size"]
    mism = bad_tok = 0
    for rid, fin in drv.finished.items():
        req = drv.sent[rid].req
        mism += fin.gen_tokens != req["budgets"][fin.directive_level]
        bad_tok += sum(not 0 <= t < vocab for t in fin.token_ids)
    unfinished = sum(rid not in drv.finished for rid in s["window_rids"])
    rids = pick_sample(s, seed, cell.mix["check_requests"])
    samples = served_samples(s, rids)
    prompt_len_mismatch = sum(len(p) != n for p, _, n in samples)
    s["rids"], s["samples"] = rids, samples
    # free the program's state; the weights are the benchmark's own
    s.pop("gw"), s.pop("eng")
    drv.gw = drv.eng = drv.pool = None
    gc.collect()
    return [
        {"name": "length_mismatch", "value": int(mism), "limit": 0},
        {"name": "prompt_mismatch", "value": int(prompt_len_mismatch),
         "limit": 0},
        {"name": "token_out_of_vocab", "value": int(bad_tok), "limit": 0},
        {"name": "unfinished", "value": int(unfinished), "limit": 0},
        {"name": "window_compiles",
         "value": int(s["run"].counters["window_compiles"]), "limit": 0},
    ]


def reference_model(cell: spec.Cell) -> dict:
    return dict(cell.config["model"], **cell.config["reference"])


def passes(c: dict) -> bool:
    if c.get("at_least"):
        return c["value"] >= c["limit"]
    return c["value"] <= c["limit"]


# ----------------------------------------------------------------- main
def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(cell: spec.Cell, allow_cpu: bool):
    """The cell's devices, or None (with the reason on stderr)."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} device_kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu" and not allow_cpu:
        print("bench: no TPU found; there is no CPU fallback",
              file=sys.stderr)
        return None
    tp = cell.config["engine"]["tp_degree"]
    if cell.chips != tp:
        print(f"bench: {cell.name} asks for {cell.chips} chips at "
              f"tp_degree {tp}; a cell runs one engine over all its chips, "
              f"so chips must equal tp_degree", file=sys.stderr)
        return None
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devs)}", file=sys.stderr)
        return None
    return devs[:cell.chips]


def main(argv=None, *, t_start: Optional[float] = None, root=None,
         allow_cpu: bool = False) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    args = parse(argv)
    root = pathlib.Path(root or ROOT)
    cell = spec.load_cell(root, args.workload)
    devs = device_info(cell, allow_cpu)
    if devs is None:
        return 1
    import jax
    if devs[0].platform == "tpu":
        log(f"compile cache: {setup_compile_cache(root)}")
    clock = CompileClock()
    g, m = cell.config["engine"], cell.config["model"]
    log(f"cell {cell.name}: config {cell.config['name']} "
        f"(layers {m['n_layers']}, d_model {m['d_model']}, heads "
        f"{m['n_heads']}/{m['n_kv_heads']}, d_ff {m['d_ff']}, vocab "
        f"{m['vocab_size']}); engine {json.dumps(g)}; traffic "
        f"{cell.mix_name} {json.dumps(cell.mix['arrival'])}; seed "
        f"{args.seed}; window {args.seconds} s; trace {args.trace}")
    s = serve(cell, args.seed, args.seconds, bool(args.trace), clock,
              t_start)
    drv, run = s["drv"], s["run"]
    lat = drv.lateness or [0.0]
    log(f"set-up {s['setup_s']:.3f} s; compiles or cache loads "
        f"{clock.count} ({clock.seconds:.3f} s); inside the window "
        f"{run.counters['window_compiles']}")
    levels = np.bincount([f.directive_level for f in drv.finished.values()],
                         minlength=3)
    in_win = len(s["window_rids"])
    done = sum(r in drv.finished for r in s["window_rids"])
    if drv.steps:
        i_long = max(range(len(drv.steps)),
                     key=lambda j: drv.steps[j][1] - drv.steps[j][0])
        a, b, _ = drv.steps[i_long]
        log(f"window: {len(drv.steps)} gateway steps, the longest "
            f"{1e3 * (b - a):.3f} ms at {a - s['t_win0']:.3f} s")
    log(f"requests: sent {len(drv.sent)} (in the window {in_win}), "
        f"finished {len(drv.finished)} (of the window's {done}); generator "
        f"late by mean {1e3 * statistics.fmean(lat):.3f} ms, max "
        f"{1e3 * max(lat):.3f} ms; directive mix of finished "
        f"{levels.tolist()}; counters {json.dumps(run.counters)}")
    mems = [d.memory_stats() or {} for d in devs]
    peaks_each = [int(m.get("peak_bytes_in_use", 0)) for m in mems]
    peak = max(peaks_each)
    log(f"memory: peak {peak} bytes of "
        f"{mems[0].get('bytes_limit', 'unknown')}; per device {peaks_each}")
    metrics = per_layer(cell, run) if args.trace else end_to_end(cell, s)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": peak,
              "memory_peak_bytes_each": peaks_each}
    breakdown = None
    if args.trace:
        device["busy_s"] = devtrace.busy_s(run.trace)
        t0, t1 = devtrace.window(run.trace)
        device["window_s"] = (t1 - t0) / 1e9
        breakdown = {"device_ops": devtrace.top_ops(run.trace),
                     "idle_gaps": devtrace.idle_gaps(run.trace)}
    compared = check(cell, s, args.seed)
    correct = all(passes(c) for c in compared)
    if cell.mix["arrival"]["kind"] == "poisson":
        attempted, failed = in_win, in_win - done
    else:
        attempted = sum(s["t_win0"] <= t <= s["t_win_end"]
                        for t in drv.t_fin.values())
        failed = 0
    result = {"correct": correct, "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["compared"] = {c["name"]: {"value": c["value"],
                                      "limit": c["limit"]} for c in compared}
    for c in compared:
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {c['name']}: {c['value']} (limit {rel} {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
