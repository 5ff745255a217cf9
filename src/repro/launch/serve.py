"""Serving launcher: the SPROUT carbon-aware service as a CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch llama2_13b \
        --region CA --replicas 2 --requests 24

Runs the real continuous-batching engine with the full SPROUT control
plane: hourly LP re-planning from the regional carbon-intensity trace,
directive rendering into system prompts, level-cost profiling, and
preemption-safe scheduling.

``--gateway`` switches to the closed-loop SproutGateway: one scheduler
pool per ``--regions`` entry, the LP re-planned per pool from its live
intensity, engine telemetry fed back into the level profiles, and requests
routed to the greenest pool under a load cap.

``--tenants`` layers service classes on top (premium/standard/batch, one
LP per (pool, tenant) with per-class quality floors); ``--slo`` arms
their TTFT/TPOT latency targets so admission routes on predicted
completion time jointly with greenness; ``--drain-at H`` empties the
``--drain-region`` pool ahead of maintenance at hour H (DESIGN.md §10).

``--config published`` serves the architecture at its published widths
(weights drawn from a seed) instead of the default reduced CPU config;
``--max-len`` sizes each slot's KV region. Carbon is priced on the
HardwareSpec of the device jax reports (``core.energy.hardware_for``).

``--chaos`` arms the default fault-injection script (DESIGN.md §12) — one
fault of every class against the first pool — and prints the recovery
counters; ``--grid-provider electricitymaps --grid-token ...`` swaps the
bundled traces for the live grid-signal client (tokenless runs fall back
to the traces, so the flag is CI-safe).
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import os
import pathlib

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core import (DEFAULT_TENANTS, LLAMA2_13B, CarbonIntensityProvider,
                        DirectiveSet, EnergyModel, GridSignalClient,
                        QualityEvaluator, Workload, hardware_for,
                        solve_directive_lp)
from repro.core.carbon import WatchdogProvider
from repro.core.policies import LevelProfiles, SproutPolicy
from repro.models import model as MD
from repro.serving import (CarbonAwareScheduler, FaultInjector, FaultPlan,
                           FaultSpec, InferenceEngine, MigrationPlanner,
                           ServeRequest, SproutGateway, no_faults,
                           serve_request_from)

# the persistent compile cache lives at a fixed path inside the checkout:
# the path is part of the cache key, so a directory that moves never hits
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache on an accelerator and
    return its directory ("" where it stays off). Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    else is set; otherwise the cache goes to ``COMPILE_CACHE_DIR``. On the
    CPU it stays off: XLA:CPU logs a machine-feature mismatch error on
    every entry it reloads. Call before the first compile, never at
    import time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return ""
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


# published widths: each weight is drawn and cast inside one program, so no
# float32 copy of the whole model is ever materialized
_init_model_jit = jax.jit(MD.init_model, static_argnums=0)


def load_model(arch: str, config: str, seed: int = 0):
    """(cfg, params) for ``arch``: ``reduced`` is the CPU-sized config with
    a 512-token vocabulary; ``published`` is the architecture at its
    published widths. Weights are random, drawn from ``seed``."""
    key = jax.random.PRNGKey(seed)
    if config == "published":
        cfg = get_config(arch)
        return cfg, _init_model_jit(cfg, key)
    cfg = reduced(arch).replace(vocab_size=512)
    return cfg, MD.init_model(cfg, key)


# request mix across service classes for --tenants runs (premium is the
# minority class with the hard floor; batch soaks up the brief levels)
TENANT_CYCLE = ("premium", "standard", "standard", "batch")


def tenant_specs(slo: bool) -> tuple:
    """The default service classes; without --slo their latency targets
    are disarmed (quality floors only, no deadlines)."""
    if slo:
        return DEFAULT_TENANTS
    return tuple(dataclasses.replace(t, ttft_s=math.inf, tpot_s=math.inf)
                 for t in DEFAULT_TENANTS)


def chaos_plan(regions) -> FaultPlan:
    """The CLI's default chaos script: one fault of every class a plain
    --gateway run can reach, aimed at the first pool so the others keep
    absorbing its recovered work."""
    r0 = regions[0]
    return FaultPlan([
        FaultSpec("carbon.nan", r0, occurrences=(0,)),
        FaultSpec("carbon.stale", r0, occurrences=(1,)),
        FaultSpec("carbon.exception", r0, occurrences=(2,)),
        FaultSpec("lp.fail", r0, occurrences=(0,)),
        FaultSpec("decode.nonfinite", "*", occurrences=(0,)),
        FaultSpec("replica.crash", f"{r0}/0", occurrences=(2,)),
        FaultSpec("migrate.dst_vanish", "*", occurrences=(0,)),
    ])


def grid_provider(region: str, args) -> CarbonIntensityProvider:
    """Trace-backed by default; --grid-provider switches to the live
    Electricity Maps / WattTime client (tokenless = immediate trace
    fallback, so the flag is safe to try offline)."""
    if args.grid_provider == "trace":
        return CarbonIntensityProvider(region, "jun")
    return GridSignalClient(region, "jun", provider=args.grid_provider,
                            token=args.grid_token)


def run_gateway(args, cfg, params, hw) -> SproutGateway:
    """Closed-loop mode: LP -> scheduler pools -> engine telemetry -> LP.
    Returns the gateway, whose stats hold what was served."""
    regions = [r.strip() for r in args.regions.split(",") if r.strip()]
    workload = Workload(seed=0)
    evaluator = QualityEvaluator(sample_size=200)
    injector = (FaultInjector(chaos_plan(regions), seed=args.chaos_seed)
                if args.chaos else no_faults())
    # the watchdog wraps every feed (live or trace): staleness aging,
    # last-good fallback, and the chaos injection points for --chaos
    providers = [WatchdogProvider(grid_provider(r, args),
                                  fault_injector=injector)
                 for r in regions]
    k_min = min(p.k_min for p in providers)
    k_max = max(p.k_max for p in providers)
    pools = []
    for j, prov in enumerate(providers):
        engines = [
            # eos_id=-1: the tiny random model has no meaningful EOS, so
            # decoding is budget-bound and measured token counts carry the
            # per-level brevity structure
            InferenceEngine(cfg, params, n_slots=args.slots,
                            max_len=args.max_len,
                            seed=100 * j + i, decode_block=args.decode_block,
                            eos_id=-1, **engine_kv_kwargs(args))
            for i in range(args.replicas)]
        pools.append((prov, CarbonAwareScheduler(
            engines, fault_injector=injector)))
    tenants = tenant_specs(args.slo) if args.tenants else None
    # tenant mode solves its own per-(pool, tenant) LPs with per-class xi
    # values — a single-mix SproutPolicy (and --xi) only applies without
    # --tenants, so don't build one that would be silently ignored
    policy = None if tenants else SproutPolicy(
        k0_min=k_min, k0_max=k_max, xi=args.xi,
        k1=hw.embodied_gco2 / hw.lifetime_s)
    # the accounting profile mirrors the engine's KV dtype, so the int8
    # flag halves modeled decode KV bytes end to end (roofline -> level
    # profiles -> LP -> Eq. 1 carbon)
    profile = LLAMA2_13B.with_int8_kv() if args.kv_int8 else LLAMA2_13B
    migration = MigrationPlanner() if args.migrate else None
    gw = SproutGateway(pools, policy=policy, tenants=tenants,
                       energy=EnergyModel(hw), hw=hw,
                       model_profile=profile, load_cap=args.load_cap,
                       forecast_horizon=args.forecast_horizon,
                       migration=migration, fault_injector=injector)

    for hour in range(args.hours):
        pool_sample = [workload.sample_request(hour + i * 0.01)
                       for i in range(300)]
        gw.set_quality(evaluator.evaluate(pool_sample).q)
        reqs = [serve_request_from(workload.sample_request(hour + i * 0.01),
                                   token_scale=320.0 / args.max_new,
                                   max_new=args.max_new,
                                   tenant=(TENANT_CYCLE[i % len(TENANT_CYCLE)]
                                           if tenants else ""))
                for i in range(args.requests)]
        # >= (not ==): --drain-at takes a float hour, and the loop steps
        # in whole hours — drain fires at the first hour past the mark.
        # The drain runs through run_hour's on_inflight hook, i.e. with
        # the hour's work IN FLIGHT — each hour is served to idle, so
        # draining between hours would always find an empty backlog and
        # demonstrate nothing but the admission skip.
        on_inflight = None
        if args.drain_at >= 0 and hour >= args.drain_at \
                and not gw.draining:

            def on_inflight(g, hour=hour):
                # default target: the pool holding the most in-flight
                # work — the interesting maintenance case; --drain-region
                # pins a specific one
                region = args.drain_region or max(
                    g.pools, key=lambda p: p.load()).key
                moved = g.drain_pool(region, deadline=float(hour))
                print(f"  [hour {hour}] draining {region} ahead of "
                      f"maintenance; moved {moved} backlogged requests")
        s = gw.run_hour(float(hour), reqs, on_inflight=on_inflight)
        ks = " ".join(f"{k}={v:4.0f}" for k, v in s["k0"].items())
        xs = " ".join(f"{k}:{np.round(v, 2)}" for k, v in s["x"].items())
        rt = " ".join(f"{k}={v}" for k, v in s["routes"].items())
        kv = " ".join(
            f"{k}={v.get('kv_bytes_in_use', 0) / 1024:.0f}KiB"
            f"@{v.get('occupancy', 1.0):.0%}"
            for k, v in s["kv"].items())
        mig = f"  migrated={s['migrated']}" if migration or s["draining"] \
            else ""
        slo = ""
        if s["slo"]:
            slo = "  slo[" + " ".join(
                f"{k}={v:.0%}" for k, v in sorted(s["slo"].items())) + "]"
        print(f"hour {hour}: CI[{ks}]  served={s['served']:3d}  "
              f"carbon={s['carbon_g']:.4f}g  routes[{rt}]  x[{xs}]  "
              f"kv[{kv}]{mig}{slo}", flush=True)
    st = gw.stats
    print(f"total: {st.carbon_g:.4f} gCO2 across {st.requests} requests "
          f"({1000 * st.carbon_per_request:.3f} mg/req, "
          f"{st.rejected} rejected, {st.migrated} migrated)")
    print(f"level mix: {np.round(st.level_counts / max(st.requests, 1), 3)}")
    if args.chaos:
        inj = " ".join(f"{e.point}@{e.target}" for e in injector.events)
        wd = sum(sum(p.provider.faults.values()) for p in gw.pools
                 if hasattr(p.provider, "faults"))
        print(f"chaos: injected[{inj}]  recovered_faults={st.faults}  "
              f"watchdog_faults={wd}  plan_holds={st.plan_holds}  "
              f"shed={st.shed}  wasted={st.wasted_g:.4f}g")
    if tenants:
        att = " ".join(f"{name}={st.slo_attainment(name):.0%}"
                       f"({st.tenant_requests.get(name, 0)})"
                       for name in ("premium", "standard", "batch"))
        print(f"slo attainment: {att}")
    print(f"profiled e (kWh/level): {np.round(gw.profiles.e, 9)}")
    return gw


def engine_kv_kwargs(args) -> dict:
    """KV-layout engine kwargs shared by both serving modes."""
    kw = {"kv_int8": args.kv_int8,
          "prefill_chunk": args.prefill_chunk,
          "tp_degree": args.tp_degree}
    if args.paged:
        kw.update(paged=True, page_size=args.page_size,
                  n_pages=args.pages if args.pages > 0 else None,
                  prefix_cache=args.prefix_cache)
    return kw


def main(argv=None):
    """Run the service; returns the gateway (``--gateway``) or the
    scheduler, so an in-process caller can inspect what was served."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2_13b")
    ap.add_argument("--config", default="reduced",
                    choices=("reduced", "published"),
                    help="reduced CPU-sized config (vocab 512) or the "
                         "architecture's published widths; weights are "
                         "random either way")
    ap.add_argument("--max-len", type=int, default=96,
                    help="KV region per slot, in tokens (prompt + output)")
    ap.add_argument("--region", default="CA")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--hours", type=int, default=4)
    ap.add_argument("--requests", type=int, default=6,
                    help="requests per simulated hour")
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--decode-block", type=int, default=8,
                    help="tokens decoded per fused device dispatch")
    ap.add_argument("--xi", type=float, default=0.1,
                    help="Eq. 3 quality relaxation for the single-mix LP; "
                         "inert under --tenants (each class carries its "
                         "own xi)")
    ap.add_argument("--gateway", action="store_true",
                    help="closed-loop SproutGateway over regional pools")
    ap.add_argument("--regions", default="CA,TX",
                    help="comma-separated regions for --gateway pools")
    ap.add_argument("--load-cap", type=int, default=8,
                    help="per-pool in-flight cap for green routing")
    ap.add_argument("--migrate", action="store_true",
                    help="cross-region MigrationPlanner: move queued/"
                         "preempted work to greener pools at re-plan ticks "
                         "(--gateway only)")
    ap.add_argument("--forecast-horizon", type=float, default=0.0,
                    help="hours of intensity forecast the per-pool LP "
                         "re-plan (and migration) solves against; 0 = "
                         "instantaneous (--gateway only)")
    ap.add_argument("--tenants", action="store_true",
                    help="premium/standard/batch service classes: one LP "
                         "per (pool, tenant) with per-class quality "
                         "floors (--gateway only)")
    ap.add_argument("--slo", action="store_true",
                    help="arm the tenant classes' TTFT/TPOT latency "
                         "targets: requests carry deadlines and admission "
                         "routes on predicted completion time jointly "
                         "with greenness (implies --tenants)")
    ap.add_argument("--drain-at", type=float, default=-1.0,
                    help="simulated hour at which to drain a pool ahead "
                         "of maintenance (-1 = never; --gateway only)")
    ap.add_argument("--drain-region", default="",
                    help="region to drain at --drain-at (default: the "
                         "pool holding the most in-flight work at the "
                         "drain moment)")
    ap.add_argument("--paged", action="store_true",
                    help="block-table paged KV cache + paged decode kernel")
    ap.add_argument("--page-size", type=int, default=32,
                    help="tokens per KV page (128-256 on TPU; small pages "
                         "suit the reduced CPU config)")
    ap.add_argument("--pages", type=int, default=0,
                    help="page budget per engine (0 = dense-equivalent "
                         "n_slots * max_len worth of pages)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV cache (halves decode HBM traffic; "
                         "accounting profile follows)")
    ap.add_argument("--tp-degree", type=int, default=1,
                    help="tensor-parallel sharding per engine: params and "
                         "KV heads split over a (1, T) device mesh "
                         "(DESIGN.md §14). Needs >= T jax devices; on CPU "
                         "set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 before launch")
    ap.add_argument("--chaos", action="store_true",
                    help="arm the default fault-injection script (one "
                         "fault of every class aimed at the first pool) "
                         "and report recovery counters (--gateway only)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="FaultInjector seed for --chaos")
    ap.add_argument("--grid-provider", default="trace",
                    choices=("trace", "electricitymaps", "watttime"),
                    help="carbon-signal source: bundled synthetic traces "
                         "(default) or the live grid APIs via "
                         "GridSignalClient (needs --grid-token; tokenless "
                         "falls straight back to the traces)")
    ap.add_argument("--grid-token", default="",
                    help="API token for --grid-provider (never bundled; "
                         "empty = CI-safe trace fallback)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="continuous batching: admit arrivals against live "
                         "decode lanes as prefill chunks of this many "
                         "tokens interleaved into the decode scan "
                         "(0 = slot-epoch whole-prompt prefill)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over the paged KV store "
                         "(requires --paged): shared full prompt pages are "
                         "content-hashed, refcounted, and adopted by later "
                         "requests with copy-on-write on divergence — "
                         "cached prompt spans skip prefill entirely and "
                         "Eq. 1 accounting credits the skipped tokens")
    args = ap.parse_args(argv)
    if args.slo:
        args.tenants = True

    setup_compile_cache()
    hw = hardware_for(jax.devices()[0].device_kind)
    cfg, params = load_model(args.arch, args.config)
    if args.gateway:
        return run_gateway(args, cfg, params, hw)
    grid = CarbonIntensityProvider(args.region, "jun")
    energy = EnergyModel(hw)
    profile = LLAMA2_13B.with_int8_kv() if args.kv_int8 else LLAMA2_13B
    directives = DirectiveSet()
    profiles = LevelProfiles.fresh()
    evaluator = QualityEvaluator(sample_size=200)
    workload = Workload(seed=0)
    rng = np.random.default_rng(0)
    q = np.ones(3) / 3
    plan = {"x": np.ones(3) / 3}

    sched = CarbonAwareScheduler(
        [InferenceEngine(cfg, params, n_slots=args.slots,
                         max_len=args.max_len, seed=i,
                         decode_block=args.decode_block,
                         **engine_kv_kwargs(args))
         for i in range(args.replicas)],
        directives,
        level_fn=lambda: int(rng.choice(3, p=plan["x"])))

    total_g = served = 0
    for hour in range(args.hours):
        k0 = grid.intensity(hour)
        if profiles.counts.min() >= 2:
            sol = solve_directive_lp(
                profiles.e, profiles.p, q, k0=k0,
                k1=hw.embodied_gco2 / hw.lifetime_s,
                k0_min=grid.k_min, k0_max=grid.k_max, xi=args.xi)
            plan["x"] = sol.x
        pool = [workload.sample_request(hour + i * 0.01) for i in range(300)]
        q = evaluator.evaluate(pool).q
        for i in range(args.requests):
            sched.submit(ServeRequest(0, f"request {hour}:{i} — explain "
                                      "briefly.", max_new_tokens=args.max_new))
        for f in sched.run():
            kwh = energy.request_energy_kwh(profile, f.prompt_tokens,
                                            f.gen_tokens)
            total_g += k0 * kwh * 1.2
            profiles.update(f.directive_level, kwh, f.latency_s)
            served += 1
        mixes = np.round(plan["x"], 2)
        print(f"hour {hour}: CI={k0:5.0f} gCO2/kWh  served={served:3d}  "
              f"x={mixes}", flush=True)
        sched.finished = []
    for req, reason in sched.rejected:
        print(f"rejected rid={req.rid}: {reason}", flush=True)
    print(f"total (13B-scale estimate): {total_g:.3f} gCO2 "
          f"across {served} requests")
    return sched


if __name__ == "__main__":
    main()
