"""Bring-up smoke test: serve granite_3_2b at its published widths on a TPU.

    python chip_smoke.py           # one chip: phases A and B
    python chip_smoke.py --tp 4    # four chips: a tp=4 engine against tp=1

Phase A drives the serving launcher's own ``main`` (``repro.launch.serve``)
with the carbon-aware gateway, paged KV in 128-token pages, the radix
prefix cache and chunked prefill, at granite_3_2b's published widths with
random weights drawn from a seed. Every request must be served, none
rejected, and every emitted token id must lie in the vocabulary; the
engine's compiled decode program must contain the native paged kernel
(``tpu_custom_call``), not its interpret mode or the XLA reference.

Phase B compares the native paged-attention kernel with the float32
reference (``kernels/ref.py``) at granite widths and 128-token pages, for
bf16 and for int8 KV, and fails above ``KERNEL_ERR_BOUND``.

``--tp 4`` runs only the tensor-parallel comparison: a tp=4 engine against
a tp=1 engine on the same greedy requests. It fails when the first-step
(prefill) logits differ by more than ``TP_LOGITS_REL_BOUND`` of their
largest magnitude, and reports greedy-token agreement.

Everything runs in this one process, which holds the chip. Without a TPU,
or without the repository's ``src/`` beside this file, the script exits
non-zero and prints no verdict. Compile seconds, phase seconds and peak
device memory are printed for information; they are not measurements. The
last line of stdout is the JSON verdict naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ARCH = "granite_3_2b"
SEED = 0
PAGE = 128
MAX_LEN = 1024
HOURS = 2
REQUESTS_PER_HOUR = 3
# bf16 output rounding (2**-8 relative) plus single-pass MXU products in
# the kernel's float32 dots, against a float32 "highest"-precision oracle
KERNEL_ERR_BOUND = 3e-2
# bf16 partial sums all-reduced in a different order across 40 layers: a
# few percent of the logits' range; a broken sharding is off by O(1)
TP_LOGITS_REL_BOUND = 0.1
TP_PROMPTS = ("The carbon intensity of the grid", "Summarize briefly:",
              "Explain paged attention to a new engineer.", "hello")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileClock:
    """Counts the backend compiles JAX reports, and sums their seconds."""

    def __init__(self, monitoring) -> None:
        self.seconds, self.count = 0.0, 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def phase_serve(jax, clock) -> None:
    """Phase A: the launcher's main path at published widths."""
    from repro.analysis.jaxpr_audit import instrument
    from repro.launch import serve
    from repro.serving.gateway import SproutGateway

    argv = ["--arch", ARCH, "--config", "published", "--gateway", "--paged",
            "--prefix-cache", "--prefill-chunk", str(PAGE),
            "--page-size", str(PAGE), "--max-len", str(MAX_LEN),
            "--replicas", "1", "--hours", str(HOURS),
            "--requests", str(REQUESTS_PER_HOUR), "--max-new", "16"]
    print(f"phase A: serve {' '.join(argv)}", flush=True)
    emitted = []
    account = SproutGateway._account

    def record(self, pool, fin):          # observe every finished request
        emitted.append(list(fin.token_ids))
        return account(self, pool, fin)

    t0, c0 = time.monotonic(), clock.seconds
    SproutGateway._account = record
    try:
        gw = serve.main(argv)
    finally:
        SproutGateway._account = account
    eng = gw.pools[0].scheduler.engines[0]
    vocab = eng.cfg.vocab_size
    expected = HOURS * REQUESTS_PER_HOUR
    n_tok = sum(map(len, emitted))
    print(f"phase A: served={gw.stats.requests}/{expected} "
          f"rejected={gw.stats.rejected} tokens={n_tok} vocab={vocab} "
          f"d_model={eng.cfg.d_model} layers={eng.cfg.n_layers} "
          f"seconds={time.monotonic() - t0:.1f} "
          f"compile_seconds={clock.seconds - c0:.1f}", flush=True)
    check(eng.cfg.d_model == 2048 and eng.cfg.n_layers == 40,
          "phase A did not serve the published widths")
    check(gw.stats.requests == expected and len(emitted) == expected,
          f"served {gw.stats.requests} of {expected} requests")
    check(gw.stats.rejected == 0, f"{gw.stats.rejected} requests rejected")
    check(n_tok > 0 and all(0 <= t < vocab for ids in emitted for t in ids),
          "a token id outside the vocabulary (or no tokens at all)")

    # the decode program this engine dispatches, re-lowered from the
    # argument specs the jaxpr audit's recorder captures on first dispatch
    eng.entry_points = {}
    rec = instrument(eng)
    eng.submit(eng.tok.encode("one more request, for the compiled text"),
               max_new_tokens=8)
    eng.run_to_completion()
    names = sorted(n for n in rec.programs if n.startswith("decode_"))
    check(bool(names), "no decode program was dispatched")
    fn, specs = rec.programs[names[0]]
    text = fn.lower(*specs).compile().as_text()
    check("tpu_custom_call" in text,
          f"{names[0]} holds no tpu_custom_call: the paged kernel did not "
          "lower natively")
    print(f"phase A: {names[0]} contains tpu_custom_call; peak device "
          f"memory {peak_bytes(jax.devices()[0])}", flush=True)


def phase_kernel(jax, clock) -> None:
    """Phase B: native paged attention against the float32 reference."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    B, H, KVH, D, P, max_pages = 8, 32, 8, 64, 64, MAX_LEN // PAGE
    L, layer = 3, jnp.int32(1)           # a layer-stacked store, middle layer
    lengths = jnp.asarray([1, 127, 128, 129, 300, 512, 777, MAX_LEN],
                          jnp.int32)
    rng = np.random.default_rng(SEED)
    bt = jnp.asarray(rng.permutation(P)[: B * max_pages]
                     .reshape(B, max_pages), jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(SEED), 7)
    q = jax.random.normal(ks[0], (B, H, D), jnp.bfloat16)
    kv, sc = (L, P, PAGE, KVH, D), (L, P, PAGE, KVH)
    cases = {
        "bf16": (jax.random.normal(ks[1], kv, jnp.bfloat16),
                 jax.random.normal(ks[2], kv, jnp.bfloat16), None, None),
        "int8": (jax.random.randint(ks[3], kv, -127, 128, jnp.int8),
                 jax.random.randint(ks[4], kv, -127, 128, jnp.int8),
                 jax.random.uniform(ks[5], sc, jnp.float32, 0.005, 0.02),
                 jax.random.uniform(ks[6], sc, jnp.float32, 0.005, 0.02)),
    }
    for name, (kp, vp, ksc, vsc) in cases.items():
        t0, c0 = time.monotonic(), clock.seconds
        out = ops.paged_attention(q, kp, vp, bt, lengths, layer, ksc, vsc,
                                  impl="pallas")
        with jax.default_matmul_precision("highest"):
            want = ref.paged_attention_ref(
                q.astype(jnp.float32), kp, vp, bt, lengths, layer, ksc, vsc)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
        print(f"phase B: paged_attention {name} B={B} H={H} KVH={KVH} "
              f"D={D} page={PAGE}: max_abs_err={err:.3e} "
              f"(bound {KERNEL_ERR_BOUND:.0e}) "
              f"seconds={time.monotonic() - t0:.1f} "
              f"compile_seconds={clock.seconds - c0:.1f}", flush=True)
        check(np.isfinite(err) and err <= KERNEL_ERR_BOUND,
              f"paged_attention {name} error {err} above {KERNEL_ERR_BOUND}")


def phase_tp(jax, clock, tp: int) -> None:
    """--tp: a tp-sharded engine against tp=1 on the same greedy requests."""
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve
    from repro.models import model as MD
    from repro.serving.engine import InferenceEngine

    cfg, params = serve.load_model(ARCH, "published", seed=SEED)
    streams, engines = {}, {}
    for t in (1, tp):
        t0, c0 = time.monotonic(), clock.seconds
        eng = InferenceEngine(cfg, params, n_slots=len(TP_PROMPTS),
                              max_len=MAX_LEN, paged=True, page_size=PAGE,
                              eos_id=-1, seed=SEED, tp_degree=t)
        for p in TP_PROMPTS:
            eng.submit(eng.tok.encode(p), max_new_tokens=16)
        eng.run_to_completion()
        streams[t] = [f.token_ids for f in sorted(eng.finished,
                                                   key=lambda f: f.rid)]
        engines[t] = eng
        print(f"tp={t}: served={len(eng.finished)}/{len(TP_PROMPTS)} "
              f"seconds={time.monotonic() - t0:.1f} "
              f"compile_seconds={clock.seconds - c0:.1f}", flush=True)
        check(len(eng.finished) == len(TP_PROMPTS), f"tp={t} lost requests")

    tok = engines[1].tok
    ids = [tok.encode(p) for p in TP_PROMPTS]
    toks = np.zeros((len(ids), max(map(len, ids))), np.int32)
    for i, x in enumerate(ids):
        toks[i, : len(x)] = x
    lens = jnp.asarray([len(x) for x in ids], jnp.int32)
    prefill = jax.jit(lambda p, t, n: MD.prefill(
        cfg, p, t, max_len=MAX_LEN, lengths=n)[0])
    logits = {}
    for t in (1, tp):
        full = prefill(engines[t].params, jnp.asarray(toks), lens)
        last = jnp.take_along_axis(full, (lens - 1)[:, None, None], axis=1)
        logits[t] = np.asarray(last[:, 0].astype(jnp.float32))
    diff = float(np.max(np.abs(logits[tp] - logits[1])))
    scale = float(np.max(np.abs(logits[1])))
    same = sum(a == b for x, y in zip(streams[1], streams[tp])
               for a, b in zip(x, y))
    total = sum(len(x) for x in streams[1])
    print(f"tp={tp} vs tp=1: first-step logits max_abs_diff={diff:.4e} "
          f"max_abs_logit={scale:.4e} rel={diff / scale:.4e} "
          f"(bound {TP_LOGITS_REL_BOUND}); greedy tokens agree "
          f"{same}/{total}; first tokens agree "
          f"{sum(x[0] == y[0] for x, y in zip(streams[1], streams[tp]))}"
          f"/{len(TP_PROMPTS)}", flush=True)
    check(np.isfinite(diff) and diff <= TP_LOGITS_REL_BOUND * scale,
          f"tp={tp} logits differ by {diff} (largest logit {scale})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp", type=int, default=1, choices=(1, 4),
                    help="4: run only the tp=4 against tp=1 comparison")
    args = ap.parse_args(argv)

    src = pathlib.Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repository source at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    import jax
    import jax.monitoring

    devs = jax.devices()
    platform, kind, count = devs[0].platform, devs[0].device_kind, len(devs)
    print(f"device: platform={platform} kind={kind} count={count}",
          flush=True)
    if platform != "tpu":
        print("chip_smoke: no TPU; there is no CPU fallback", file=sys.stderr)
        return 1
    check(count >= args.tp, f"--tp {args.tp} needs {args.tp} chips")
    check("SPROUT_KERNEL_IMPL" not in os.environ,
          "SPROUT_KERNEL_IMPL is set; the smoke test runs the auto path")
    from repro.kernels import ops
    from repro.launch import serve
    check(ops.resolve_impl("auto") == "pallas",
          "kernel dispatch does not resolve to the native Pallas kernel")
    print(f"compile cache: {serve.setup_compile_cache()}", flush=True)
    clock = CompileClock(jax.monitoring)

    if args.tp == 1:
        phase_serve(jax, clock)
        phase_kernel(jax, clock)
    else:
        phase_tp(jax, clock, args.tp)
    print(f"compile seconds in all: {clock.seconds:.1f} over "
          f"{clock.count} compiles", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
