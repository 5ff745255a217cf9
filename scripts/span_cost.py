"""Host cost of the serving path's spans, with the profiler off and on.

Usage:
    python scripts/span_cost.py [--n 10000]

Times ``n`` enters and exits of ``repro.serving.spans.span`` (bare, and
with the five stats ``sprout.engine.dispatch`` carries) and ``n`` calls
of ``spans.mark`` (three stats), first with no profiler running, then
inside an active ``jax.profiler`` trace written to a temporary directory.
Prints one JSON line of microseconds per call. Measure on the host that
serves: the cost is the host's, not the accelerator's.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


def per_call_us(fn, n: int) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return 1e6 * (time.perf_counter() - t0) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=10_000)
    args = ap.parse_args(argv)
    import jax

    from repro.serving import spans

    def bare():
        with spans.span(spans.ENGINE_PREPARE):
            pass

    def stats():
        with spans.span(spans.ENGINE_DISPATCH, bs=64, k=8, mode="full", chunk=128, cold=0):
            pass

    def mark():
        spans.mark(spans.FIRST_TOKEN, rid=1, wait_us=1.5, prefill_us=2.5)

    calls = {"span": bare, "span_stats": stats, "mark": mark}
    out = {"device_kind": jax.devices()[0].device_kind, "n": args.n}
    for fn in calls.values():  # first calls import and allocate
        fn()
    out["off_us"] = {k: per_call_us(fn, args.n) for k, fn in calls.items()}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        out["on_us"] = {k: per_call_us(fn, args.n) for k, fn in calls.items()}
        jax.profiler.stop_trace()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
