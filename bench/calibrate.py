"""Readings behind a configuration's ``check`` limits, several seeds in one
process (the compiled programs are shared across seeds).

    python bench/calibrate.py --workload <name> --seeds 1,2 --seconds 8

For each seed it serves the cell's traffic as a run does, takes the same
sample of greedy requests, and runs the run's own comparison twice: on the
served tokens (the program's reading, which has to be correct) and with
the tokens the fp8 control puts first at the same positions in their place
(the control's reading, which has to be not correct). The benchmark's own
runs never run the control. Writes ``bench/traces/calibrate_<workload>.json``.

On one v5e a third seed in one process ran out of HBM loading the fused
programs; give each process two seeds at most.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

if __name__ == "__main__":
    _ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness, spec  # noqa: E402


def readings(cell, seed: int, seconds: float, clock, entry_points=None):
    s = harness.serve(cell, seed, seconds, False, clock, time.monotonic(),
                      entry_points=entry_points)
    # share the fused programs across seeds; the engine's prefill entry
    # points close over the engine itself, and would keep its pool alive
    eps = {k: v for k, v in s["eng"].entry_points.items()
           if not k.startswith("prefill_")}
    compared = harness.check(cell, s, seed)
    control = harness.check(cell, s, seed, control="fp8")
    got = {c["name"]: c["value"] for c in compared}
    ctl = {c["name"]: c["value"] for c in control}
    # the next seed's weights and pool must not meet this seed's on the chip
    s.clear()
    gc.collect()
    return eps, {"seed": seed, "program_max_gap": got["max_logit_gap"],
                 "program_correct": all(map(harness.passes, compared)),
                 "control_max_gap": ctl["max_logit_gap"],
                 "control_correct": all(map(harness.passes, control)),
                 "tokens_checked": got["tokens_checked"],
                 "compared": got}


def main(argv=None, *, root=None, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    root = pathlib.Path(root or harness.ROOT)
    cell = spec.load_cell(root, args.workload)
    devs = harness.device_info(cell, allow_cpu)
    if devs is None:
        return 1
    if devs[0].platform == "tpu":
        harness.setup_compile_cache(root)
    clock = harness.CompileClock()
    eps, rows = None, []
    for seed in (int(x) for x in args.seeds.split(",")):
        eps, row = readings(cell, seed, args.seconds, clock, eps)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {
        "workload": cell.name, "limit": cell.config["check"],
        "program_max_gap_max": max(r["program_max_gap"] for r in rows),
        "control_max_gap_min": min(r["control_max_gap"] for r in rows),
        "program_all_correct": all(r["program_correct"] for r in rows),
        "control_any_correct": any(r["control_correct"] for r in rows),
        "rows": rows}
    out = root / "bench" / "traces"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"calibrate_{cell.name}.json").write_text(json.dumps(summary))
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
