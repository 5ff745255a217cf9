"""Where a traced benchmark window went, by the program's own spans and
device scopes.

Usage:
    python scripts/trace_report.py bench/traces/<cell> [--json OUT]

Reads the newest ``.xplane.pb`` under the directory (``bench/run.py
--trace 1`` writes one per cell) and prints, for the ``bench.window``
span:

* idle: device-idle time split by the innermost host span open on the
  serving thread at that moment (``sprout.*`` or ``bench.*``; ``none``
  between them), ms and share of all idle time;
* scopes: device time of the ``jit_fused`` programs by the innermost
  ``sprout.*`` named scope in each op's ``op_name`` (``none`` outside
  every scope; a TPU op keeps ``op_name`` in its ``tf_op`` stat);
* steps: gateway steps and dispatches, host spans per step, the longest
  gateway steps by innermost span, and garbage collections over 1 ms.
"""

from __future__ import annotations

import argparse
import glob
import json
import pathlib
import re
import sys
from collections import defaultdict

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import devtrace  # noqa: E402

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SCOPE = re.compile(r"sprout\.[a-z_]+")
MARKS = ("sprout.request.first_token",)
OP_NAME_STAT = "tf_op"  # where a TPU's XLA Ops events keep op_name
ENCLOSING = ("while", "conditional", "call")  # their time is their body's


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    tr = {"path": paths[-1], "ops": [], "host": defaultdict(list)}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr["ops"].extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                key = f"{plane.name}/{line.name}"
                tr["host"][key].extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                    for e in line.events
                    if e.name.startswith(("sprout.", "bench."))
                )
    return tr


def innermost(spans, t0: float, t1: float):
    """Split [t0, t1) into (start, end, name) segments, each named by the
    innermost of the properly nested ``spans`` open there."""
    segs, stack, cur = [], [], t0
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > cur:
                segs.append((cur, end, top))
                cur = end
        if s > cur:
            segs.append((cur, s, stack[-1][1] if stack else "none"))
            cur = s
        stack.append((e, name))
    while stack:
        end, top = stack.pop()
        if end > cur:
            segs.append((cur, end, top))
            cur = end
    if t1 > cur:
        segs.append((cur, t1, "none"))
    return [(max(s, t0), min(e, t1), n) for s, e, n in segs if e > t0 and s < t1]


def overlap_by_name(segs, intervals) -> dict:
    """Per segment name, the time it shares with sorted disjoint intervals."""
    out, j = defaultdict(float), 0
    for s, e, name in segs:
        while j < len(intervals) and intervals[j][1] <= s:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < e:
            out[name] += min(e, intervals[k][1]) - max(s, intervals[k][0])
            k += 1
    return dict(out)


def report(tr: dict) -> dict:
    (line, window) = next(
        (k, ev) for k, evs in tr["host"].items() for ev in evs if ev[0] == "bench.window"
    )
    _, t0, t1, _ = window
    serving = [
        (n, s, e) for n, s, e, _ in tr["host"][line] if n != "bench.window" and n not in MARKS
    ]
    segs = innermost(serving, t0, t1)
    busy = devtrace.union((s, e - s) for _, s, e in tr["ops"])
    edges = [t0] + [x for s, e in busy if e > t0 and s < t1 for x in (max(s, t0), min(e, t1))]
    edges.append(t1)
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    idle_ns = sum(e - s for s, e in idle)
    by_span = overlap_by_name(segs, idle)
    out = {
        "window_ms": (t1 - t0) / 1e6,
        "idle_ms": idle_ns / 1e6,
        "idle_by_innermost_span": {
            n: {"ms": v / 1e6, "share": v / idle_ns if idle_ns else 0.0}
            for n, v in sorted(by_span.items(), key=lambda kv: -kv[1])
        },
    }

    out.update(fused_scopes(tr["path"], t0, t1))

    inside = [ev for ev in tr["host"][line] if t0 <= ev[1] and ev[2] <= t1]
    steps = [ev for ev in inside if ev[0] == "sprout.gateway.step"]
    count = defaultdict(int)
    for ev in inside:
        count[ev[0]] += 1
    n_spans = sum(v for k, v in count.items() if k.startswith("sprout.") and k not in MARKS)
    out["counts"] = dict(sorted(count.items()))
    out["sprout_spans_per_gateway_step"] = n_spans / len(steps) if steps else None
    longest = []
    for _, s, e, _ in sorted(steps, key=lambda x: x[1] - x[2])[:5]:
        parts = defaultdict(float)
        for a, b, n in innermost(serving, s, e):
            parts[n] += b - a
        longest.append(
            {
                "at_ms": (s - t0) / 1e6,
                "ms": (e - s) / 1e6,
                "innermost_ms": {n: v / 1e6 for n, v in sorted(parts.items(), key=lambda kv: -kv[1])},
            }
        )
    out["longest_gateway_steps"] = longest
    gcs = [ev for evs in tr["host"].values() for ev in evs if ev[0] == "sprout.gc"]
    gcs = [ev for ev in gcs if t0 <= ev[1] < t1]
    out["gc"] = {
        "count": len(gcs),
        "ms": sum(e - s for _, s, e, _ in gcs) / 1e6,
        "over_1ms": [
            {"at_ms": (s - t0) / 1e6, "ms": (e - s) / 1e6, "generation": st.get("generation")}
            for _, s, e, st in gcs
            if e - s > 1e6
        ],
    }
    return out


def fused_scopes(path: str, t0: float, t1: float) -> dict:
    """Device time of the fused programs' ops inside [t0, t1), by the
    innermost ``sprout.*`` scope of their ``op_name``. A TPU's ``XLA Ops``
    carry ``op_name`` in their event metadata's ``tf_op`` stat, which
    ``jax.profiler.ProfileData`` does not expose, so this reads the raw
    XSpace."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xs = xplane_pb2.XSpace()
    xs.ParseFromString(pathlib.Path(path).read_bytes())
    by_scope = defaultdict(float)
    for plane in xs.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        tf_op = [k for k, v in plane.stat_metadata.items() if v.name == OP_NAME_STAT]
        op_name = {
            k: st.str_value
            for k, md in plane.event_metadata.items()
            for st in md.stats
            if st.metadata_id in tf_op
        }
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                name = op_name.get(e.metadata_id, "")
                kind = devtrace.op_kind(plane.event_metadata[e.metadata_id].name)
                s = line.timestamp_ns + e.offset_ps / 1e3
                if name.startswith("jit(fused)") and kind not in ENCLOSING and t0 <= s < t1:
                    scopes = SCOPE.findall(name)
                    by_scope[scopes[-1] if scopes else "none"] += e.duration_ps / 1e3
    total = sum(by_scope.values())
    return {
        "fused_device_ms": total / 1e6,
        "fused_by_innermost_scope": {
            n: {"ms": v / 1e6, "share": v / total if total else 0.0}
            for n, v in sorted(by_scope.items(), key=lambda kv: -kv[1])
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--json", help="also write the report here")
    args = ap.parse_args(argv)
    out = report(load(args.trace_dir))
    text = json.dumps(out, indent=1)
    print(text)
    if args.json:
        pathlib.Path(args.json).write_text(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
