"""Profiler trace -> plain events -> the numbers per-layer readers use.

``normalize`` reads the ``.xplane.pb`` the JAX profiler writes, keeping
three lists of ``[name, start_ns, duration_ns]`` on the trace's one clock:
``device_ops`` (the "XLA Ops" lines of the TPU planes), ``device_modules``
(their "XLA Modules" lines: one event per compiled program run) and
``host_spans`` (the benchmark's own ``bench.*`` TraceAnnotation spans).
Everything after that works on the plain lists, so the reduction is
checked on a small recorded trace without a chip.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, List, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def normalize(trace_dir: str) -> Dict[str, list]:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"device_ops": [], "device_modules": [], "host_spans": []}
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                key = {"XLA Ops": "device_ops",
                       "XLA Modules": "device_modules"}.get(line.name)
                if key:
                    out[key].extend([e.name, float(e.start_ns),
                                     float(e.duration_ns)]
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host_spans"].extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name.startswith("bench."))
    return out


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    merged: List[List[float]] = []
    for s, e in sorted((s, s + d) for s, d in intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_intervals(tr: dict) -> List[Tuple[float, float]]:
    return union((s, d) for _, s, d in tr["device_ops"])


def overlap_ns(intervals, t0: float, t1: float) -> float:
    return sum(max(0.0, min(e, t1) - max(s, t0)) for s, e in intervals)


def spans(tr: dict, name: str) -> List[Tuple[float, float]]:
    return sorted((s, s + d) for n, s, d in tr["host_spans"] if n == name)


def window(tr: dict) -> Tuple[float, float]:
    """The traced window: the benchmark's ``bench.window`` span."""
    (w,) = spans(tr, "bench.window")
    return w


def busy_s(tr: dict) -> float:
    t0, t1 = window(tr)
    return overlap_ns(busy_intervals(tr), t0, t1) / 1e9


def module_s(tr: dict, prefix: str) -> float:
    return sum(d for n, _, d in tr["device_modules"]
               if n.startswith(prefix)) / 1e9


# ops that only enclose others: their time is their body's
_ENCLOSING = ("while", "conditional", "call")


def op_kind(event_name: str) -> str:
    """An op's kind from its trace name, e.g. ``"%fusion.406 = bf16[...]
    fusion(...)"`` -> ``"fusion"``, ``"%broadcast.51.clone = ..."`` ->
    ``"broadcast"``."""
    return event_name.split(" = ")[0].lstrip("%").split(".")[0]


def op_s(tr: dict, kind: str) -> float:
    return sum(d for n, _, d in tr["device_ops"] if op_kind(n) == kind) / 1e9


def top_ops(tr: dict, n: int = 10) -> List[list]:
    """Device time by op kind, enclosing loops left out."""
    by: Dict[str, float] = {}
    for name, _, d in tr["device_ops"]:
        k = op_kind(name)
        if k not in _ENCLOSING:
            by[k] = by.get(k, 0.0) + d
    return [[k, v / 1e9] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: dict, n: int = 10) -> List[list]:
    """The longest device-idle gaps inside the window, each named by the
    innermost benchmark host span open at its midpoint."""
    t0, t1 = window(tr)
    busy = [(max(s, t0), min(e, t1)) for s, e in busy_intervals(tr)
            if e > t0 and s < t1]
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = sorted(((s, s + d, nm) for nm, s, d in tr["host_spans"]
                   if nm != "bench.window"), key=lambda x: x[1] - x[0])
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + e)
        label = next((nm for hs, he, nm in host if hs <= mid < he), "none")
        out.append([label, (e - s) / 1e9])
    return out
