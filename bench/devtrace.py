"""Profiler trace -> plain events -> the numbers per-layer readers use.

``normalize`` reads the ``.xplane.pb`` the JAX profiler writes, keeping
three lists on the trace's one clock: ``device_ops`` (the "XLA Ops" lines
of the TPU planes) and ``device_modules`` (their "XLA Modules" lines: one
event per compiled program run), each event ``[name, start_ns,
duration_ns, device]`` with the device's index from its plane
(``/device:TPU:<index>``), and ``host_spans``, ``[name, start_ns,
duration_ns]`` for the benchmark's own ``bench.*`` TraceAnnotation spans
and the program's ``sprout.*`` spans. Everything after that works on the
plain lists, so the reduction is checked on a small recorded trace without
a chip.

Device times are per device: a time is the mean over the devices that ran
an operation in the trace, so that on one device it is that device's
time, and on a tensor-parallel cell one chip's share of the work.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, List, Optional, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIXES = ("bench.", "sprout.")


def normalize(trace_dir: str) -> Dict[str, list]:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"device_ops": [], "device_modules": [], "host_spans": []}
    for plane in pd.planes:
        dev = _DEVICE_PLANE.match(plane.name)
        if dev:
            for line in plane.lines:
                key = {"XLA Ops": "device_ops",
                       "XLA Modules": "device_modules"}.get(line.name)
                if key:
                    out[key].extend([e.name, float(e.start_ns),
                                     float(e.duration_ns), int(dev[1])]
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host_spans"].extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(HOST_PREFIXES))
    return out


def devices(tr: dict) -> List[int]:
    """The devices that ran an operation in the trace."""
    return sorted({ev[3] for ev in tr["device_ops"]})


def _per_device(total: float, tr: dict) -> float:
    return total / max(len(devices(tr)), 1)


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    merged: List[List[float]] = []
    for s, e in sorted((s, s + d) for s, d in intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_intervals(tr: dict, device: Optional[int] = None
                   ) -> List[Tuple[float, float]]:
    """When one device (or, with ``device`` None, any) runs an op."""
    return union((s, d) for _, s, d, dev in tr["device_ops"]
                 if device is None or dev == device)


def overlap_ns(intervals, t0: float, t1: float) -> float:
    return sum(max(0.0, min(e, t1) - max(s, t0)) for s, e in intervals)


def spans(tr: dict, name: str) -> List[Tuple[float, float]]:
    return sorted((s, s + d) for n, s, d in tr["host_spans"] if n == name)


def window(tr: dict) -> Tuple[float, float]:
    """The traced window: the benchmark's ``bench.window`` span."""
    (w,) = spans(tr, "bench.window")
    return w


def busy_s(tr: dict) -> float:
    """Seconds of the window in which a device ran an op, per device."""
    t0, t1 = window(tr)
    return _per_device(sum(overlap_ns(busy_intervals(tr, dev), t0, t1)
                           for dev in devices(tr)), tr) / 1e9


def module_s(tr: dict, prefix: str) -> float:
    return _per_device(sum(d for n, _, d, _ in tr["device_modules"]
                           if n.startswith(prefix)), tr) / 1e9


# ops that only enclose others: their time is their body's
_ENCLOSING = ("while", "conditional", "call")


def op_kind(event_name: str) -> str:
    """An op's kind from its trace name, e.g. ``"%fusion.406 = bf16[...]
    fusion(...)"`` -> ``"fusion"``, ``"%broadcast.51.clone = ..."`` ->
    ``"broadcast"``."""
    return event_name.split(" = ")[0].lstrip("%").split(".")[0]


def op_s(tr: dict, kind: str) -> float:
    return _per_device(sum(d for n, _, d, _ in tr["device_ops"]
                           if op_kind(n) == kind), tr) / 1e9


def top_ops(tr: dict, n: int = 10) -> List[list]:
    """Device time per device by op kind, enclosing loops left out."""
    by: Dict[str, float] = {}
    for name, _, d, _ in tr["device_ops"]:
        k = op_kind(name)
        if k not in _ENCLOSING:
            by[k] = by.get(k, 0.0) + d
    return [[k, _per_device(v, tr) / 1e9] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: dict, n: int = 10) -> List[list]:
    """The longest gaps inside the window in which no device runs an op,
    each named by the innermost host span open at its midpoint."""
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
