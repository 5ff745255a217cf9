"""The program's own host events in a traced run: the ``sprout.*`` spans
and marks that ``src/repro/serving/spans.py`` records, with their stats.

``events(run)`` re-reads the run's ``.xplane.pb`` (the directory
``harness.serve`` writes: ``bench/traces/<cell>`` under the checkout) and
returns ``[name, start_ns, duration_ns, stats]`` for each ``sprout.*``
event wholly inside the ``bench.window`` span, on the profiler's clock,
sorted by start. A program without those spans gives an empty list, and
a run without a trace gives ``None``. Each file is parsed once.
"""
from __future__ import annotations

import functools
import glob
from typing import List, Optional

PREFIX = "sprout."
WINDOW = "bench.window"


def trace_file(run) -> Optional[str]:
    if run.trace is None:
        return None
    d = run.cell.root / "bench" / "traces" / run.cell.name
    paths = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))
    return paths[-1] if paths else None


@functools.lru_cache(maxsize=4)
def read_file(path: str) -> List[list]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    found, window = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name == WINDOW:
                    window = (float(e.start_ns),
                              float(e.start_ns + e.duration_ns))
                elif name.startswith(PREFIX):
                    found.append([name, float(e.start_ns),
                                  float(e.duration_ns), dict(e.stats)])
    if window is None:
        return []
    t0, t1 = window
    return sorted((ev for ev in found
                   if ev[1] >= t0 and ev[1] + ev[2] <= t1),
                  key=lambda ev: ev[1])


def events(run) -> Optional[List[list]]:
    path = trace_file(run)
    return None if path is None else read_file(path)


def named(evs: List[list], name: str) -> List[list]:
    return [ev for ev in evs if ev[0] == name]


def mean_stat(run, name: str, stat: str) -> Optional[float]:
    """Mean of one stat over the window's events of one name."""
    vals = [ev[3][stat] for ev in named(events(run) or [], name)]
    return sum(vals) / len(vals) if vals else None
