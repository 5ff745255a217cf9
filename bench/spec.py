"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration (its ``file``) and a traffic mix
(``bench/traffic/<mix>.json``); each per-layer metric is a reader in
``bench/metrics/<name>.py``, or ``<base>.py`` for ``<base>.<cell kind>``.
The configuration names its plain reference (``reference.module``: a
class ``Reference`` in ``bench/reference/<module>.py``) and the module
that counts its operations and bytes (``counts``: ``bench/<counts>.py``,
``counts`` where the key is absent). Files are looked up under the
checkout root first, then beside this file, so a cell defined entirely in
another directory runs with the readers that ship here, and a new
architecture brings its reference and counts as new files.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Callable, List, Optional

from bench import loadgen

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    root: pathlib.Path
    name: str
    chips: int
    config: dict          # the configuration file as run
    mix: dict             # the traffic mix file
    mix_name: str
    end_to_end: List[dict]
    per_layer: List[dict]


def _find(root: pathlib.Path, rel: str) -> Optional[pathlib.Path]:
    for base in (root / "bench", HERE):
        p = base / rel
        if p.is_file():
            return p
    return None


def _applies(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(root, workload: str) -> Cell:
    root = pathlib.Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix_path = _find(root, f"traffic/{w['traffic']}.json")
    if mix_path is None:
        raise FileNotFoundError(f"no traffic mix {w['traffic']!r}")
    mix = loadgen.load_mix(mix_path)
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload, names)]
    return Cell(root, workload, int(w["chips"]), config, mix, w["traffic"],
                e2e, per_layer)


@functools.lru_cache(maxsize=None)
def _module(path: pathlib.Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(root, metric: str) -> Callable:
    """The ``read(run)`` function of a per-layer metric."""
    root = pathlib.Path(root)
    path = _find(root, f"metrics/{metric}.py") or \
        _find(root, f"metrics/{metric.split('.')[0]}.py")
    if path is None:
        raise FileNotFoundError(f"no reader for metric {metric!r}")
    return _module(path).read


def reference(cell: Cell) -> type:
    """The plain reference class the configuration names."""
    name = cell.config["reference"]["module"]
    path = _find(cell.root, f"reference/{name}.py")
    if path is None:
        raise FileNotFoundError(f"no reference module {name!r}")
    return _module(path).Reference


def counts(cell: Cell) -> ModuleType:
    """The module of operation and byte counts the configuration names."""
    name = cell.config.get("counts", "counts")
    path = _find(cell.root, f"{name}.py")
    if path is None:
        raise FileNotFoundError(f"no counts module {name!r}")
    return _module(path)
