"""One generator for every traffic mix: ``bench/traffic/<mix>.json``.

A mix is data: the task table (lognormal prompt and per-directive-level
output lengths, the six tasks and weights of the Sprout paper's workload),
caps, sampling, and the arrival process. Request sizes and inter-arrival
gaps are drawn once from the mix's ``pool_seed``, so every run seed offers
the same work; the run seed orders the sizes within consecutive blocks of
``order_block`` requests, so every stretch of a run carries the same work
whatever the seed, and draws the prompt text. A block of 1 keeps the
pool's order for every seed: the gateway draws directive levels in
dispatch order, so a reorder pairs sizes with other levels and changes
the output lengths, and so the work, that a saturated window holds.

Every ``greedy_every``-th request of a sampled mix is greedy, so its
tokens can be held against the reference; a batch whose rows are all
greedy runs the engine's greedy programs, which set-up warms for batch
buckets up to ``greedy_buckets_max``.

Arrival kinds:
  ``poisson``  open loop, ``rate_per_s`` fixed in the file;
  ``backlog``  the harness keeps ``depth`` requests waiting beyond the
               engine's slots (a saturated batch queue).
"""
from __future__ import annotations

import json
import math
import pathlib
import string
from typing import Dict, List

import numpy as np

_LETTERS = np.frombuffer((string.ascii_lowercase + " ").encode(), np.uint8)


def load_mix(path) -> dict:
    mix = json.loads(pathlib.Path(path).read_text())
    for key in ("tasks", "task_weights", "pool_seed", "pool_size", "max_new",
                "min_new", "prompt_bytes_max", "order_block", "sampling",
                "arrival"):
        if key not in mix:
            raise KeyError(f"traffic mix {path} has no {key!r}")
    return mix


def _lognormal(rng, mean, std, lo=1.0):
    # the parameterisation of repro.core.workload._lognormal
    var = math.log(1.0 + (std / max(mean, 1e-9)) ** 2)
    mu = math.log(max(mean, 1e-9)) - var / 2
    return max(lo, float(rng.lognormal(mu, math.sqrt(var))))


def size_pool(mix: dict) -> List[dict]:
    """The mix's fixed set of request sizes: task, prompt bytes and the
    output budget per directive level (L0 >= L1 >= L2)."""
    rng = np.random.default_rng(mix["pool_seed"])
    names = sorted(mix["task_weights"])
    w = np.array([mix["task_weights"][n] for n in names], np.float64)
    scale = float(mix.get("token_scale", 1.0))
    pool = []
    for _ in range(mix["pool_size"]):
        task = names[int(rng.choice(len(names), p=w / w.sum()))]
        tp = mix["tasks"][task]
        gen = np.array([_lognormal(rng, m, s)
                        for m, s in zip(tp["gen_mean"], tp["gen_std"])])
        gen = np.maximum.accumulate(gen[::-1])[::-1]
        prompt = int(_lognormal(rng, tp["prompt_mean"], tp["prompt_std"]))
        pool.append({
            "task": task,
            "prompt_bytes": min(prompt, mix["prompt_bytes_max"]),
            "budgets": [int(np.clip(round(g / scale), mix["min_new"],
                                    mix["max_new"])) for g in gen]})
    return pool


class Traffic:
    """The requests of one run, in arrival order, from ``seed``."""

    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.seed = int(seed)
        self.pool = size_pool(mix)
        rng = np.random.default_rng([self.seed, 0])
        n, block = len(self.pool), int(mix["order_block"])
        self.order = np.concatenate([
            b + rng.permutation(min(block, n - b))
            for b in range(0, n, block)])
        arr = mix["arrival"]
        self.kind = arr["kind"]
        if self.kind == "poisson":
            gaps = np.random.default_rng([mix["pool_seed"], 1]).exponential(
                1.0 / arr["rate_per_s"], len(self.pool))
            self.offsets = np.cumsum(gaps)
        elif self.kind != "backlog":
            raise ValueError(f"unknown arrival kind {self.kind!r}")
        self.greedy_every = int(mix.get("greedy_every", 0))

    def offset(self, i: int) -> float:
        """Scheduled arrival of request ``i``, seconds after traffic starts
        (poisson only); the gap sequence repeats past the pool."""
        n = len(self.offsets)
        return float(self.offsets[-1] * (i // n) + self.offsets[i % n])

    def request(self, i: int) -> Dict:
        """Request ``i``: its task, user prompt text, output budget per
        directive level and sampling parameters."""
        shape = self.pool[self.order[i % len(self.pool)]]
        rng = np.random.default_rng([self.seed, 1, i])
        text = _LETTERS[rng.integers(0, len(_LETTERS),
                                     shape["prompt_bytes"])].tobytes().decode()
        s = self.mix["sampling"]
        greedy = s["temperature"] <= 0 or (
            self.greedy_every > 0 and i % self.greedy_every == 0)
        return {"task": shape["task"], "prompt": text,
                "budgets": list(shape["budgets"]),
                "temperature": 0.0 if greedy else float(s["temperature"]),
                "top_k": 0 if greedy else int(s.get("top_k", 0)),
                "top_p": 1.0 if greedy else float(s.get("top_p", 1.0)),
                "greedy": greedy}
