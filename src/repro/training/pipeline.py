"""GPipe-style pipeline parallelism over a mesh axis (off by default).

Each device on the ``stage`` axis owns one contiguous stage's parameters;
microbatches flow stage-to-stage via ``lax.ppermute`` inside ``shard_map``.
The schedule is the classic GPipe ramp: M microbatches over S stages take
M + S - 1 ticks with (S-1)/(M+S-1) bubble overhead — choose M >= 4S to keep
the bubble under 20%. Designed for the ``pod`` axis of the production mesh
(cross-pod DCI hops carry exactly one microbatch activation per tick, the
cheapest possible inter-pod pattern for deep models).

``pipeline_apply`` is mesh-agnostic: it runs inside any shard_map whose
``axis_name`` enumerates stages; see tests/test_pipeline.py for the wiring.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp


PyTree = Any


def pipeline_apply(fn: Callable, stage_params: PyTree, microbatches,
                   axis_name: str = "stage"):
    """Run ``y_mb = fn(stage_params, x_mb)`` through S pipeline stages.

    ``fn``: one stage's computation (shape-preserving on the activation).
    ``stage_params``: THIS device's stage parameters (shard_map slices the
    stage axis before calling us).
    ``microbatches``: (M, ...) activations, replicated across stages.
    Returns (M, ...) outputs (replicated across stages after the final
    collect).
    """
    S = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    buf = jnp.zeros_like(microbatches[0])
    outs = jnp.zeros_like(microbatches)

    def tick(carry, t):
        buf, outs = carry
        # stage 0 ingests microbatch t while t < M; later stages consume
        # what the previous stage handed over on the last tick.
        inject = microbatches[jnp.clip(t, 0, M - 1)]
        x = jnp.where(idx == 0, inject, buf)
        y = fn(stage_params, x)
        handoff = jax.lax.ppermute(y, axis_name, perm)
        om = t - (S - 1)
        write = jnp.logical_and(idx == S - 1, om >= 0)
        outs = outs.at[jnp.clip(om, 0, M - 1)].set(
            jnp.where(write, y, outs[jnp.clip(om, 0, M - 1)]))
        return (handoff, outs), None

    (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(T))
    # results live on the last stage; replicate to every stage
    outs = jax.lax.psum(jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)),
                        axis_name)
    return outs


def bubble_fraction(n_microbatches: int, n_stages: int) -> float:
    """GPipe idle fraction — the napkin number behind 'M >= 4S'."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
