"""Production mesh builders.

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run pins the device count before any jax
call; tests and benches must keep seeing 1 CPU device).

Every mesh is built with ``AxisType.Auto`` axes: ``jax.make_mesh`` defaults
to explicit-sharding axes, under which the unchanged model code would have
to annotate every gather and matmul. Auto axes leave placement to GSPMD
propagation from the parameter and cache shardings (DESIGN.md §14).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_tp_mesh(tp_degree: int):
    """Serving mesh for tensor-parallel decode: ``(1, tp)`` over axes
    ``("data", "model")`` on the first ``tp_degree`` local devices.

    The degenerate data axis keeps the axis names identical to the
    production mesh, so the same ``launch/sharding.py`` rules derive the
    specs (CPU CI forces the device pool with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""
    assert tp_degree >= 1
    if jax.device_count() < tp_degree:
        raise ValueError(
            f"tp_degree={tp_degree} needs {tp_degree} devices but jax sees "
            f"{jax.device_count()}; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={tp_degree} (or more) "
            f"before importing jax")
    return _auto_mesh((1, tp_degree), ("data", "model"),
                      jax.devices()[:tp_degree])


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model).

    The dry-run pins ``--xla_force_host_platform_device_count=512``; the
    single-pod mesh uses the first 256 of those placeholder devices."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    return _auto_mesh(shape, axes, jax.devices()[:n])


def batch_axes(multi_pod: bool = False):
    """Mesh axes the global batch shards over (DP spans pods)."""
    return ("pod", "data") if multi_pod else ("data",)


def fsdp_axes(multi_pod: bool = False):
    """Mesh axes parameter 'dense' dims shard over (ZeRO-style)."""
    return ("pod", "data") if multi_pod else ("data",)


def ep_axes(multi_pod: bool = False):
    """Mesh axes MoE experts shard over (expert parallelism)."""
    return ("pod", "data") if multi_pod else ("data",)
