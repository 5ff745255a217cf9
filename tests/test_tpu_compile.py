"""Compile guard: the Pallas kernels compile for a described TPU v5e.

Nothing here runs on a chip. The TPU compiler that ships with jax compiles
for a v5e:2x2 topology that is described, not attached, and refuses what
the chip's compiler would refuse: a block shape off the (8, 128) tiling
rule, scratch that outgrows VMEM, a kernel that cannot lower. Interpret
mode, which the other kernel suites use, checks none of that.

Shapes are granite_3_2b's published widths (32 query heads, 8 KV heads,
head dim 64, d_model 2048) at the engine's 128-token pages. The topology
is described inside a fixture, never at import: only one process at a time
may load the TPU library, and every xdist worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rmsnorm import fused_rmsnorm

B, H, KVH, D, D_MODEL = 8, 32, 8, 64, 2048
PAGE, MAX_PAGES, N_PAGES = 128, 8, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
             for s, dt in shapes]
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel did not lower natively"


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_attention_compiles_for_v5e(one_chip, kv_dtype):
    kv = jnp.dtype(kv_dtype)
    shapes = [((B, H, D), jnp.bfloat16), ((N_PAGES, PAGE, KVH, D), kv),
              ((N_PAGES, PAGE, KVH, D), kv), ((B, MAX_PAGES), jnp.int32),
              ((B,), jnp.int32)]
    if kv == jnp.int8:
        shapes += [((N_PAGES, PAGE, KVH), jnp.float32)] * 2
    _compile(paged_attention, *shapes, sharding=one_chip)


@pytest.mark.parametrize("t", [128, 512])
def test_flash_attention_compiles_for_v5e(one_chip, t):
    _compile(flash_attention, ((1, H, t, D), jnp.bfloat16),
             ((1, KVH, t, D), jnp.bfloat16), ((1, KVH, t, D), jnp.bfloat16),
             sharding=one_chip)


def test_fused_rmsnorm_compiles_for_v5e(one_chip):
    _compile(fused_rmsnorm, ((256, D_MODEL), jnp.bfloat16),
             ((D_MODEL,), jnp.float32), ((256, D_MODEL), jnp.bfloat16),
             sharding=one_chip)
