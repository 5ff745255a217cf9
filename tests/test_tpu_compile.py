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

The engine's fused paged programs are compiled here too, at those widths
with 4 layers: they must update the carried page store in place, with no
whole-store broadcast or dynamic-update-slice and no whole-store
temporaries beyond the layout copies at the loop's entry and exit.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rmsnorm import fused_rmsnorm
from repro.models import model as MD
from repro.serving.engine import InferenceEngine

B, H, KVH, D, D_MODEL = 8, 32, 8, 64, 2048
PAGE, MAX_PAGES, N_PAGES, N_LAYERS = 128, 8, 64, 4


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
    store = (N_LAYERS, N_PAGES, PAGE, KVH, D)
    shapes = [((B, H, D), jnp.bfloat16), (store, kv), (store, kv),
              ((B, MAX_PAGES), jnp.int32), ((B,), jnp.int32),
              ((), jnp.int32)]
    if kv == jnp.int8:
        shapes += [(store[:-1], jnp.float32)] * 2
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


@pytest.mark.parametrize("k,chunk", [(8, 0), (2, PAGE)])
def test_fused_paged_program_updates_store_in_place(one_chip, monkeypatch,
                                                    k, chunk):
    """The engine's k = 8 paged decode program, and its mixed program with
    one prompt chunk per step, at granite widths with 4 layers, 64 pages
    and 16 lanes, the cache donated: no op builds or rewrites a whole
    stacked store, the donated store still aliases its output, and the
    temporaries stay under 2.5 K+V stores (the slice-and-restack scan kept
    about 6). The mixed program runs two steps: a one-step scan lets the
    compiler stage this small store in fast memory, which no real size
    allows."""
    cfg = get_config("granite_3_2b").replace(n_layers=N_LAYERS)
    params = jax.eval_shape(lambda key: MD.init_model(cfg, key),
                            jax.random.PRNGKey(0))
    # shapes only: nothing is allocated for a device that is not there
    init = MD.init_paged_cache
    monkeypatch.setattr(MD, "init_paged_cache",
                        lambda *a: jax.eval_shape(lambda: init(*a)))
    bs = 16
    eng = InferenceEngine(cfg, params, n_slots=bs, max_len=2048, paged=True,
                          page_size=PAGE, n_pages=N_PAGES,
                          paged_impl="pallas", prefill_chunk=PAGE)
    fn, _ = eng._fused_for(k, "greedy", bs, chunk)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ints = ("last", "pos", "gc", "max_new", "topk", "rows")
    state = {n: spec((bs,), jnp.int32) for n in ints}
    state.update(live=spec((bs,), bool), ok=spec((bs,), bool),
                 temp=spec((bs,), jnp.float32), topp=spec((bs,), jnp.float32),
                 key=spec((2,), jnp.uint32))
    chunk_xs = None
    if chunk:
        state["chunk_lane"] = spec((), jnp.int32)
        chunk_xs = (spec((k, chunk), jnp.int32), spec((k,), jnp.int32),
                    spec((k,), jnp.int32), spec((k,), bool))
    as_spec = lambda a: spec(a.shape, a.dtype)           # noqa: E731
    compiled = fn.lower(
        jax.tree.map(as_spec, params), jax.tree.map(as_spec, eng.cache),
        spec((bs, eng.pages.max_pages), jnp.int32), state,
        chunk_xs).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the paged kernel did not lower"
    store = eng.cache[0]["k"]
    assert store.shape == (N_LAYERS, N_PAGES, PAGE, KVH, D)
    shape = re.escape("bf16[" + ",".join(map(str, store.shape)) + "]")
    whole = re.findall(shape + r"\S*\s+(broadcast|dynamic-update-slice)\(",
                       text)
    assert not whole, f"store-shaped ops: {whole}"
    kv_bytes = 2 * store.size * store.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= kv_bytes, "the store is not donated"
    assert mem.temp_size_in_bytes <= 2.5 * kv_bytes, \
        f"temporaries {mem.temp_size_in_bytes / kv_bytes:.2f} K+V stores"
