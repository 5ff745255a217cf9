"""Sharding rules (divisibility over all archs), HLO cost parser, roofline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import ASSIGNED, get_config
from repro.launch import hlo_cost as HC
from repro.launch import roofline as RL
from repro.launch import sharding as SH
from repro.launch.shapes import SHAPES, SHAPE_BY_NAME, input_specs, skip_reason
from repro.models import model as MD


def _mesh(multi=False):
    if multi:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def _axis_size(mesh, axis):
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("multi", [False, True])
def test_param_shardings_divide(arch, multi):
    mesh = _mesh(multi)
    cfg = get_config(arch).padded_for_tp(mesh.shape["model"])
    shapes = jax.eval_shape(lambda: MD.init_model(cfg, jax.random.PRNGKey(0)))
    shards = SH.param_shardings(cfg, mesh, shapes)
    n_sharded = 0
    for (path, leaf), sh in zip(jax.tree_util.tree_leaves_with_path(shapes),
                                jax.tree_util.tree_leaves(shards)):
        spec = sh.spec
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
            assert dim % _axis_size(mesh, ax) == 0, \
                f"{jax.tree_util.keystr(path)}: {leaf.shape} vs {spec}"
            if ax is not None:
                n_sharded += 1
    assert n_sharded > 0   # something actually sharded


@pytest.mark.parametrize("arch", ["granite_3_2b", "deepseek_v3_671b",
                                  "hymba_1_5b", "xlstm_1_3b", "whisper_base"])
def test_cache_shardings_divide(arch):
    mesh = _mesh()
    cfg = get_config(arch).padded_for_tp(16)
    cell = SHAPE_BY_NAME["decode_32k"]
    cache = jax.eval_shape(lambda: MD.init_cache(cfg, cell.global_batch, 1024))
    shards = SH.cache_shardings(cfg, mesh, cache)
    for leaf, sh in zip(jax.tree_util.tree_leaves(cache),
                        jax.tree_util.tree_leaves(shards)):
        for dim, ax in zip(leaf.shape, tuple(sh.spec) + (None,) * 8):
            assert dim % _axis_size(mesh, ax) == 0


def test_big_param_fraction_sharded():
    """>= 99% of parameter BYTES must be sharded across >= 16 ways."""
    mesh = _mesh()
    cfg = get_config("command_r_plus_104b").padded_for_tp(16)
    shapes = jax.eval_shape(lambda: MD.init_model(cfg, jax.random.PRNGKey(0)))
    shards = SH.param_shardings(cfg, mesh, shapes)
    tot = shard16 = 0
    for leaf, sh in zip(jax.tree_util.tree_leaves(shapes),
                        jax.tree_util.tree_leaves(shards)):
        b = np.prod(leaf.shape) * 2
        ways = 1
        for ax in sh.spec:
            ways *= _axis_size(mesh, ax)
        tot += b
        if ways >= 16:
            shard16 += b
    assert shard16 / tot > 0.99


def test_skip_matrix():
    assert skip_reason("granite_3_2b", SHAPE_BY_NAME["long_500k"])
    assert skip_reason("hymba_1_5b", SHAPE_BY_NAME["long_500k"]) is None
    assert skip_reason("xlstm_1_3b", SHAPE_BY_NAME["long_500k"]) is None
    assert skip_reason("whisper_base", SHAPE_BY_NAME["decode_32k"]) is None
    n_cells = len(ASSIGNED) * len(SHAPES)
    n_skipped = sum(1 for a in ASSIGNED for s in SHAPES if skip_reason(a, s))
    assert n_cells == 40 and n_skipped == 8


def test_input_specs_cover_all_runnable_cells():
    for arch in ASSIGNED:
        for cell in SHAPES:
            if skip_reason(arch, cell):
                continue
            cfg = get_config(arch).padded_for_tp(16)
            specs = input_specs(cfg, cell)
            assert specs, (arch, cell.name)


# ----------------------------------------------------------------------
# HLO cost parser
# ----------------------------------------------------------------------

def test_hlo_cost_counts_scan_trips():
    def body(x, w):
        return jnp.tanh(x @ w), None
    w = jnp.zeros((8, 128, 128), jnp.float32)
    x = jnp.ones((4, 128), jnp.float32)

    def f(x, w):
        y, _ = jax.lax.scan(body, x, w)
        return jnp.sum(y)

    text = jax.jit(f).lower(x, w).compile().as_text()
    r = HC.analyze(text)
    want = 8 * 2 * 4 * 128 * 128
    assert want * 0.95 <= r.flops <= want * 1.3
    assert any(m >= 8 for m in r.loop_info.values())


def test_hlo_cost_inplace_dus_not_inflated():
    def f(buf, xs):
        def body(b, i):
            return jax.lax.dynamic_update_slice_in_dim(
                b, xs[i][None], i * 4, 0), None
        out, _ = jax.lax.scan(body, buf, jnp.arange(8))
        return out

    buf = jnp.zeros((32, 1024), jnp.float32)
    xs = jnp.ones((8, 1024), jnp.float32)
    text = jax.jit(f).lower(buf, xs).compile().as_text()
    r = HC.analyze(text)
    # in-place updates: traffic ~ slices (8 x 4KB x few), NOT 8 x 128KB
    assert r.bytes < 8 * buf.nbytes * 0.5


def test_roofline_report_fields():
    rep = RL.RooflineReport(
        arch="a", shape="train_4k", mesh="single", chips=256,
        flops_per_dev=1e12, bytes_per_dev=1e11, wire_bytes_per_dev=1e10,
        compute_s=1e12 / RL.PEAK_FLOPS, memory_s=1e11 / RL.HBM_BW,
        collective_s=1e10 / RL.ICI_BW, model_flops_total=2e14,
        collectives={"all-reduce": 3})
    assert rep.dominant == "collective"
    assert 0 < rep.useful_ratio < 1
    assert 0 < rep.roofline_fraction <= 1


def test_model_flops_moe_uses_active_params():
    dense = RL.model_flops(get_config("granite_3_2b"),
                           SHAPE_BY_NAME["train_4k"])
    total, active = RL.model_param_counts(get_config("deepseek_v3_671b"))
    assert active < 0.15 * total      # 671B total, 37B-ish active
    moe = RL.model_flops(get_config("deepseek_v3_671b"),
                         SHAPE_BY_NAME["train_4k"])
    assert moe < 6 * total * 256 * 4096 * 0.2
    assert dense > 0


def test_collective_parse_ring_model():
    text = """
ENTRY %main (p: f32[16,1024]) -> f32[16,1024] {
  %p = f32[16,1024]{1,0} parameter(0)
  ROOT %all-reduce.1 = f32[16,1024]{1,0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    st = RL.parse_collectives(text)
    assert st.counts == {"all-reduce": 1}
    want = 2 * (3 / 4) * 16 * 1024 * 4
    assert st.wire_bytes == pytest.approx(want)
