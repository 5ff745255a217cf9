"""The float32 reference against the serving model's own path at a
reduced size: chunked prefill into the paged store, then paged decode."""
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import weights  # noqa: E402
from bench.reference.dense_decoder import DenseDecoder  # noqa: E402
from repro.models import model as MD  # noqa: E402
from repro.models.common import ModelConfig  # noqa: E402

MODEL = {"name": "ref-test", "family": "dense", "n_layers": 2, "d_model": 64,
         "n_heads": 4, "n_kv_heads": 2, "d_ff": 128, "vocab_size": 300,
         "act": "silu", "norm": "rmsnorm", "pos": "rope",
         "rope_theta": 10000.0, "tie_embeddings": True, "dtype": "float32",
         "attn_impl": "xla"}
PAGE, CHUNK, PAGES = 16, 16, 8


def served_logits(cfg, params, prompt, extra):
    """Logits the engine's programs produce: the prompt streamed in 16-token
    chunks into a paged store, then one paged decode step per extra token."""
    cache = MD.init_paged_cache(cfg, PAGES, PAGE)
    bt = jnp.arange(PAGES, dtype=jnp.int32)[None]
    chunk = jax.jit(lambda p, t, p0, n, c: MD.prefill_chunk_step(
        cfg, p, t, p0, n, jnp.int32(0), c, block_table=bt))
    decode = jax.jit(lambda p, t, pos, c: MD.forward(
        cfg, p, t, mode="decode", positions=pos, cache=c, block_table=bt,
        live=jnp.asarray([True]), paged_impl="xla"))
    for p0 in range(0, len(prompt), CHUNK):
        piece = prompt[p0:p0 + CHUNK]
        toks = np.zeros(CHUNK, np.int32)
        toks[: len(piece)] = piece
        last, cache = chunk(params, jnp.asarray(toks), jnp.int32(p0),
                            jnp.int32(len(piece)), cache)
    out = [np.asarray(last)]
    for j, t in enumerate(extra[:-1]):
        lg, cache = decode(params, jnp.asarray([[t]], jnp.int32),
                           jnp.asarray([len(prompt) + j]), cache)
        out.append(np.asarray(lg[0]))
    return np.stack(out)


def test_reference_matches_chunked_prefill_and_paged_decode():
    cfg = ModelConfig(**MODEL)
    shapes = jax.eval_shape(lambda k: MD.init_model(cfg, k),
                            jax.random.PRNGKey(0))
    params = weights.make_params(shapes, 3_000_000_017)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 300, 40).tolist()      # three chunks, ragged
    extra = rng.integers(0, 300, 6).tolist()
    got = served_logits(cfg, params, prompt, extra)
    seq = prompt + extra[:-1]
    rows = np.arange(len(prompt) - 1, len(seq))
    ref_model = dict(MODEL, norm_eps=1e-6)
    want = DenseDecoder(ref_model, params).logits(seq, rows)
    assert got.shape == want.shape == (6, 300)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale
    # the fp8 control is measurably off the same reference
    ctl = DenseDecoder(ref_model, params, quant="fp8").logits(seq, rows)
    assert np.abs(ctl - want).max() > 1e-2 * scale


def test_weights_same_seed_same_values():
    cfg = ModelConfig(**MODEL)
    shapes = jax.eval_shape(lambda k: MD.init_model(cfg, k),
                            jax.random.PRNGKey(0))
    a = weights.make_params(shapes, 2**33 + 5)
    b = weights.make_params(shapes, 2**33 + 5)
    c = weights.make_params(shapes, 5)
    ta, tb, tc = (jax.tree_util.tree_leaves(x) for x in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(ta, tb))
    assert not np.array_equal(ta[0], tc[0])
    assert all(x.dtype == s.dtype and x.shape == s.shape
               for x, s in zip(ta, jax.tree_util.tree_leaves(shapes)))
