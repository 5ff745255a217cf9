"""Random weights drawn from the run seed, on the device, in one program.

The benchmark makes the weights itself and hands them to the system under
test, so the reference reads the same numbers without taking anything the
program made. The layout (leaf names and shapes) is the program's
parameter tree, read from ``jax.eval_shape`` of its initializer: that is
the checkpoint format the engine loads. Values: norm scales 1, the
embedding N(0, 1/d_model), every other matrix N(0, 1/fan_in), all in the
dtype the program serves them in. With ``shardings`` (the engine's
tensor-parallel placement) the same program writes each leaf straight into
its shards; the values do not depend on the placement.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def make_params(shape_tree, seed: int, shardings=None):
    flat, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(flat):
            name = jax.tree_util.keystr(path)
            if name.endswith("['scale']"):
                out.append(jnp.ones(leaf.shape, leaf.dtype))
                continue
            fan_in = leaf.shape[-1] if name.endswith("['tok']") \
                else leaf.shape[-2]
            draw = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                     leaf.dtype)
            out.append((draw * (1.0 / math.sqrt(fan_in))).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    kw = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(build, **kw)(seed_key(seed))
