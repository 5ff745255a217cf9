"""GPipe pipeline over a stage axis: matches sequential execution
(subprocess: needs >1 device)."""
import os
import subprocess
import sys

from repro.training.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(16, 4) == 3 / 19
    assert bubble_fraction(1, 4) == 0.75
    assert bubble_fraction(32, 2) < 0.05


_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import AxisType, PartitionSpec as P
from repro.training.pipeline import pipeline_apply

S, M, B, D = 4, 8, 2, 16
mesh = jax.make_mesh((S,), ("stage",), axis_types=(AxisType.Auto,))
key = jax.random.PRNGKey(0)
W = jax.random.normal(key, (S, D, D)) * 0.3
b = jax.random.normal(jax.random.PRNGKey(1), (S, D)) * 0.1
mbs = jax.random.normal(jax.random.PRNGKey(2), (M, B, D))

def stage_fn(params, x):
    w, bias = params
    return jnp.tanh(x @ w + bias)

@partial(jax.shard_map, mesh=mesh, in_specs=(P("stage"), P(None)),
         out_specs=P(None), check_vma=False)
def piped(params, mbs):
    w, bias = params
    return pipeline_apply(stage_fn, (w[0], bias[0]), mbs, "stage")

got = piped((W, b), mbs)

# sequential reference
want = mbs
for s in range(S):
    want = jnp.tanh(want @ W[s] + b[s])
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
print("PIPELINE_OK")
"""


def test_pipeline_matches_sequential_subprocess():
    # JAX_PLATFORMS stays pinned to cpu: an unpinned jax probes for TPU
    # hardware and spends minutes in metadata-fetch retries on CPU-only
    # containers, while the forced host device count only applies to the
    # CPU platform anyway.
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _SNIPPET], env=env,
                       capture_output=True, text=True, timeout=420,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
