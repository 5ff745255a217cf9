"""A run with its timed path broken underneath checks not correct: a token
altered where it is produced, a decode step that leaves the KV state
unchanged, and half of the batch left out (its rows given the other
half's tokens). The cell can have no exchange between chips: it runs on
one."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax.numpy as jnp  # noqa: E402

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402
from repro.models import attention as A  # noqa: E402
from repro.models import model as MD  # noqa: E402
from repro.serving.engine import InferenceEngine  # noqa: E402


def token_altered(monkeypatch):
    def altered(logits, key, *unused, fold_ids=None):
        return ((jnp.argmax(logits, -1) + 1) % logits.shape[-1]).astype(
            jnp.int32)
    monkeypatch.setitem(InferenceEngine._SAMPLE_FNS, "greedy", altered)


def state_unchanged(monkeypatch):
    monkeypatch.setattr(A, "_paged_cache_write",
                        lambda cache, *args, **kw: cache)


def half_batch(monkeypatch):
    orig = MD.decode_sample_step

    def half(*args, **kw):
        toks, *rest = orig(*args, **kw)
        h = toks.shape[0] // 2
        if h:
            toks = toks.at[h:].set(toks[: toks.shape[0] - h])
        return (toks, *rest)
    monkeypatch.setattr(MD, "decode_sample_step", half)


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   half_batch])
def test_broken_timed_path_is_not_correct(fault, tmp_path, capsys,
                                          monkeypatch):
    root = tiny.write(tmp_path)
    fault(monkeypatch)
    rc = harness.main(["--workload", "tiny.batch", "--seed", "3000000017",
                       "--seconds", "2", "--trace", "0"],
                      root=root, allow_cpu=True)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert res["correct"] is False, res["compared"]
