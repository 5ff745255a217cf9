"""A configuration brings its own reference and counts: a tiny cell whose
configuration names a reference module and a counts module that exist
only as new files in its own directory runs through the harness, with no
edit to a harness file, and checks correct against that reference."""
import json
import pathlib
import sys
import textwrap

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import counts, harness, spec  # noqa: E402
from bench.tests import tiny  # noqa: E402

# the same dense decoder written another way: numpy in float64, the whole
# sequence at once, counting the instances the harness makes
REFERENCE = '''
"""Plain float64 numpy reference of a dense pre-norm decoder."""
import numpy as np
import ml_dtypes


def _f(a):
    return np.asarray(a).astype(np.float64)


def _fp8(x, axis):
    s = np.maximum(np.abs(x).max(axis=axis, keepdims=True), 1e-30) / 448.0
    return (x / s).astype(ml_dtypes.float8_e4m3fn).astype(np.float64) * s


class Reference:
    made = 0

    def __init__(self, m, params, quant=""):
        Reference.made += 1
        self.m, self.p, self.quant = m, params, quant

    def mm(self, a, w):
        if self.quant == "fp8":
            a, w = _fp8(a, -1), _fp8(w, 0)
        return a @ w

    def logits(self, tokens, rows):
        m, p = self.m, self.p
        H, KV = m["n_heads"], m["n_kv_heads"]
        D = m["d_model"] // H
        eps, theta = m["norm_eps"], m["rope_theta"]
        rms = lambda x, g: x / np.sqrt((x * x).mean(-1, keepdims=True)
                                       + eps) * g
        T = len(tokens)
        inv = theta ** -(np.arange(0, D, 2) / D)
        ang = np.arange(T)[:, None] * inv
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]

        def rope(x):
            a, b = x[..., : D // 2], x[..., D // 2:]
            return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

        emb = _f(p["embed"]["tok"])
        seg = p["segs"][0]
        x = emb[np.asarray(tokens)]
        causal = np.tril(np.ones((T, T), bool))
        for l in range(m["n_layers"]):
            w = lambda a: _f(a[l])
            h = rms(x, w(seg["ln1"]["scale"]))
            q = rope(self.mm(h, w(seg["attn"]["wq"])).reshape(T, H, D))
            k = rope(self.mm(h, w(seg["attn"]["wk"])).reshape(T, KV, D))
            v = self.mm(h, w(seg["attn"]["wv"])).reshape(T, KV, D)
            k, v = np.repeat(k, H // KV, 1), np.repeat(v, H // KV, 1)
            s = np.einsum("thd,shd->hts", q, k) / np.sqrt(D)
            s = np.where(causal, s, -np.inf)
            s = np.exp(s - s.max(-1, keepdims=True))
            o = np.einsum("hts,shd->thd", s / s.sum(-1, keepdims=True), v)
            x = x + self.mm(o.reshape(T, H * D), w(seg["attn"]["wo"]))
            h2 = rms(x, w(seg["ln2"]["scale"]))
            g = self.mm(h2, w(seg["mlp"]["w_gate"]))
            u = self.mm(h2, w(seg["mlp"]["w_up"]))
            x = x + self.mm(g / (1 + np.exp(-g)) * u,
                            w(seg["mlp"]["w_down"]))
        h = rms(x[np.asarray(rows)], _f(p["norm"]["scale"]))
        return self.mm(h, emb.T).astype(np.float32)
'''

COUNTS = '''
"""Counts of a dense decoder, as the harness's own."""
from bench.counts import *  # noqa: F401,F403
'''


def write(root: pathlib.Path) -> pathlib.Path:
    root = tiny.write(root)
    (root / "bench/reference").mkdir()
    (root / "bench/reference/plain_numpy.py").write_text(
        textwrap.dedent(REFERENCE))
    (root / "bench/counts_plain.py").write_text(textwrap.dedent(COUNTS))
    path = root / "bench/configs/tiny.json"
    config = json.loads(path.read_text())
    config["reference"]["module"] = "plain_numpy"
    config["counts"] = "counts_plain"
    path.write_text(json.dumps(config))
    return root


def test_modules_are_found_by_the_names_in_the_configuration(tmp_path):
    root = write(tmp_path)
    cell = spec.load_cell(root, "tiny.batch")
    assert pathlib.Path(spec.counts(cell).__file__) == \
        root / "bench/counts_plain.py"
    assert spec.counts(cell) is not counts
    ref = spec.reference(cell)
    assert ref.__name__ == "Reference" and ref.made == 0
    # the repository's cells name the repository's reference and counts
    real = spec.load_cell(ROOT, "granite_3_2b.chat")
    assert spec.reference(real).__name__ == "DenseDecoder"
    assert pathlib.Path(spec.counts(real).__file__) == \
        ROOT / "bench/counts.py"


def test_cell_with_its_own_reference_and_counts_is_correct(tmp_path, capsys,
                                                            monkeypatch):
    root = write(tmp_path)
    cell = spec.load_cell(root, "tiny.batch")
    seen = []
    end_to_end = harness.end_to_end
    monkeypatch.setattr(harness, "end_to_end", lambda c, s: seen.append(
        s["run"].counts) or end_to_end(c, s))
    rc = harness.main(["--workload", "tiny.batch", "--seed", "3000000023",
                       "--seconds", "2", "--trace", "0"],
                      root=root, allow_cpu=True)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert res["correct"] is True, res["compared"]
    assert res["compared"]["tokens_checked"]["value"] >= 20
    assert seen == [spec.counts(cell)]
    assert spec.reference(cell).made == 1
