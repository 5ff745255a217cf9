"""The harness end to end on the CPU: a cell defined only in another
directory runs and checks correct; without a TPU, or without the
repository's source, the command exits non-zero and prints no result."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402

SEED = 3_000_000_017


def run_cell(root, workload, capsys, trace=0):
    rc = harness.main(["--workload", workload, "--seed", str(SEED),
                       "--seconds", "2", "--trace", str(trace)],
                      root=root, allow_cpu=True)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_cell_defined_elsewhere_runs_and_is_correct(tmp_path, capsys):
    root = tiny.write(tmp_path)
    res = run_cell(root, "tiny.batch", capsys)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "compared"
    assert res["compared"]["window_compiles"]["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0


def _command(cwd, argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + argv, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGV = ["--workload", "granite_3_2b.chat", "--seed", str(SEED),
        "--seconds", "1", "--trace", "0"]


def test_no_tpu_exits_nonzero_without_result():
    p = _command(ROOT, ARGV)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "traces",
                                                  ".jax_cache"))
    p = _command(tmp_path, ARGV)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


@pytest.mark.parametrize("name", ["granite_3_2b.chat", "minicpm_2b.batch",
                                  "granite_3_2b.batch"])
def test_benchmark_cells_load(name):
    from bench import spec
    cell = spec.load_cell(ROOT, name)
    assert cell.chips == 1 and cell.per_layer
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    for m in cell.per_layer:
        assert m["moves"] in names
        assert callable(spec.reader(ROOT, m["name"]))
