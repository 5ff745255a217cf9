"""A tensor-parallel cell end to end on forced host devices: a tiny
tp_degree 2 cell on two of four CPU devices draws its weights straight
into the engine's placement (the same values as on one device), warms
every fused program as the engine's dispatch will call it, runs, and
checks correct; a cell whose chips differ from its tp_degree is refused."""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402

SEED = 3_000_000_019


def add_cell(root: pathlib.Path, name: str, chips: int, tp: int) -> None:
    """A copy of tiny.batch as ``name``, on ``chips`` chips at ``tp``."""
    config = json.loads((root / "bench/configs/tiny.json").read_text())
    config["engine"] = dict(config["engine"], tp_degree=tp)
    (root / f"bench/configs/{name}.json").write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name=name,
                                 file=f"bench/configs/{name}.json"))
    bench["workloads"].append({"name": f"{name}.batch", "config": name,
                               "traffic": "tiny_batch", "chips": chips,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.batch" in m.get("workloads", []):
            m["workloads"].append(f"{name}.batch")
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


SCRIPT = """
import json, pathlib, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
import numpy as np
from bench import harness, spec
cell = spec.load_cell(pathlib.Path({cell_root!r}), "tiny_tp2.batch")
cfg = harness.model_config(cell)
sharded = harness.make_weights(cfg, {seed}, cell.config["engine"])
whole = harness.make_weights(cfg, {seed}, dict(cell.config["engine"],
                                               tp_degree=1))
leaves = jax.tree_util.tree_leaves(sharded)
gw, eng = harness.make_gateway(cfg, sharded, cell, {seed})
print("weights:", json.dumps({{
    "engine_moved_none": all(a is b for a, b in
                             zip(leaves, jax.tree_util.tree_leaves(eng.params))),
    "equal": all(np.array_equal(a, b) for a, b in
                 zip(leaves, jax.tree_util.tree_leaves(whole))),
    "devices": max(len(a.sharding.device_set) for a in leaves),
    "largest_share": max(a.addressable_shards[0].data.size / a.size
                         for a in (sharded["embed"]["tok"],
                                   sharded["segs"][0]["mlp"]["w_up"]))}}),
      file=sys.stderr)
del sharded, whole, leaves, gw, eng
# a fused program compiled after set-up has warmed them all: the engine's
# own dispatch met an argument placed otherwise than in the warm-up
import logging
late = []
class Late(logging.Handler):
    def emit(self, record):
        if record.getMessage().startswith("Compiling jit(fused)"):
            late.append(1)
warm_fused = harness.warm_fused
def warm_then_count(*a):
    warm_fused(*a)
    jax.config.update("jax_log_compiles", True)
    logging.getLogger("jax").addHandler(Late())
harness.warm_fused = warm_then_count
rc = harness.main(["--workload", "tiny_tp2.batch", "--seed", "{seed}",
                   "--seconds", "2", "--trace", "0"],
                  root={cell_root!r}, allow_cpu=True)
print("late fused compiles:", len(late), file=sys.stderr)
sys.exit(rc)
"""


def test_tp2_cell_runs_and_is_correct(tmp_path):
    root = tiny.write(tmp_path)
    add_cell(root, "tiny_tp2", chips=2, tp=2)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                         cell_root=str(root), seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    (weights,) = [json.loads(line.split(":", 1)[1])
                  for line in p.stderr.splitlines()
                  if line.startswith("weights:")]
    # the embedding and the MLP held half on each device, as the engine
    # places them (its device_put moves nothing), with the values one
    # device draws
    assert weights == {"engine_moved_none": True, "equal": True,
                       "devices": 2, "largest_share": 0.5}
    # every fused program the engine dispatches was warmed in set-up
    assert "late fused compiles: 0" in p.stderr.splitlines()
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["compared"]
    assert res["device"]["count"] == 2
    assert len(res["device"]["memory_peak_bytes_each"]) == 2
    assert res["compared"]["window_compiles"]["value"] == 0
    assert res["metrics"]["output_tok_s"]["value"] > 0


def test_chips_other_than_tp_degree_refused(tmp_path, capsys):
    root = tiny.write(tmp_path)
    add_cell(root, "tiny_c4", chips=4, tp=2)
    rc = harness.main(["--workload", "tiny_c4.batch", "--seed", str(SEED),
                       "--seconds", "2", "--trace", "0"],
                      root=root, allow_cpu=True)
    out = capsys.readouterr()
    assert rc != 0
    assert not any(line.startswith("{") for line in out.out.splitlines())
    assert "chips must equal tp_degree" in out.err
