"""The comparison that decides ``correct`` separates the program from its
lower-precision control: at the tiny size, over three seeds, the served
tokens stay inside the limit and the fp8 control's do not."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import calibrate, harness, spec  # noqa: E402
from bench.tests import tiny  # noqa: E402


def test_program_inside_limit_control_outside(tmp_path):
    root = tiny.write(tmp_path)
    cell = spec.load_cell(root, "tiny.batch")
    clock = harness.CompileClock()
    limit = cell.config["check"]["max_logit_gap"]
    eps = None
    for seed in (3_000_000_021, 22, 23):
        eps, row = calibrate.readings(cell, seed, 2.0, clock, eps)
        assert row["tokens_checked"] >= cell.config["check"][
            "min_tokens_checked"]
        assert row["program_max_gap"] <= limit, row
        assert row["control_max_gap"] > limit, row
        # routed through the run's own comparison, the control fails it
        assert not row["control_correct"], row
