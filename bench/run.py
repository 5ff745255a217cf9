"""Run one benchmark cell once on the chip this process finds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, metrics and bounds are in BENCHMARK.json; PERF.md explains
them. Exits non-zero, printing no result, without a TPU or without the
repository's ``src/``. The last line of stdout is the result as JSON.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# libtpu would otherwise log to a fixed /tmp path shared by every checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
