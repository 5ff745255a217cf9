"""A tiny benchmark written into a directory of its own: a configuration,
two traffic mixes and a BENCHMARK.json naming two cells, sized for the
CPU. The harness finds all of it by name; the metric readers are the ones
beside the harness."""
from __future__ import annotations

import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]

MODEL = {"name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64,
         "n_heads": 4, "n_kv_heads": 2, "d_ff": 128, "vocab_size": 512,
         "act": "silu", "norm": "rmsnorm", "pos": "rope",
         "rope_theta": 10000.0, "tie_embeddings": True,
         "dtype": "bfloat16", "attn_impl": "xla"}
ENGINE = {"n_slots": 2, "max_len": 128, "page_size": 16, "n_pages": 40,
          "decode_block": 2, "prefill_chunk": 16, "prefix_cache": True,
          "tp_degree": 1}
# readings of tiny.batch on the CPU (bench/calibrate.py, seeds 21 to 26):
# the program's widest gap 0 to 0.0086, the fp8 control's 0.115 to 0.337
CHECK = {"max_logit_gap": 0.04, "min_tokens_checked": 20}


def write(root: pathlib.Path, seconds: int = 2) -> pathlib.Path:
    root = pathlib.Path(root)
    (root / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    config = {"name": "tiny", "source": "a CPU-sized dense decoder",
              "model": MODEL, "engine": ENGINE,
              "reference": {"module": "dense_decoder", "norm_eps": 1e-6},
              "check": CHECK}
    (root / "bench/configs/tiny.json").write_text(json.dumps(config))
    base = json.loads((BENCH / "traffic" / "chat.json").read_text())
    base.update(prompt_bytes_max=40, max_new=24, token_scale=10.0,
                pool_size=256, warmup_s=1.0, drain_s=30.0, check_requests=8)
    chat = dict(base, arrival={"kind": "poisson", "rate_per_s": 4.0},
                greedy_every=2)
    batch = dict(base, arrival={"kind": "backlog", "depth": 4},
                 sampling={"temperature": 0.0, "top_k": 0, "top_p": 1.0},
                 greedy_every=0, drain_s=0.0)
    (root / "bench/traffic/tiny_chat.json").write_text(json.dumps(chat))
    (root / "bench/traffic/tiny_batch.json").write_text(json.dumps(batch))
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": seconds,
        "configs": [{"name": "tiny", "source": "none",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "CPU test"}],
        "workloads": [
            {"name": "tiny.chat", "config": "tiny", "traffic": "tiny_chat",
             "chips": 1, "why": "CPU test"},
            {"name": "tiny.batch", "config": "tiny", "traffic": "tiny_batch",
             "chips": 1, "why": "CPU test"}],
        "end_to_end": [
            {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock",
             "workloads": ["tiny.chat"]},
            {"name": "output_tok_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.25, "source": "host_clock",
             "workloads": ["tiny.batch"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "route_us.chat", "unit": "us", "better": "lower",
             "source": "host_clock", "layer": "gateway",
             "moves": "ttft_p95_ms", "workloads": ["tiny.chat"]},
            {"name": "lanes_per_step.batch", "unit": "lanes",
             "better": "higher", "source": "program_counter",
             "layer": "scheduler", "moves": "output_tok_s",
             "workloads": ["tiny.batch"]}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
