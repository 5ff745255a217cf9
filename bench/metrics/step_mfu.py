"""Model FLOPs of the decode tokens and prompt tokens the fused programs
processed in the traced window (the configuration's counts module), over
the chips' peak for those programs' device time, percent: FLOPs / (chips x
per-device time x per-chip bf16 peak)."""
import numpy as np

from bench import devtrace

MODULE = "jit_fused"


def read(run):
    if run.trace is None or not run.peaks:
        return None
    t = devtrace.module_s(run.trace, MODULE)
    keys = run.trace_counts.get("decode_keys") or []
    lens = run.trace_counts.get("prompt_lens") or []
    if t <= 0 or not (keys or lens):
        return None
    counts = run.counts
    flops = counts.decode_flops(run.model, np.concatenate(keys)
                                if keys else np.zeros(0))
    if lens:
        pos = np.concatenate([np.arange(n) for n in lens])
        flops += counts.prefill_flops(run.model, pos, len(lens))
    chips = len(devtrace.devices(run.trace))
    return 100.0 * flops / (chips * t * run.peaks["bf16_flops_per_s"])
