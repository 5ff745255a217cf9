"""The paged-attention kernel's share of its roofline, percent: the least
time the live keys and values allow (every key and value a live lane
attends to read once, from the lengths the engine held at each dispatch,
over the HBM bandwidth of the chips that share them; decode attention is
bound by bytes, at about 1 to 4 FLOPs per byte) over the kernel's device
time per device (trace events named ``paged_attention``). Bytes and FLOPs
come from the configuration's counts module."""
import numpy as np

from bench import devtrace

KERNEL = "paged_attention"


def read(run):
    if run.trace is None or not run.peaks:
        return None
    t = devtrace.op_s(run.trace, KERNEL)
    keys = run.trace_counts.get("decode_keys") or []
    if t <= 0 or not keys:
        return None
    n_keys = np.concatenate(keys)
    chips = len(devtrace.devices(run.trace))
    least = max(run.counts.paged_attn_bytes(run.model, n_keys)
                / (chips * run.peaks["hbm_bytes_per_s"]),
                run.counts.paged_attn_flops(run.model, n_keys)
                / (chips * run.peaks["bf16_flops_per_s"]))
    return 100.0 * least / t
