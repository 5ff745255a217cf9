"""Device time of the fused decode / mixed programs (module ``jit_fused``)
per scan step they ran, in the traced window, milliseconds; the time is
the mean over the devices."""
from bench import devtrace

MODULE = "jit_fused"


def read(run):
    if run.trace is None:
        return None
    c0, c1 = run.trace_counts["c0"], run.trace_counts["c1"]
    steps = c1["steps"] - c0["steps"]
    t = devtrace.module_s(run.trace, MODULE)
    if steps <= 0 or t <= 0:
        return None
    return 1e3 * t / steps
