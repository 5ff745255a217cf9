"""Share of the time inside ``SproutGateway.step`` calls that returned
tokens during which no operation ran on a device, percent, the mean over
the devices. The spans are the benchmark's ``bench.step`` annotations, on
the profiler's clock."""
from bench import devtrace


def read(run):
    if run.trace is None:
        return None
    steps = devtrace.spans(run.trace, "bench.step")
    shares = []
    for dev in devtrace.devices(run.trace):
        busy = devtrace.busy_intervals(run.trace, dev)
        span = inside = 0.0
        for (s, e), tokens in zip(steps, run.trace_steps):
            if tokens > 0:
                span += e - s
                inside += devtrace.overlap_ns(busy, s, e)
        if span <= 0:
            return None
        shares.append(100.0 * (1.0 - inside / span))
    return sum(shares) / len(shares) if shares else None
