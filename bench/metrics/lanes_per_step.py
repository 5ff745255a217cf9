"""Decode lanes per scan step over the window: tokens decoded over scan
steps run (engine counters ``decode_tokens`` / ``steps``)."""


def read(run):
    s = run.counters.get("steps", 0)
    return run.counters["decode_tokens"] / s if s else None
