"""Requests admitted into engine slots per fused dispatch over the window
(engine counters: new slot occupants over ``decode_syncs``)."""


def read(run):
    d = run.counters.get("decode_syncs", 0)
    return run.counters["admitted"] / d if d else None
