"""Serial host work per fused dispatch: over the program's
``sprout.gateway.step`` spans in the traced window that hold a
``sprout.engine.dispatch``, the mean of the step's duration less the time
in its ``sprout.engine.fetch`` spans (where the host waits on the
device), milliseconds."""
from bench import progtrace


def read(run):
    evs = progtrace.events(run)
    if not evs:
        return None
    starts = [s for _, s, _, _ in progtrace.named(evs, "sprout.engine.dispatch")]
    fetches = progtrace.named(evs, "sprout.engine.fetch")
    host = []
    for _, s, d, _ in progtrace.named(evs, "sprout.gateway.step"):
        e = s + d
        if any(s <= t <= e for t in starts):
            waited = sum(fd for _, fs, fd, _ in fetches
                         if fs >= s and fs + fd <= e)
            host.append(d - waited)
    return sum(host) / len(host) / 1e6 if host else None
