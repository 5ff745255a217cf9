"""Mean host time of one ``SproutGateway.submit`` call in the window
(routing, tenant and deadline stamping, scheduler enqueue), microseconds."""


def read(run):
    if not run.submit_s:
        return None
    return 1e6 * sum(run.submit_s) / len(run.submit_s)
