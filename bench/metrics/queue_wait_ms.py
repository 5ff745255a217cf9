"""Mean time the requests that got their first token in the traced window
waited for a slot: from ``scheduler.submit`` through the scheduler's and
the engine's queues to admission. Read from the program's
``sprout.request.first_token`` marks (stat ``wait_us``), milliseconds."""
from bench import progtrace


def read(run):
    us = progtrace.mean_stat(run, "sprout.request.first_token", "wait_us")
    return None if us is None else us / 1e3
