"""Mean time from admission to first token of the requests that got their
first token in the traced window: the prompt's chunks streamed through
the fused blocks (or a whole-prompt prefill on an idle engine). Read from
the program's ``sprout.request.first_token`` marks (stat ``prefill_us``),
milliseconds."""
from bench import progtrace


def read(run):
    us = progtrace.mean_stat(run, "sprout.request.first_token", "prefill_us")
    return None if us is None else us / 1e3
