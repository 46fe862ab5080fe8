"""Message counters (the paper's I/M counters, ``collect_metrics=True``):
device self milliseconds per job and chip of the ops in phase scope
``message_accounting``, in whichever phase they run."""

from bench.layers import scope_ms


def read(run: dict):
    return scope_ms(run, "message_accounting")
