"""Exchange and delivery (``exec/iteration.py:hybrid_remote_delivery``,
``core/runtime.py:deliver``): device self milliseconds per job and chip
of the ops in phase scope ``remote_delivery``, the exchanged values
folded into their targets."""

from bench.layers import scope_ms


def read(run: dict):
    return scope_ms(run, "remote_delivery")
