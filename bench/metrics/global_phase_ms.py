"""Global phase (``exec/iteration.py:hybrid_global_phase``): device self
milliseconds per job and chip of the ops in phase scope
``global_phase`` that no inner scope claims."""

from bench.layers import scope_ms


def read(run: dict):
    return scope_ms(run, "global_phase")
