"""Local phase (``exec/iteration.py:hybrid_local``, the fused
``min_step`` / ``pr_step`` kernels): device self milliseconds per job
and chip of the ops in phase scope ``local_phase`` that no inner scope
claims (its counters count under ``message_accounting``)."""

from bench.layers import scope_ms


def read(run: dict):
    return scope_ms(run, "local_phase")
