"""Exchange and delivery (``exec/iteration.py:exchange_phase``): device
self milliseconds per job and chip of the ops in phase scope
``exchange``, the export table built and shared; on a mesh, the
all-gather of ``core/distributed.py:make_dist_hybrid_step``."""

from bench.layers import scope_ms


def read(run: dict):
    return scope_ms(run, "exchange")
