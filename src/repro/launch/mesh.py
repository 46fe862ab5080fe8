"""Production mesh definitions (TPU v5e).

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips — the ``pod`` axis is the
GraphHP partition axis for hybrid-sync training (DESIGN.md §6).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).  The
axes are ``AxisType.Auto``: under ``jax.set_mesh`` eager code keeps working
on arrays sharded over them, and the explicit shardings every call site
passes (``shard_map``, ``NamedSharding``) decide placement.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (CPU) devices exist — for tests."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return _auto_mesh((data, model), ("data", "model"))
