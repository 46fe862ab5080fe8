"""Engines as policy objects.

GraphHP, Hama and AM-Hama share one execution skeleton — initialize, then
iterate a synchronization-delimited step until quiescence — and differ only
in what one step does.  An :class:`EnginePolicy` captures exactly that
difference: an ``init`` building the starting :class:`EngineState` and a
``step`` advancing it by one superstep / global iteration.  The driver
(:func:`repro.exec.driver.run_engine`) owns the loop, the halt rule, and
the hook points; every public runner (``run_bsp`` / ``run_am`` /
``run_hybrid`` / ``run_hybrid_ft`` / ``ServeEngine`` / the shard_map
distributed step) is a thin configuration built from one of the
constructors below.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

from repro.core.runtime import init_state
from repro.exec.iteration import (am_superstep, bsp_superstep,
                                  hybrid_iteration, init_hybrid)

__all__ = ["EnginePolicy", "bsp_policy", "am_policy", "hybrid_policy",
           "POLICIES", "make_policy"]


@dataclasses.dataclass(frozen=True)
class EnginePolicy:
    """One engine = two functions.

    ``init(graph, prog, vdata) -> EngineState`` builds iteration 0's state;
    ``step(graph, prog, es, vdata) -> EngineState`` advances one
    synchronization-delimited unit (a superstep, or a global iteration with
    its pseudo-superstep local phase) and must increment
    ``counters.iterations`` by exactly 1 — the driver's halt rule and
    checkpoint cadence count on it.  Both must be jittable.

    Policies built by the constructors below with equal knobs compare and
    hash equal (their ``init`` and ``step`` are :class:`Bound`), so the
    driver builds one jit per program and policy, not one per run.
    """

    name: str
    init: Callable
    step: Callable


@dataclasses.dataclass(frozen=True)
class Bound:
    """``fn`` with keyword ``knobs`` bound: a ``functools.partial`` that
    compares and hashes by its function and knobs (a partial does
    neither), so two policies built with the same knobs are equal."""

    fn: Callable
    knobs: tuple[tuple[str, Any], ...]

    def __call__(self, *args):
        return self.fn(*args, **dict(self.knobs))

    def with_knobs(self, **knobs: Any) -> "Bound":
        """The same function with ``knobs`` replacing its own; each must
        be one it already binds."""
        own = dict(self.knobs)
        unknown = sorted(set(knobs) - set(own))
        if unknown:
            raise TypeError(f"{getattr(self.fn, '__name__', self.fn)} "
                            f"binds no knob {unknown}")
        return _bind(self.fn, **{**own, **knobs})


def _bind(fn: Callable, **knobs: Any) -> Bound:
    return Bound(fn, tuple(sorted(knobs.items())))


def bsp_policy(use_ell: bool = True, collect_metrics: bool = True,
               gather_table: Callable | None = None) -> EnginePolicy:
    """Hama: one exchange + one bulk Compute() per superstep."""
    return EnginePolicy(
        name="bsp", init=init_state,
        step=_bind(bsp_superstep, gather_table=gather_table,
                   use_ell=use_ell, collect_metrics=collect_metrics))


def am_policy(use_ell: bool = True, collect_metrics: bool = True,
              gather_table: Callable | None = None) -> EnginePolicy:
    """AM-Hama: Hama's cadence + in-memory same-superstep local delivery."""
    return EnginePolicy(
        name="am", init=init_state,
        step=_bind(am_superstep, gather_table=gather_table,
                   use_ell=use_ell, collect_metrics=collect_metrics))


def hybrid_policy(use_ell: bool = True, collect_metrics: bool = True,
                  max_local_steps: int = 100_000,
                  gather_table: Callable | None = None,
                  wire_dtype=None) -> EnginePolicy:
    """GraphHP: one exchange per global iteration, then pseudo-supersteps
    to per-partition quiescence (fused Pallas local phase where eligible)."""
    return EnginePolicy(
        name="hybrid",
        init=_bind(init_hybrid, use_ell=use_ell,
                   collect_metrics=collect_metrics),
        step=_bind(hybrid_iteration, gather_table=gather_table,
                   max_local_steps=max_local_steps, wire_dtype=wire_dtype,
                   use_ell=use_ell, collect_metrics=collect_metrics))


POLICIES: dict[str, Callable[..., EnginePolicy]] = {
    "bsp": bsp_policy,
    "am": am_policy,
    "hybrid": hybrid_policy,
}


def make_policy(name: str, **knobs: Any) -> EnginePolicy:
    """Build a policy by engine name ('bsp' | 'am' | 'hybrid')."""
    if name not in POLICIES:
        raise KeyError(f"unknown engine {name!r}; have {sorted(POLICIES)}")
    return POLICIES[name](**knobs)
