"""Span tracing for the superstep executor.

Three granularities, one :class:`Tracer`:

* **run-level** — :func:`repro.exec.driver.run_engine` opens its own
  ``engine.*`` spans (:func:`repro.obs.span`) on every call: the whole
  run, the init, the dispatch of the jitted loop, the loop's trace and
  the wait for the device.  They land in a ``jax.profiler`` trace beside
  the device ops, whose phases carry ``jax.named_scope`` names
  (:mod:`repro.exec.iteration`).  This is all a ``device_loop=True`` run
  records: there is no host boundary between its steps.
* **superstep-level** — :class:`TraceHook` records one span per executor
  step with the counter deltas and the exchange bytes the step is about
  to put on the wire.  Works on every host-driven run path (``run_bsp``
  / ``run_am`` / ``run_hybrid(device_loop=False)`` / ``run_hybrid_ft`` /
  ``ServeEngine``); :func:`trace_hooks` picks it.
* **phase-level** — :func:`phased_run` executes an engine's superstep as
  its composable phase functions (:mod:`repro.exec.iteration`), jitting
  and timing each phase separately: exchange, delivery, global apply,
  local phase.  The composition is bit-identical to the fused step (the
  phase functions *are* the step body), so phase attribution costs only
  the extra dispatch boundaries.

A :class:`Tracer` keeps its spans in memory for the Chrome export;
:meth:`Tracer.span` also opens a :func:`repro.obs.span`, so its blocks
show in a profiler trace too.  A ``None``/disabled tracer contributes
zero hooks (:func:`trace_hooks` returns ``()``), and all accounting
(exchange bytes, counter deltas) runs only when a span is actually being
recorded.

:func:`wrap_hooks` decorates any other executor hook (checkpointing, the
FT fault hook) so its per-method work shows up as ``cat="hook"`` spans —
that is how checkpoint save time is separated from step time in a trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Sequence

import numpy as np

from repro.obs import clock, span as obs_span

__all__ = ["Span", "Tracer", "TraceHook", "trace_hooks",
           "wrap_hooks", "exchange_bytes", "exchange_bytes_per_partition",
           "halo_slots_per_partition", "phased_run", "SuperstepRecord",
           "PhasedRunResult", "COMM_PHASES"]


@dataclasses.dataclass
class Span:
    """One trace event.  ``ts``/``dur`` are seconds in the
    :func:`repro.obs.clock.perf_counter` domain; the Chrome exporter
    converts to microseconds.  ``ph`` follows the trace-event format:
    ``"X"`` complete spans, ``"i"`` instants."""

    name: str
    ts: float
    dur: float = 0.0
    cat: str = ""
    tid: int = 0
    ph: str = "X"
    args: dict = dataclasses.field(default_factory=dict)


class Tracer:
    """Append-only span sink.  ``enabled=False`` turns every recording
    method into a no-op so instrumentation can stay wired in production
    code paths."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.spans: list[Span] = []
        self.track_names: dict[int, str] = {}

    def name_track(self, tid: int, name: str) -> None:
        self.track_names[int(tid)] = name

    def add(self, name: str, ts: float, dur: float = 0.0, cat: str = "",
            tid: int = 0, ph: str = "X", **args) -> None:
        if self.enabled:
            self.spans.append(Span(name, ts, dur, cat, tid, ph, dict(args)))

    def instant(self, name: str, cat: str = "", tid: int = 0, **args) -> None:
        """A zero-duration annotation (e.g. a recovery event)."""
        self.add(name, clock.perf_counter(), 0.0, cat, tid, ph="i", **args)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "", tid: int = 0, **args):
        """Record the block as one complete span, in memory and in a
        profiler trace if one is open; the yielded dict can be mutated to
        attach args discovered inside the block (the profiler's copy
        keeps the args given here)."""
        mutable = dict(args)
        if not self.enabled:
            yield mutable
            return
        t0 = clock.perf_counter()
        try:
            with obs_span(name, **args):
                yield mutable
        finally:
            self.spans.append(Span(name, t0, clock.perf_counter() - t0,
                                   cat, tid, "X", mutable))


# ---------------------------------------------------------------------------
# exchange-bytes accounting (host-side, from the engine state the step is
# about to exchange — every engine's step body starts with the exchange, so
# the current export buffer is exactly what crosses the wire next).
# ---------------------------------------------------------------------------

def _wire_itemsize(dtype, wire_dtype) -> int:
    if wire_dtype is not None and np.issubdtype(dtype, np.floating):
        return np.dtype(wire_dtype).itemsize
    return np.dtype(dtype).itemsize


def exchange_bytes_per_partition(graph, es, wire_dtype=None) -> np.ndarray:
    """(P,) bytes each partition contributes to the next exchange: its
    valid *sending* export slots times the per-slot payload bytes of every
    exported leaf (after ``wire_dtype`` quantization, matching
    :func:`repro.core.runtime.exchange`'s wire encoding)."""
    import jax

    send = np.asarray(jax.device_get(es.export_send))          # (P, Vp)
    slot = np.asarray(graph.export_slot)                       # (P, X)
    mask = np.asarray(graph.export_mask)
    p = np.arange(send.shape[0])[:, None]
    sending = np.logical_and(send[p, slot], mask)              # (P, X)
    n_sending = sending.sum(axis=1)                            # (P,)
    per_slot = 0
    for leaf in jax.tree_util.tree_leaves(es.export_out):
        width = int(np.prod(leaf.shape[2:], dtype=np.int64)) if \
            leaf.ndim > 2 else 1
        per_slot += width * _wire_itemsize(leaf.dtype, wire_dtype)
    return n_sending.astype(np.int64) * per_slot


def exchange_bytes(graph, es, wire_dtype=None) -> int:
    """Total bytes the next exchange puts on the wire (see
    :func:`exchange_bytes_per_partition`)."""
    return int(exchange_bytes_per_partition(graph, es, wire_dtype).sum())


def halo_slots_per_partition(graph) -> np.ndarray:
    """(P,) valid halo slots per partition — each one is a remote
    out-state the partition consumes per exchange (static per graph)."""
    return np.asarray(graph.halo_mask).sum(axis=1).astype(np.int64)


def _counters_host(counters) -> dict:
    import jax
    c = jax.device_get(counters)
    return {
        "iterations": int(np.asarray(c.iterations)),
        "net_messages": int(np.asarray(c.net_messages)),
        "net_local_messages": int(np.asarray(c.net_local_messages)),
        "mem_messages": int(np.asarray(c.mem_messages)),
        "pseudo_supersteps": np.asarray(c.pseudo_supersteps).astype(np.int64),
    }


def _counter_deltas(before: dict, after: dict) -> dict:
    return {
        "net_messages": after["net_messages"] - before["net_messages"],
        "net_local_messages": (after["net_local_messages"]
                               - before["net_local_messages"]),
        "mem_messages": after["mem_messages"] - before["mem_messages"],
        "pseudo_supersteps": int((after["pseudo_supersteps"]
                                  - before["pseudo_supersteps"]).sum()),
    }


# ---------------------------------------------------------------------------
# executor hooks
# ---------------------------------------------------------------------------

# ExecHook lives in repro.exec.driver, which imports jax; import it here
# (obs -> exec), never the other way around — the executor must not pay a
# tracing import when no one traces.
from repro.exec.driver import ExecContext, ExecHook  # noqa: E402


class TraceHook(ExecHook):
    """One span per executor step, with the step's exchange bytes and
    counter deltas as args.

    Stepwise — rejected by ``device_loop=True`` runs (no host boundary
    between steps; :func:`trace_hooks` hands those none).  Put this hook
    *last* in the hook list: span order then brackets the step plus the
    preceding hooks' after-work (wrap those with :func:`wrap_hooks` to see
    their cost separately).
    """

    def __init__(self, tracer: Tracer, tid: int = 0, wire_dtype=None):
        self.tracer = tracer
        self.tid = tid
        self.wire_dtype = wire_dtype
        self._t0 = 0.0
        self._xb = 0
        self._before: dict | None = None

    def on_start(self, ctx: ExecContext) -> None:
        self.tracer.instant("run_start", cat="engine", tid=self.tid,
                            iteration=ctx.iteration)

    def before_step(self, ctx: ExecContext) -> None:
        if not self.tracer.enabled:
            return
        self._xb = exchange_bytes(ctx.graph, ctx.es, self.wire_dtype)
        self._before = _counters_host(ctx.es.counters)
        self._t0 = clock.perf_counter()

    def after_step(self, ctx: ExecContext) -> None:
        if not self.tracer.enabled or self._before is None:
            return
        import jax
        jax.block_until_ready(ctx.es)
        dur = clock.perf_counter() - self._t0
        after = _counters_host(ctx.es.counters)
        self.tracer.add(
            "superstep", self._t0, dur, cat="superstep", tid=self.tid,
            iteration=ctx.iteration, exchange_bytes=self._xb, barriers=1,
            **_counter_deltas(self._before, after))
        self._before = None


def trace_hooks(tracer: Tracer | None, device_loop: bool = False,
                tid: int = 0, wire_dtype=None) -> tuple[ExecHook, ...]:
    """The hooks a run should carry for ``tracer``: ``()`` when tracing is
    off (the disabled path adds zero hooks, zero work) or under
    ``device_loop=True`` (no host boundary between steps: the run's own
    ``engine.*`` spans are all it records), a stepwise :class:`TraceHook`
    on host-driven runs."""
    if tracer is None or not tracer.enabled or device_loop:
        return ()
    return (TraceHook(tracer, tid=tid, wire_dtype=wire_dtype),)


class _WrappedHook(ExecHook):
    """Delegates to ``inner``, timing each overridden method as a
    ``cat="hook"`` span.  Return values pass through untouched, so the
    driver's consumed-tick contract (``before_step`` returning ``False``)
    is preserved."""

    def __init__(self, inner: ExecHook, tracer: Tracer, tid: int = 0):
        self.inner = inner
        self.tracer = tracer
        self.tid = tid

    def _call(self, method: str, ctx: ExecContext):
        fn = getattr(self.inner, method)
        if not self.tracer.enabled:
            return fn(ctx)
        name = f"{type(self.inner).__name__}.{method}"
        t0 = clock.perf_counter()
        try:
            return fn(ctx)
        finally:
            self.tracer.add(name, t0, clock.perf_counter() - t0,
                            cat="hook", tid=self.tid,
                            iteration=ctx.iteration)

    def on_start(self, ctx): return self._call("on_start", ctx)

    def before_step(self, ctx): return self._call("before_step", ctx)

    def after_step(self, ctx): return self._call("after_step", ctx)

    def on_exit(self, ctx): return self._call("on_exit", ctx)


def wrap_hooks(tracer: Tracer | None, hooks: Sequence[ExecHook],
               tid: int = 0) -> tuple[ExecHook, ...]:
    """Wrap each hook so its method calls appear as spans; identity when
    tracing is off."""
    if tracer is None or not tracer.enabled:
        return tuple(hooks)
    return tuple(_WrappedHook(h, tracer, tid=tid) for h in hooks)


# ---------------------------------------------------------------------------
# phase-level profiling: run an engine as its composable phases.
# ---------------------------------------------------------------------------

#: phases counted as communication when computing the local-compute
#: fraction; everything else in a superstep is compute.
COMM_PHASES = ("exchange", "delivery")


@dataclasses.dataclass
class SuperstepRecord:
    """One profiled superstep / global iteration."""

    superstep: int
    barriers: int                     # global synchronizations (always 1)
    exchange_bytes: int               # bytes this superstep's exchange moved
    phase_seconds: dict[str, float]   # phase name -> wall seconds
    pseudo_supersteps: int            # summed over partitions, this step
    net_messages: int
    net_local_messages: int
    mem_messages: int

    @property
    def total_seconds(self) -> float:
        return sum(self.phase_seconds.values())

    @property
    def local_compute_fraction(self) -> float:
        """Fraction of this superstep's wall time spent computing (global
        apply + local phase) rather than exchanging/delivering."""
        total = self.total_seconds
        if total <= 0.0:
            return 0.0
        comm = sum(v for k, v in self.phase_seconds.items()
                   if k in COMM_PHASES)
        return (total - comm) / total


@dataclasses.dataclass
class PhasedRunResult:
    engine: str
    es: Any
    iterations: int
    records: list[SuperstepRecord]

    @property
    def total_barriers(self) -> int:
        return sum(r.barriers for r in self.records)

    @property
    def total_exchange_bytes(self) -> int:
        return sum(r.exchange_bytes for r in self.records)

    @property
    def mean_local_compute_fraction(self) -> float:
        if not self.records:
            return 0.0
        return sum(r.local_compute_fraction for r in self.records) \
            / len(self.records)


def _phase_fns(graph, prog, vdata, engine: str, use_ell: bool,
               collect_metrics: bool, max_local_steps: int,
               wire_dtype) -> list[tuple[str, Callable]]:
    from repro.exec import iteration as it

    if engine == "bsp":
        return [
            ("exchange", lambda es: it.exchange_phase(graph, prog, es)),
            ("delivery", lambda es: it.bsp_delivery(
                graph, prog, es, use_ell, collect_metrics)),
            ("compute", lambda es: it.bsp_compute(graph, prog, es, vdata)),
        ]
    if engine == "hybrid":
        return [
            ("exchange", lambda es: it.exchange_phase(
                graph, prog, es, wire_dtype=wire_dtype)),
            ("delivery", lambda es: it.hybrid_remote_delivery(
                graph, prog, es, use_ell, collect_metrics)),
            ("global", lambda es: it.hybrid_global_phase(
                graph, prog, es, vdata, use_ell, collect_metrics)),
            ("local", lambda es: it.hybrid_local(
                graph, prog, es, vdata, max_local_steps, use_ell,
                collect_metrics)),
        ]
    raise ValueError(f"phased profiling supports engines 'bsp' and "
                     f"'hybrid', not {engine!r}")


def phased_run(graph, prog, engine: str = "hybrid", vdata: Any = None, *,
               tracer: Tracer | None = None, tid: int = 0,
               use_ell: bool = True, collect_metrics: bool = True,
               max_iters: int = 100_000, max_local_steps: int = 100_000,
               wire_dtype=None) -> PhasedRunResult:
    """Run ``engine`` to quiescence with each superstep decomposed into
    its phase functions, jitted and timed one by one.

    The phases compose to exactly the engine's fused step body
    (:mod:`repro.exec.iteration` builds the step from the same functions),
    so the final state and every counter are bit-identical to
    ``run_bsp`` / ``run_hybrid`` — only the phase boundaries cost extra
    dispatches.  Returns a :class:`PhasedRunResult`; with ``tracer`` the
    same data lands as per-phase + per-superstep spans.
    """
    import jax

    from repro.core.runtime import quiescent
    from repro.exec.policy import make_policy

    knobs = dict(use_ell=use_ell, collect_metrics=collect_metrics)
    if engine == "hybrid":
        knobs["max_local_steps"] = max_local_steps
    policy = make_policy(engine, **knobs)
    phases = [(name, jax.jit(fn)) for name, fn in _phase_fns(
        graph, prog, vdata, engine, use_ell, collect_metrics,
        max_local_steps, wire_dtype)]

    es = policy.init(graph, prog, vdata)
    records: list[SuperstepRecord] = []
    step = 0
    while step < max_iters and not bool(quiescent(prog, es)):
        step += 1
        xb = exchange_bytes(graph, es, wire_dtype)
        before = _counters_host(es.counters)
        secs: dict[str, float] = {}
        t_start = clock.perf_counter()
        for name, fn in phases:
            t0 = clock.perf_counter()
            es = jax.block_until_ready(fn(es))
            secs[name] = clock.perf_counter() - t0
            if tracer is not None:
                tracer.add(f"{engine}.{name}", t0, secs[name], cat="phase",
                           tid=tid, superstep=step)
        deltas = _counter_deltas(before, _counters_host(es.counters))
        rec = SuperstepRecord(
            superstep=step, barriers=1, exchange_bytes=xb,
            phase_seconds=secs, pseudo_supersteps=deltas["pseudo_supersteps"],
            net_messages=deltas["net_messages"],
            net_local_messages=deltas["net_local_messages"],
            mem_messages=deltas["mem_messages"])
        records.append(rec)
        if tracer is not None:
            tracer.add(f"{engine}.superstep", t_start,
                       clock.perf_counter() - t_start, cat="superstep",
                       tid=tid, superstep=step, exchange_bytes=xb,
                       barriers=1,
                       local_compute_fraction=rec.local_compute_fraction,
                       **deltas)
    return PhasedRunResult(engine=engine, es=es, iterations=step,
                           records=records)


def traced_dist_step(step: Callable, tracer: Tracer, n_devices: int,
                     tid: int = 0, wire_dtype=None) -> Callable:
    """Wrap a distributed step ``(graph, es) -> es`` with host-side span
    recording: per-block (per-device) exchange bytes, halo sizes, and
    pseudo-superstep counts ride each span's args.  Used by
    :func:`repro.core.distributed.make_dist_hybrid_step` when a tracer is
    passed; the ``tracer=None`` path returns the step untouched."""
    import jax

    def blocked(vec: np.ndarray) -> list[int]:
        return [int(b.sum()) for b in np.array_split(vec, n_devices)]

    def wrapped(graph, es):
        if not tracer.enabled:
            return step(graph, es)
        xb = exchange_bytes_per_partition(graph, es, wire_dtype)
        halo = halo_slots_per_partition(graph)
        before = _counters_host(es.counters)
        t0 = clock.perf_counter()
        es = jax.block_until_ready(step(graph, es))
        dur = clock.perf_counter() - t0
        after = _counters_host(es.counters)
        pseudo = (after["pseudo_supersteps"]
                  - before["pseudo_supersteps"])
        tracer.add(
            "dist_step", t0, dur, cat="superstep", tid=tid,
            iteration=after["iterations"],
            exchange_bytes=int(xb.sum()),
            exchange_bytes_per_block=blocked(xb),
            halo_slots_per_block=blocked(halo),
            pseudo_supersteps_per_block=blocked(pseudo),
            net_messages=after["net_messages"] - before["net_messages"])
        return es

    return wrapped
