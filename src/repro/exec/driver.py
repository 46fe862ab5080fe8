"""The one superstep executor.

Every run path in the reproduction — ``run_bsp``, ``run_am``,
``run_hybrid``, the fault-tolerant driver, the serving layer and the
shard_map distributed step — is this loop with a different
:class:`~repro.exec.policy.EnginePolicy` and hook set:

    init -> [ while not quiescent and iteration < max_iters: step ] -> done

Two lowerings of the same loop:

* :func:`run_engine` — host-driven; checks ``quiescent`` once per step and
  calls :class:`ExecHook` methods between steps (checkpointing, failure
  detection, per-lane convergence tracking, ...).  ``device_loop=True``
  instead jits the whole loop, with ``policy.init`` unless the caller
  seeds the state, for runs whose hooks need nothing between steps; the
  host syncs once, at the end.  On a graph whose leaves lie on a mesh of
  more than one device the device loop is one shard_map over that mesh
  (:func:`repro.core.distributed.shard_loop`).  Either way the jit is
  built once per program object and policy (and ``max_iters`` and the
  mesh for the loop) and reused by every later run: a program object is
  treated as immutable once it has run.
* :func:`while_engine` — the bare ``lax.while_loop`` form, for embedding
  inside a larger jitted computation (the serving layer's full-run path).

The driver is the only place an outer iteration loop exists; the policy
modules contain step bodies, the engine modules contain configuration.

Every :func:`run_engine` call opens host spans (:func:`repro.obs.span`,
recorded only inside a ``jax.profiler`` session): ``engine.run`` around
the call, ``engine.init`` around an eager ``policy.init`` (the host loop's;
the device loop runs it inside its jit), and on the device-loop path
``engine.dispatch`` (the enqueue of the jitted loop, and on a miss of the
jit cache also its trace, lowering and compile or persistent-cache load;
stat ``cached``: whether the jitted loop came from the cache; stat
``chips``: the devices the loop runs on, the mesh's or 1),
``engine.loop_trace`` (inside the traced function: recorded once per
trace of the loop, so once per program, policy, ``max_iters``, mesh and
seeded or not, and again only if the argument shapes change) and
``engine.wait`` (the host blocked on the device).
"""

from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.runtime import EngineState, quiescent
from repro.exec.policy import EnginePolicy
from repro.obs import span

__all__ = ["run_engine", "while_engine", "ExecContext", "ExecHook"]


@dataclasses.dataclass
class ExecContext:
    """Mutable view of a run, handed to every hook.

    ``iteration`` mirrors ``int(es.counters.iterations)`` after every step
    and restore; ``tick`` counts host-loop trips (including trips a hook
    turned into a restore instead of a step), so failure-detection clocks
    can advance even when no progress is made.  On the device loop ``es``
    is None until the loop returns unless the caller seeded it: the jitted
    loop runs ``policy.init`` itself.
    """

    graph: Any
    prog: Any
    policy: EnginePolicy | None
    vdata: Any
    es: EngineState | None
    iteration: int = 0
    tick: int = 0


class ExecHook:
    """Executor hook protocol — subclass and override what you need.

    ``on_start`` runs once before the loop (a resume hook may replace
    ``ctx.es`` / ``ctx.iteration`` here); ``before_step`` runs every tick
    and may return ``False`` to skip this tick's step (e.g. a failure was
    detected and the state was rolled back instead); ``after_step`` runs
    after each completed step (checkpoint cadence lives here); ``on_exit``
    runs once after the loop (flush/close).
    """

    def on_start(self, ctx: ExecContext) -> None: ...

    def before_step(self, ctx: ExecContext) -> bool | None: ...

    def after_step(self, ctx: ExecContext) -> None: ...

    def on_exit(self, ctx: ExecContext) -> None: ...


def while_engine(prog, step: Callable, es: EngineState, max_iters: int,
                 vote: Callable | None = None):
    """The device-side loop body: iterate ``step`` (``es -> es``) until
    quiescence or ``max_iters``, as a ``lax.while_loop``.  Not jitted here
    — embed it in whatever jit owns the surrounding computation.  Inside
    a shard_map block ``vote`` turns the block's "not quiescent" flag into
    the mesh's (one reduction over its axes)."""
    def cond(e):
        busy = jnp.logical_not(quiescent(prog, e))
        if vote is not None:
            busy = vote(busy)
        return jnp.logical_and(busy, e.counters.iterations < max_iters)

    return jax.lax.while_loop(cond, step, es)


def run_engine(
    graph,
    prog,
    policy: EnginePolicy,
    vdata: Any = None,
    *,
    max_iters: int = 100_000,
    hooks: Sequence[ExecHook] = (),
    es: EngineState | None = None,
    jit_step: Callable | None = None,
    device_loop: bool = False,
) -> ExecContext:
    """Run ``policy`` to quiescence; returns the final :class:`ExecContext`
    (``ctx.es``, ``ctx.iteration``).

    ``es`` seeds the loop (default: ``policy.init``); ``jit_step``
    overrides the jitted step ``es -> es`` of the host loop (callers with a
    compile cache — the serving layer — or a shard_map step pass their
    own).  ``device_loop=True`` lowers the whole loop, and ``policy.init``
    when no ``es`` is given, into one jit of the policy; hooks then only
    see ``on_start`` / ``on_exit`` (there is no host boundary between
    steps, and ``ctx.es`` is None at ``on_start`` unless seeded), so it
    rejects hooks that override the per-step methods, and it takes no
    ``jit_step``.  Folding the init in keeps the device free of the eager
    init's temporaries and programs beside the loop's own program.

    On a graph placed over a mesh (its leaves' ``NamedSharding`` over
    more than one device, dim 0 partition-sharded as
    :func:`repro.core.distributed.shard0_specs` lays it) the device loop
    runs the init and every step as one shard_map over that mesh, with
    ``vdata`` replicated; results and counters equal the one-device
    run's.

    The jitted loop (or default host step) is cached per ``prog`` object,
    by identity and held weakly, per equal ``policy`` and ``max_iters``,
    per mesh, and per seeded or not: a later run with them traces
    nothing.  ``prog`` is
    therefore treated as immutable once it has run; build a new program
    object for other constants.  The graph, ``vdata`` and ``es`` are jit
    arguments, so a new root in ``vdata`` reuses the loop.
    """
    if device_loop:
        _check_device_loop(hooks, jit_step)
    with span("engine.run", engine=policy.name, device_loop=device_loop):
        if es is None and not device_loop:
            with span("engine.init"):
                es = policy.init(graph, prog, vdata)
        ctx = ExecContext(graph=graph, prog=prog, policy=policy, vdata=vdata,
                          es=es, iteration=0 if es is None
                          else int(es.counters.iterations))
        for h in hooks:
            h.on_start(ctx)
        if device_loop:
            _device_loop(ctx, max_iters)
        else:
            _host_loop(ctx, hooks, max_iters, jit_step)
        for h in hooks:
            h.on_exit(ctx)
    return ctx


# id(prog) -> {key: jitted function}; an entry is dropped when its
# program is collected.  The jitted functions reach the program through a
# weak reference only, so the cache never keeps a program alive.
_JITS: dict[int, dict] = {}


def _cached_jit(prog, key, build: Callable) -> tuple[Callable, bool]:
    """The jit of ``build(prog_ref)`` for this program object and ``key``
    -> (jitted function, whether it came from the cache)."""
    jits = _JITS.get(id(prog))
    if jits is None:
        jits = _JITS[id(prog)] = {}
        weakref.finalize(prog, _JITS.pop, id(prog), None)
    cached = key in jits
    if not cached:
        jits[key] = jax.jit(build(weakref.ref(prog)))
    return jits[key], cached


def _check_device_loop(hooks: Sequence[ExecHook],
                       jit_step: Callable | None) -> None:
    if jit_step is not None:
        raise ValueError("device_loop=True jits the policy's own step; "
                         "jit_step is for the host loop")
    stepwise = [h for h in hooks
                if type(h).before_step is not ExecHook.before_step
                or type(h).after_step is not ExecHook.after_step]
    if stepwise:
        raise ValueError(
            f"device_loop=True runs with no host boundary between "
            f"steps; hooks {[type(h).__name__ for h in stepwise]} "
            f"override before_step/after_step and need the host loop")


def graph_mesh(graph):
    """The mesh of more than one device that the graph's leaves lie on
    (their ``NamedSharding``), or None for a graph on one device."""
    for leaf in jax.tree.leaves(graph):
        sharding = getattr(leaf, "sharding", None)
        mesh = getattr(sharding, "mesh", None)
        if mesh is not None and mesh.size > 1:
            return mesh
    return None


def _loop(prog, max_iters: int, policy: EnginePolicy, g, v, e,
          vote: Callable | None = None) -> EngineState:
    """The device loop's body: ``policy.init`` where ``e`` is None, then
    :func:`while_engine` over ``policy.step``."""
    if e is None:
        e = policy.init(g, prog, v)
    return while_engine(prog, lambda e_: policy.step(g, prog, e_, v), e,
                        max_iters, vote=vote)


def device_loop_jit(prog, policy: EnginePolicy, max_iters: int, init: bool,
                    mesh=None) -> tuple[Callable, bool]:
    """The jitted device loop ``(graph, vdata, es) -> es`` for this
    program object, policy, ``max_iters``, whether it runs the init, and
    the mesh the graph lies on (None: one device) -> (jitted function,
    whether it came from the cache).  On a mesh the loop is one shard_map
    over it (:func:`repro.core.distributed.shard_loop`), so every Pallas
    call runs on one block of partitions."""
    # the graph and vdata are jit *arguments*: closed over, every graph
    # array would be baked into the program as a constant
    def build(prog_ref):
        def loop(g, v, e):
            prog = prog_ref()
            body = functools.partial(_loop, prog, max_iters)
            with span("engine.loop_trace"):
                if mesh is None:
                    return body(policy, g, v, e)
                from repro.core.distributed import shard_loop
                return shard_loop(body, policy, prog, mesh, g, v, e)
        return loop

    key = ("loop", policy, max_iters, init)
    return _cached_jit(prog, key if mesh is None else key + (mesh,), build)


def _device_loop(ctx: ExecContext, max_iters: int) -> None:
    """The whole loop, with ``policy.init`` where ``ctx.es`` is None, as
    one jit of the policy (over the graph's mesh where it lies on one);
    one host sync."""
    mesh = graph_mesh(ctx.graph)
    loop, cached = device_loop_jit(ctx.prog, ctx.policy, max_iters,
                                   ctx.es is None, mesh)
    with span("engine.dispatch", cached=cached,
              chips=1 if mesh is None else mesh.size):
        ctx.es = loop(ctx.graph, ctx.vdata, ctx.es)
    with span("engine.wait"):
        ctx.iteration = int(ctx.es.counters.iterations)


def _host_loop(ctx: ExecContext, hooks: Sequence[ExecHook], max_iters: int,
               jit_step: Callable | None) -> None:
    """One jitted step per host trip, with the hooks between steps."""
    if jit_step is None:
        policy, graph, vdata = ctx.policy, ctx.graph, ctx.vdata
        step_fn, _ = _cached_jit(
            ctx.prog, ("step", policy),
            lambda prog_ref: lambda g, v, e: policy.step(g, prog_ref(), e, v))
        jit_step = lambda e: step_fn(graph, vdata, e)   # noqa: E731
    while (ctx.iteration < max_iters
           and not bool(quiescent(ctx.prog, ctx.es))):
        ctx.tick += 1
        # evaluate every hook (clocks must advance even when another
        # hook consumes the tick), then skip the step if any said so
        if False in [h.before_step(ctx) for h in hooks]:
            continue            # a hook consumed this tick (restore)
        ctx.es = jit_step(ctx.es)
        ctx.iteration = int(ctx.es.counters.iterations)
        for h in hooks:
            h.after_step(ctx)
