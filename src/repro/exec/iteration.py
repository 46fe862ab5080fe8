"""The superstep bodies behind every run path.

Each function here is one jittable unit of progress — a Hama superstep
(:func:`bsp_superstep`), an AM-Hama superstep (:func:`am_superstep`), or a
GraphHP global iteration (:func:`hybrid_iteration`) — expressed over the
same runtime primitives (``exchange`` / ``deliver`` / ``apply_phase``) and
differing only in *policy*: how often they synchronize and how far the
local phase runs between synchronizations.  The executor
(:mod:`repro.exec.driver`) iterates whichever body its
:class:`~repro.exec.policy.EnginePolicy` names; nothing here loops to
quiescence.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.graph import PartitionedGraph
from repro.core.runtime import (EngineState, apply_phase, deliver,
                                ell_channels, exchange, init_state)
from repro.core.vertex_program import StepInfo, VertexProgram
from repro.exec.local_phase import local_phase

__all__ = ["bsp_superstep", "am_superstep", "hybrid_iteration",
           "init_hybrid", "reset_export", "exchange_phase", "bsp_delivery",
           "bsp_compute", "hybrid_remote_delivery", "hybrid_global_phase",
           "hybrid_local"]


def reset_export(prog: VertexProgram, es: EngineState) -> EngineState:
    """Clear the export buffer after an exchange: values to the channel
    identity, send flags off.  Every superstep body starts with this."""
    return dataclasses.replace(
        es, export_out=prog.export_identity(es.export_out),
        export_send=jnp.zeros_like(es.export_send))


def _deliver_split(graph, prog, es, use_ell, collect_metrics):
    """Superstep delivery: remote + local halves when a channel can ride
    the Pallas ELL layouts (combine groups never mix local and remote
    edges, so counters are unchanged), else one dense 'all' pass."""
    if use_ell and ell_channels(graph, prog, es.out, es.send):
        es, _ = deliver(graph, prog, es, edges="remote", use_ell=True,
                        collect_metrics=collect_metrics)
        es, _ = deliver(graph, prog, es, edges="local", use_ell=True,
                        collect_metrics=collect_metrics)
    else:
        es, _ = deliver(graph, prog, es, edges="all",
                        collect_metrics=collect_metrics)
    return es


# ---------------------------------------------------------------------------
# phase functions: each superstep body below is a composition of these.
# The observability layer (:mod:`repro.obs`) jits and times them one by one
# to attribute wall time to exchange / delivery / compute / local phases —
# they must compose to *exactly* the unsplit bodies (the golden parity
# suite pins the composed results bit-identical).  Each runs under a
# ``jax.named_scope`` of its phase, so a device trace names the phase of
# every op; the scope changes HLO metadata only, never what is computed.
# ---------------------------------------------------------------------------

@jax.named_scope("exchange")
def exchange_phase(graph, prog, es, gather_table=None,
                   wire_dtype=None) -> EngineState:
    """The one distributed communication of a superstep / global iteration:
    gather export buffers through the halo plan, then clear them."""
    es = exchange(graph, es, gather_table, wire_dtype=wire_dtype)
    return reset_export(prog, es)


@jax.named_scope("bsp_delivery")
def bsp_delivery(graph, prog, es, use_ell: bool = True,
                 collect_metrics: bool = True) -> EngineState:
    """Hama's delivery: every edge (remote + local halves on the ELL path,
    one dense 'all' pass otherwise)."""
    return _deliver_split(graph, prog, es, use_ell, collect_metrics)


@jax.named_scope("bsp_compute")
def bsp_compute(graph, prog, es, vdata) -> EngineState:
    """Hama's bulk Compute() over all (active ∨ messaged) vertices, plus
    the superstep counter bump."""
    info = StepInfo(superstep=es.counters.iterations + 1, pseudo_step=0,
                    phase="superstep")
    es = apply_phase(graph, prog, es, graph.vertex_mask, info, vdata)
    c = es.counters
    return dataclasses.replace(
        es, counters=dataclasses.replace(
            c, iterations=c.iterations + 1,
            pseudo_supersteps=c.pseudo_supersteps + 1))


@jax.named_scope("remote_delivery")
def hybrid_remote_delivery(graph, prog, es, use_ell: bool = True,
                           collect_metrics: bool = True) -> EngineState:
    """GraphHP: deliver the just-exchanged remote messages into pending."""
    es, _ = deliver(graph, prog, es, edges="remote", use_ell=use_ell,
                    collect_metrics=collect_metrics)
    return es


@jax.named_scope("global_phase")
def hybrid_global_phase(graph, prog, es, vdata, use_ell: bool = True,
                        collect_metrics: bool = True) -> EngineState:
    """GraphHP's global phase: boundary vertices Compute() exactly once,
    then their same-partition messages are delivered for the immediate
    local phase (paper §4.2)."""
    it = es.counters.iterations + 1
    gmask = graph.is_boundary
    gonly = prog.global_only_active(es.state, vdata)
    if gonly is not None:
        gmask = jnp.logical_or(gmask, jnp.logical_and(es.active, gonly))
    info_g = StepInfo(superstep=it, pseudo_step=0, phase="global")
    es = apply_phase(graph, prog, es, gmask, info_g, vdata)
    es, _ = deliver(graph, prog, es, edges="local", use_ell=use_ell,
                    collect_metrics=collect_metrics)
    return es


@jax.named_scope("local_phase")
def hybrid_local(graph, prog, es, vdata, max_local_steps: int = 100_000,
                 use_ell: bool = True,
                 collect_metrics: bool = True) -> EngineState:
    """GraphHP's local phase — pseudo-supersteps to per-partition
    quiescence — plus the global-iteration counter bump."""
    it = es.counters.iterations + 1
    es = local_phase(graph, prog, es, vdata, it,
                     max_local_steps=max_local_steps, use_ell=use_ell,
                     collect_metrics=collect_metrics)
    c = es.counters
    return dataclasses.replace(
        es, counters=dataclasses.replace(c, iterations=c.iterations + 1))


def bsp_superstep(
    graph: PartitionedGraph,
    prog: VertexProgram,
    es: EngineState,
    vdata: Any,
    gather_table: Callable | None = None,
    use_ell: bool = True,
    collect_metrics: bool = True,
) -> EngineState:
    """One Hama superstep: exchange -> deliver(all) -> Compute(all).

    With ``use_ell`` (the default) the delivery splits into remote + local
    halves so each half can dispatch to its Pallas ELL layout.  Combine
    groups never mix local and remote edges, so counters are unchanged;
    float 'sum' inboxes may differ in the last bit (different reduction
    order).
    """
    es = exchange_phase(graph, prog, es, gather_table)
    es = bsp_delivery(graph, prog, es, use_ell, collect_metrics)
    return bsp_compute(graph, prog, es, vdata)


def am_superstep(
    graph: PartitionedGraph,
    prog: VertexProgram,
    es: EngineState,
    vdata: Any,
    gather_table: Callable | None = None,
    use_ell: bool = True,
    collect_metrics: bool = True,
) -> EngineState:
    """One AM-Hama superstep: Hama's cadence + asynchronous in-memory
    delivery between two ordered half-blocks A|B (the Grace mechanism,
    vectorized — see :mod:`repro.core.engine_am`)."""
    es = exchange_phase(graph, prog, es, gather_table)
    es = bsp_delivery(graph, prog, es, use_ell, collect_metrics)

    slot = jnp.arange(graph.vp)[None, :]
    half_a = jnp.logical_and(graph.vertex_mask, slot < graph.vp // 2)
    half_b = jnp.logical_and(graph.vertex_mask,
                             jnp.logical_not(slot < graph.vp // 2))

    info = StepInfo(superstep=es.counters.iterations + 1, pseudo_step=0,
                    phase="superstep")
    es = apply_phase(graph, prog, es, half_a, info, vdata)
    es, _ = deliver(graph, prog, es, edges="local", use_ell=use_ell,
                    collect_metrics=collect_metrics)   # A's messages, in memory
    es = apply_phase(graph, prog, es, half_b, info, vdata)
    # es.send is now B's senders only: A's in-partition messages were already
    # delivered above (delivering them again next superstep would double-count
    # for sum channels); A's cross-partition messages travel via the export
    # buffer, which accumulated A's sends in its apply_phase.

    c = es.counters
    return dataclasses.replace(
        es, counters=dataclasses.replace(
            c, iterations=c.iterations + 1,
            pseudo_supersteps=c.pseudo_supersteps + 1))


def hybrid_iteration(
    graph: PartitionedGraph,
    prog: VertexProgram,
    es: EngineState,
    vdata: Any,
    gather_table: Callable | None = None,
    max_local_steps: int = 100_000,
    wire_dtype=None,
    use_ell: bool = True,
    collect_metrics: bool = True,
) -> EngineState:
    """One global iteration: exchange -> global phase -> local phase.

    ``use_ell`` (the default) routes remote- and local-phase delivery
    through the Pallas ELL kernels for semiring-declared channels (and the
    entire local phase through the fused `pr_step` / `min_step` kernels for
    programs declaring ``fused_kernel``); ``collect_metrics=False`` drops
    the paper's message accounting from the hot loop (counters other than
    iterations/pseudo-supersteps stay put).
    """
    # -- 1. the one distributed exchange ---------------------------------
    es = exchange_phase(graph, prog, es, gather_table, wire_dtype=wire_dtype)
    es = hybrid_remote_delivery(graph, prog, es, use_ell=use_ell,
                                collect_metrics=collect_metrics)

    # -- 2. global phase: boundary vertices, exactly once -----------------
    # (plus any program-declared global-only-active vertices: interior
    #  vertices waiting on cross-partition round-trips tick here)
    es = hybrid_global_phase(graph, prog, es, vdata, use_ell=use_ell,
                             collect_metrics=collect_metrics)

    # -- 3. local phase: pseudo-supersteps until per-partition quiescence --
    return hybrid_local(graph, prog, es, vdata,
                        max_local_steps=max_local_steps, use_ell=use_ell,
                        collect_metrics=collect_metrics)


def init_hybrid(graph: PartitionedGraph, prog: VertexProgram, vdata: Any,
                use_ell: bool = True,
                collect_metrics: bool = True) -> EngineState:
    """Initialization iteration (iteration 0): same as Hama's first superstep;
    in-partition messages go to pending for iteration 1's phases, crossing
    messages ride the export buffer."""
    es = init_state(graph, prog, vdata)
    es, _ = deliver(graph, prog, es, edges="local", use_ell=use_ell,
                    collect_metrics=collect_metrics)
    return es
