"""The GraphHP local phase: pseudo-supersteps to per-partition quiescence.

This module owns everything that happens *inside* a partition between two
synchronization points — the participation/scheduling masks, the fully-fused
Pallas local phases (`pr_step` / `min_step`), and the generic
``lax.while_loop`` fallback — behind one entry point, :func:`local_phase`.
The executor's hybrid policy calls it once per global iteration; the A/B
benchmark calls :func:`fused_step_fn` directly so the kernels it times are
the exact ones the engine runs.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.graph import PartitionedGraph
from repro.core.runtime import (EngineState, _has_any_pending, apply_phase,
                                deliver, ell_send_accounting)
from repro.core.vertex_program import StepInfo, VertexProgram

__all__ = ["local_phase", "fused_step_fn", "participation_mask",
           "partition_running", "fused_local_kernel"]


def participation_mask(graph: PartitionedGraph, prog: VertexProgram) -> jax.Array:
    """Vertices eligible for local-phase computation (paper §4.2: boundary
    vertices join local phases for incremental algorithms)."""
    if prog.boundary_participates:
        return graph.vertex_mask
    return jnp.logical_and(graph.vertex_mask, jnp.logical_not(graph.is_boundary))


def partition_running(graph, prog, es, participate, vdata) -> jax.Array:
    """(P,) — does any participating vertex still need a pseudo-superstep?"""
    act = es.active
    gonly = prog.global_only_active(es.state, vdata)
    if gonly is not None:
        act = jnp.logical_and(act, jnp.logical_not(gonly))
    need = jnp.logical_or(act, _has_any_pending(prog, es.pending))
    return jnp.any(jnp.logical_and(need, participate), axis=1)


def fused_local_kernel(graph: PartitionedGraph, prog: VertexProgram,
                       use_ell: bool, max_local_steps: int) -> str | None:
    """Static gate for the fully-fused local phase: the kernel name
    ('pr_step' | 'min_step') when the program declares one and the graph
    carries a dense-base sliced-ELL layout, else None (generic loop)."""
    from repro.kernels.common import MONOTONE_SEMIRINGS

    if not (use_ell and graph.has_ell and max_local_steps > 0
            and len(prog.channels) == 1 and prog.boundary_participates
            and graph.local_ell[0].dense):
        return None
    kern = getattr(prog, "fused_kernel", None)
    if kern == "min_step":
        ch = prog.channels[0]
        # any monotone semiring fuses, provided the channel's combiner is
        # that semiring's ⊕ (the kernel's adopt-if-better state update)
        if (ch.semiring not in MONOTONE_SEMIRINGS
                or ch.combiner != ch.semiring.split("_")[0]):
            return None
        # unlike plain ELL delivery (only *messages* ride float32, judged
        # per bin), the fused loop keeps the whole vertex state in float32 —
        # integer states need every vertex id exactly representable
        (dt, _), = ch.components
        if (jnp.issubdtype(jnp.dtype(dt), jnp.integer)
                and graph.n_vertices - 1 > (1 << 24)):
            return None
    return kern if kern in ("pr_step", "min_step") else None


def _spill_extra(graph: PartitionedGraph, prog, ch, slices, views, out_d,
                 send, p):
    """⊕-combined spill-bin contributions (P*Vp, ...) for a fused kernel's
    ``extra`` operand — None when the layout is a single dense bin.  Lane
    channels keep their trailing (L,) axis through the spill SpMM."""
    if len(slices) == 1:
        return None
    from repro.core.runtime import ell_combine_bins
    from repro.kernels.common import SEMIRINGS

    _, _, ident = SEMIRINGS[ch.semiring]
    x = prog.ell_payload(ch, out_d, send)
    x = x.reshape((-1,) + x.shape[2:]).astype(jnp.float32)
    extra = jnp.full((p * graph.vp,) + x.shape[1:], ident, jnp.float32)
    return ell_combine_bins(prog, ch, slices[1:], views[1:], x, extra, p)


def fused_step_fn(graph: PartitionedGraph, prog: VertexProgram, kind: str,
                  p: int):
    """The single fused pseudo-superstep over the graph's sliced-ELL layout
    — the one implementation both the engine local phases and the A/B
    benchmark run, so they cannot drift apart.

    'pr_step': ``step(rank, delta, send) -> (rank', d_in, send')``;
    'min_step': ``step(x, send) -> (x', d_in, send')``.  All arrays are
    (p, Vp) — or (p, Vp, L) for a lane channel, with per-lane ``send``
    gating inside the kernel (the SpMM dispatch) — and spill bins beyond
    the dense base feed the kernel's ``extra`` operand through
    :func:`_spill_extra`.
    """
    from repro.core.runtime import slice_flat

    ch = prog.channels[0]
    vp = graph.vp
    slices = graph.local_ell
    views = [slice_flat(s, graph, p) for s in slices]
    _, idx, msk = views[0]
    flat = lambda a: a.reshape((-1,) + a.shape[2:])
    unflat = lambda a: a.reshape((p, vp) + a.shape[1:])

    if kind == "pr_step":
        from repro.kernels.pr_step import fused_pr_step

        val = slices[0].val.reshape(-1, slices[0].kb)

        def step(rank, delta, send):
            extra = _spill_extra(graph, prog, ch, slices, views,
                                 {ch.name: delta}, send, p)
            r, d, s = fused_pr_step(
                idx, val, msk, flat(delta), flat(send),
                flat(rank), extra, damping=prog.damping, tol=prog.tol)
            return unflat(r), unflat(d), unflat(s)
    elif kind == "min_step":
        from repro.kernels.min_step import fused_min_step

        val = prog.ell_edge_values(ch, slices[0].val).reshape(
            -1, slices[0].kb)

        def step(x, send):
            extra = _spill_extra(graph, prog, ch, slices, views,
                                 {ch.name: x}, send, p)
            xn, d, s = fused_min_step(
                idx, val, msk, flat(x), flat(send), extra=extra,
                semiring=ch.semiring)
            return unflat(xn), unflat(d), unflat(s)
    else:  # pragma: no cover
        raise ValueError(kind)
    return step, slices, views


def _fused_pr_local_phase(
    graph: PartitionedGraph,
    prog: VertexProgram,
    es: EngineState,
    running0: jax.Array,
    max_local_steps: int,
    collect_metrics: bool,
) -> EngineState:
    """Local phase fused through the `pr_step` Pallas kernel.

    One kernel call performs deliver(pseudo-superstep s) + apply(s+1): the
    incremental-PageRank pseudo-superstep chain gather -> segment-sum ->
    add -> compare collapses into a single VMEM-resident pass per step, so
    the iterated-a-lot inner loop pays one HBM round-trip instead of four
    and zero message-accounting reductions when ``collect_metrics=False``.

    Kernel contract (asserted by ``prog.fused_kernel == 'pr_step'``):
    single 'sum' channel, always-valid emit ``x[src] * w`` with w > 0 and
    sent deltas > tol > 0 (so delivered sums are strictly positive and
    d_in > 0 <=> has-message), apply is ``rank += delta; send = delta >
    tol``, never self-activating, additive SourceCombine, boundary
    vertices participating.  The bootstrap below runs the first apply
    (consuming the inbox filled by the global phase) in plain jnp, then the
    while-loop iterates the fused kernel; trip count, pseudo-superstep and
    message counters match the generic path exactly.
    """
    p = es.send.shape[0]
    ch = prog.channels[0]
    kstep, slices, views = fused_step_fn(graph, prog, "pr_step", p)
    tol = prog.tol
    name = ch.name
    # lane channels: send flags ride the loop per-lane (the kernel's SpMM
    # gating); vertex-level views (`vany`) feed scheduling and counters,
    # `ex` broadcasts vertex masks against lane arrays.  Scalar channels:
    # both are the identity and the loop below is the original computation.
    lanes = ch.lanes
    ex = (lambda a: a[..., None]) if lanes else (lambda a: a)
    vany = (lambda a: jnp.any(a, axis=-1)) if lanes else (lambda a: a)

    (p0,), has0 = es.pending[name]
    # bootstrap: apply_1 consumes the inbox (payload is 0 wherever ~has,
    # the sum identity, so the adds need no explicit compute mask)
    rank = es.state["rank"] + p0
    send = p0 > tol
    if lanes:
        # the lane program pre-neutralizes out per lane (sub-tol lanes
        # carry 0), mirroring PersonalizedPageRank.apply
        out_delta = jnp.where(ex(has0), jnp.where(send, p0, 0.0),
                              es.out["delta"])
    else:
        out_delta = jnp.where(has0, p0, es.out["delta"])
    exp_out = es.export_out["delta"] + jnp.where(send, p0, 0.0)
    exp_send = jnp.logical_or(es.export_send, vany(send))
    c0 = es.counters

    def cond(carry):
        _, _, _, _, _, _, _, running, _, _, k, _ = carry
        return jnp.logical_and(jnp.any(running), k < max_local_steps)

    def body(carry):
        (rank, delta, send, has, out_d, eo, esend, running, pseudo,
         metrics, k, _prev) = carry
        # pre-step apply state, so a max_local_steps cutoff can roll the
        # final fused apply back to generic-path semantics (see below)
        prev = (rank, out_d, eo, esend, send)
        rank_n, d_in, send_n = kstep(rank, delta, send)
        net_local, mem = metrics
        if collect_metrics:
            # exact parity with the dense accounting: has-flags from the
            # send gather, one combined local group per messaged dst (a
            # K-lane message counts once — vertex-level send)
            has_n, mem_inc = ell_send_accounting(graph, slices, views,
                                                 vany(send).reshape(-1), p)
            with jax.named_scope("message_accounting"):
                net_local = net_local + jnp.sum(has_n).astype(jnp.int32)
                mem = mem + mem_inc
        else:
            has_n = vany(d_in > 0)     # positive-contribution invariant
        if lanes:
            out_d = jnp.where(ex(has_n), jnp.where(send_n, d_in, 0.0), out_d)
        else:
            out_d = jnp.where(has_n, d_in, out_d)
        eo = eo + jnp.where(send_n, d_in, 0.0)
        esend = jnp.logical_or(esend, vany(send_n))
        running = jnp.any(has_n, axis=1)
        pseudo = pseudo + running.astype(jnp.int32)
        return (rank_n, d_in, send_n, has_n, out_d, eo, esend, running,
                pseudo, (net_local, mem), k + 1, prev)

    carry0 = (rank, p0, send, has0, out_delta, exp_out, exp_send, running0,
              c0.pseudo_supersteps,
              (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)),
              jnp.zeros((), jnp.int32),
              (rank, out_delta, exp_out, exp_send, send))
    (rank, delta, send, has, out_delta, exp_out, exp_send, _, pseudo,
     (net_local, mem), _,
     (rank_p, out_p, eo_p, esend_p, send_p)) = jax.lax.while_loop(
        cond, body, carry0)

    # max_local_steps cutoff: the kernel has already folded the final
    # delivery into rank/out/export, but the generic path leaves it
    # pending-only for the next iteration's apply — roll the non-pending
    # state back one step so the delivery is not applied twice.  At a
    # quiescent exit `has` is all-False and this is the identity.
    cut = jnp.any(has)
    rank = jnp.where(cut, rank_p, rank)
    out_delta = jnp.where(cut, out_p, out_delta)
    exp_out = jnp.where(cut, eo_p, exp_out)
    exp_send = jnp.where(cut, esend_p, exp_send)
    send = jnp.where(cut, send_p, send)

    counters = dataclasses.replace(
        c0, pseudo_supersteps=pseudo,
        net_local_messages=c0.net_local_messages + net_local,
        mem_messages=c0.mem_messages + mem)
    return dataclasses.replace(
        es, state={"rank": rank}, out={"delta": out_delta}, send=vany(send),
        pending={name: ((delta,), has)},
        export_out={"delta": exp_out}, export_send=exp_send,
        counters=counters)


def _fused_min_local_phase(
    graph: PartitionedGraph,
    prog: VertexProgram,
    es: EngineState,
    running0: jax.Array,
    max_local_steps: int,
    collect_metrics: bool,
) -> EngineState:
    """Local phase fused through the `min_step` Pallas kernel — the
    monotone-semiring twin of :func:`_fused_pr_local_phase` serving SSSP,
    WCC, widest-path and random-walk style adopt-if-better programs.

    One kernel call performs deliver(pseudo-superstep s) + apply(s+1): the
    relax chain gather -> segment-⊕ -> ⊕ -> compare collapses into a
    single VMEM-resident pass per step, with the same cutoff-rollback
    semantics as the PageRank fusion.

    Kernel contract (asserted by ``prog.fused_kernel == 'min_step'``):
    single single-component channel whose combiner is the ⊕ of its monotone
    semiring (min_add/min_mul/max_add/max_min) and whose state, out and
    channel share one name and one value (``out == state``), always-valid
    emit ``x[src] ⊗ edge_val`` (``ell_payload`` / ``ell_edge_values`` define
    the factorization), apply is ``new = state ⊕ msg; send = new improves
    state``, never self-activating, keep-latest SourceCombine (the default
    ``accumulate_export``), boundary vertices participating.  The whole
    state rides the loop as float32 and is cast back under the vertex mask
    on exit (the gate in ``fused_local_kernel`` guarantees integer states
    stay exact).
    """
    from repro.kernels.common import SEMIRINGS, semiring_improves

    ch = prog.channels[0]
    name = ch.name
    dt, ident = ch.components[0]
    combine, _, sr_ident = SEMIRINGS[ch.semiring]
    improves = semiring_improves(ch.semiring)
    p = es.send.shape[0]
    kstep, slices, views = fused_step_fn(graph, prog, "min_step", p)
    vmask = graph.vertex_mask
    # lane channels: per-lane send flags ride the loop (SpMM gating in the
    # kernel); `vany` collapses to the vertex level for scheduling/export
    # (the generic keep-latest SourceCombine gates on vertex send) and `ex`
    # broadcasts vertex masks against lane arrays.  Scalar channels: both
    # are the identity and the loop is the original computation.
    lanes = ch.lanes
    ex = (lambda a: a[..., None]) if lanes else (lambda a: a)
    vany = (lambda a: jnp.any(a, axis=-1)) if lanes else (lambda a: a)

    (m0,), has0 = es.pending[name]
    x0 = es.state[name].astype(jnp.float32)
    eo0 = es.export_out[name]
    # bootstrap: apply_1 consumes the inbox (payload is the ⊕-identity
    # wherever ~has, so the combines need no explicit compute mask)
    m0f = jnp.where(ex(has0), m0.astype(jnp.float32), sr_ident)
    x1 = combine(x0, m0f)
    send1 = improves(x1, x0)
    eo_f = jnp.where(ex(vany(send1)), x1, eo0.astype(jnp.float32))
    esend1 = jnp.logical_or(es.export_send, vany(send1))
    c0 = es.counters

    def cond(carry):
        _, _, _, _, _, _, running, _, _, k, _ = carry
        return jnp.logical_and(jnp.any(running), k < max_local_steps)

    def body(carry):
        (x, d_in, send, has, eo, esend, running, pseudo, metrics, k,
         _prev) = carry
        # pre-step apply state for the max_local_steps cutoff rollback
        prev = (x, eo, esend, send)
        x_n, d_n, send_n = kstep(x, send)
        net_local, mem = metrics
        if collect_metrics:
            has_n, mem_inc = ell_send_accounting(graph, slices, views,
                                                 vany(send).reshape(-1), p)
            with jax.named_scope("message_accounting"):
                net_local = net_local + jnp.sum(has_n).astype(jnp.int32)
                mem = mem + mem_inc
        else:
            # some sender beat the identity (any lane)
            has_n = vany(improves(d_n, sr_ident))
        eo = jnp.where(ex(vany(send_n)), x_n, eo)
        esend = jnp.logical_or(esend, vany(send_n))
        running = jnp.any(has_n, axis=1)
        pseudo = pseudo + running.astype(jnp.int32)
        return (x_n, d_n, send_n, has_n, eo, esend, running, pseudo,
                (net_local, mem), k + 1, prev)

    carry0 = (x1, m0f, send1, has0, eo_f, esend1, running0,
              c0.pseudo_supersteps,
              (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)),
              jnp.zeros((), jnp.int32),
              (x1, eo_f, esend1, send1))
    (x, d_in, send, has, eo, esend, _, pseudo, (net_local, mem), _,
     (x_p, eo_p, esend_p, send_p)) = jax.lax.while_loop(cond, body, carry0)

    # max_local_steps cutoff: roll the final fused apply back so the still-
    # pending delivery is not applied twice (identity at a quiescent exit)
    cut = jnp.any(has)
    x = jnp.where(cut, x_p, x)
    eo = jnp.where(cut, eo_p, eo)
    esend = jnp.where(cut, esend_p, esend)
    send = jnp.where(cut, send_p, send)

    # leave the float32 loop: integer states cast back exactly (gate) under
    # the vertex mask, so padded sentinel slots keep their original bits
    state = jnp.where(ex(vmask), x.astype(dt), es.state[name])
    exp_out = jnp.where(ex(vmask), eo.astype(dt), eo0)
    payload = jnp.where(ex(has), d_in.astype(dt), jnp.asarray(ident, dt))

    counters = dataclasses.replace(
        c0, pseudo_supersteps=pseudo,
        net_local_messages=c0.net_local_messages + net_local,
        mem_messages=c0.mem_messages + mem)
    return dataclasses.replace(
        es, state={name: state}, out={name: state}, send=vany(send),
        pending={name: ((payload,), has)},
        export_out={name: exp_out}, export_send=esend,
        counters=counters)


def local_phase(
    graph: PartitionedGraph,
    prog: VertexProgram,
    es: EngineState,
    vdata: Any,
    superstep,
    max_local_steps: int = 100_000,
    use_ell: bool = True,
    collect_metrics: bool = True,
) -> EngineState:
    """Pseudo-supersteps to per-partition quiescence (Algorithm 2's inner
    while loop) — the defining move of the hybrid policy.

    Dispatches to a fully-fused Pallas phase when the program/graph qualify
    (:func:`fused_local_kernel`), else iterates the generic
    apply -> local-deliver ``lax.while_loop`` with a per-partition
    ``running`` mask so pseudo-superstep counts stay faithful.
    """
    participate = participation_mask(graph, prog)
    running0 = partition_running(graph, prog, es, participate, vdata)
    c0 = es.counters
    es = dataclasses.replace(es, counters=dataclasses.replace(
        c0, pseudo_supersteps=c0.pseudo_supersteps + running0.astype(jnp.int32)))

    fused = fused_local_kernel(graph, prog, use_ell, max_local_steps)
    if fused == "pr_step":
        return _fused_pr_local_phase(graph, prog, es, running0,
                                     max_local_steps, collect_metrics)
    if fused == "min_step":
        return _fused_min_local_phase(graph, prog, es, running0,
                                      max_local_steps, collect_metrics)

    def cond(carry):
        es_, running, k = carry
        return jnp.logical_and(jnp.any(running), k < max_local_steps)

    def body(carry):
        es_, running, k = carry
        mask = jnp.logical_and(participate, running[:, None])
        info_l = StepInfo(superstep=superstep, pseudo_step=k + 1,
                          phase="local")
        es_ = apply_phase(graph, prog, es_, mask, info_l, vdata)
        es_, _ = deliver(graph, prog, es_, edges="local", use_ell=use_ell,
                         collect_metrics=collect_metrics)
        running = partition_running(graph, prog, es_, mask, vdata)
        c = es_.counters
        es_ = dataclasses.replace(es_, counters=dataclasses.replace(
            c, pseudo_supersteps=c.pseudo_supersteps + running.astype(jnp.int32)))
        return es_, running, k + 1

    es, _, _ = jax.lax.while_loop(
        cond, body, (es, running0, jnp.zeros((), jnp.int32)))
    return es
