"""Shared execution primitives for the three engines (Hama / AM-Hama / GraphHP).

The engines differ only in *when* they exchange across partitions and *which*
edges deliver in a step; the primitives here are common:

  ``exchange``         gather exported out-states across the partition cut
                       (the once-per-iteration distributed communication),
  ``deliver``          generate + combine messages along a selected edge set
                       into the per-vertex pending inboxes,
  ``apply_phase``      run the vertex program on a masked vertex set,
                       consuming pending inboxes (Pregel reactivation rules).

All primitives run on partition-major arrays ``(P, ...)`` and are pure, so the
same code serves the host (all partitions on one device; used by tests and the
paper-table benchmarks) and the distributed `shard_map` lowering (a block of
partitions per device; used by the multi-pod dry-run) — only the export-table
gather differs, which is injected as ``gather_table``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.graph import EllSlice, PartitionedGraph
from repro.core.vertex_program import (Channel, StepInfo, VertexProgram,
                                       combine_segments)

__all__ = ["Counters", "EngineState", "init_state", "exchange", "deliver",
           "apply_phase", "merge_inbox", "quiescent", "gather_per_partition",
           "ell_channels", "ell_f32_exact", "ell_slices", "slice_flat",
           "ell_send_accounting", "ell_group_accounting"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Counters:
    """The paper's metrics: I (global iterations), M (network messages), plus
    in-memory message and pseudo-superstep counts."""

    iterations: jax.Array          # () int32
    pseudo_supersteps: jax.Array   # (P,) int32
    net_messages: jax.Array        # () int32  — combined, crossing the cut
    net_local_messages: jax.Array  # () int32  — combined, same-partition RPC (Hama)
    mem_messages: jax.Array        # () int32  — raw in-memory deliveries

    @staticmethod
    def zeros(p: int) -> "Counters":
        z = jnp.zeros((), jnp.int32)
        return Counters(z, jnp.zeros((p,), jnp.int32), z, z, z)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EngineState:
    state: Any                 # app vertex state: dict of (P, Vp, ...)
    out: Any                   # current out-state: dict of (P, Vp, ...)
    send: jax.Array            # (P, Vp) bool — sent in the last apply
    active: jax.Array          # (P, Vp) bool
    export_out: Any            # accumulated out-state for the next exchange
    export_send: jax.Array     # (P, Vp) bool accumulated
    pending: Any               # {ch: (payload tuple (P,Vp,...), has (P,Vp))}
    halo_out: Any              # dict of (P, H, ...) — gathered remote out-states
    halo_send: jax.Array       # (P, H) bool
    counters: Counters


def gather_per_partition(leaf: jax.Array, idx: jax.Array) -> jax.Array:
    """leaf (P, N, ...) gathered with idx (P, K) -> (P, K, ...)."""
    return jax.vmap(lambda l, i: l[i])(leaf, idx)


def _empty_inbox(prog: VertexProgram, p: int, vp: int):
    return {
        ch.name: (ch.identity_like((p, vp)), jnp.zeros((p, vp), bool))
        for ch in prog.channels
    }


def init_state(graph: PartitionedGraph, prog: VertexProgram, vdata: Any) -> EngineState:
    """Run the paper's initialization iteration (superstep 0)."""
    state, out, send, active = prog.init(graph.vertex_gid, graph.vertex_mask, vdata)
    send = jnp.logical_and(send, graph.vertex_mask)
    active = jnp.logical_and(active, graph.vertex_mask)
    # the partitions this call holds: all of them, or one shard_map block's
    p, vp, h = graph.vertex_gid.shape[0], graph.vp, graph.hp
    halo_out = jax.tree.map(
        lambda l: jnp.zeros((p, h) + l.shape[2:], l.dtype), out)
    return EngineState(
        state=state, out=out, send=send, active=active,
        export_out=out, export_send=send,
        pending=_empty_inbox(prog, p, vp),
        halo_out=halo_out, halo_send=jnp.zeros((p, h), bool),
        counters=Counters.zeros(p),
    )


# ---------------------------------------------------------------------------
# exchange: the once-per-global-iteration distributed communication.
# ---------------------------------------------------------------------------

def exchange(
    graph: PartitionedGraph,
    es: EngineState,
    gather_table: Callable[[Any], Any] | None = None,
    wire_dtype=None,
) -> EngineState:
    """Gather exported out-states through the halo plan.

    ``gather_table`` maps per-partition export buffers (P_local, X, ...) to the
    globally-visible table (P, X, ...); identity on the host, an all-gather
    over the device axis inside shard_map.

    ``wire_dtype`` (e.g. bf16) quantizes float payloads *before* the wire —
    a GraphHP ``Combine()``-style bandwidth optimization: halves exchange
    bytes; safe for monotone/incremental programs (min/accumulate re-apply
    the combiner on the receiver) at ≤0.4% value quantization.  §Perf.
    """
    exports = jax.tree.map(
        lambda l: gather_per_partition(l, graph.export_slot), es.export_out)
    exp_send = jnp.logical_and(
        gather_per_partition(es.export_send, graph.export_slot),
        graph.export_mask)
    dtypes = jax.tree.map(lambda l: l.dtype, exports)
    if wire_dtype is not None:
        # quantize, then BITCAST to the integer carrier: a plain
        # convert->allgather->convert chain gets folded away by XLA's
        # simplifier (lossy-cast hoisting), erasing the wire savings
        carrier = jnp.uint16 if wire_dtype == jnp.bfloat16 else jnp.uint8
        exports = jax.tree.map(
            lambda l: jax.lax.bitcast_convert_type(
                l.astype(wire_dtype), carrier)
            if jnp.issubdtype(l.dtype, jnp.floating) else l, exports)
    if gather_table is not None:
        exports = gather_table(exports)
        exp_send = gather_table(exp_send)
    if wire_dtype is not None:
        # decode iff the ORIGINAL leaf was floating (the saved dtypes tree
        # drives the decision): keying on the carrier dtype would also
        # bitcast channels whose genuine payload dtype is uint16/uint8 and
        # corrupt them on the way back
        exports = jax.tree.map(
            lambda l, dt: jax.lax.bitcast_convert_type(l, wire_dtype)
            .astype(dt) if jnp.issubdtype(dt, jnp.floating) else l,
            exports, dtypes)

    def pull(leaf):
        flat = leaf.reshape((-1,) + leaf.shape[2:])
        return flat[graph.halo_ptr]

    halo_out = jax.tree.map(pull, exports)
    halo_send = jnp.logical_and(pull(exp_send), graph.halo_mask)
    return dataclasses.replace(es, halo_out=halo_out, halo_send=halo_send)


# ---------------------------------------------------------------------------
# deliver: emit + combine along a selected edge set into pending inboxes.
# ---------------------------------------------------------------------------

def merge_inbox(ch: Channel, a, b):
    """Pairwise monoid merge of two combined inboxes (payloads, has)."""
    (pa, ha), (pb, hb) = a, b
    has = jnp.logical_or(ha, hb)
    if ch.combiner == "sum":
        out = tuple(x + y for x, y in zip(pa, pb))
    elif ch.combiner == "min":
        out = tuple(jnp.minimum(x, y) for x, y in zip(pa, pb))
    elif ch.combiner == "max":
        out = tuple(jnp.maximum(x, y) for x, y in zip(pa, pb))
    elif ch.combiner == "lexmin":
        a_lt_b = _lex_lt(pa, pb)
        out = tuple(jnp.where(a_lt_b, x, y) for x, y in zip(pa, pb))
    else:  # pragma: no cover
        raise ValueError(ch.combiner)
    return out, has


def _lex_lt(pa, pb):
    lt = jnp.zeros(pa[0].shape, bool)
    eq = jnp.ones(pa[0].shape, bool)
    for x, y in zip(pa, pb):
        lt = jnp.logical_or(lt, jnp.logical_and(eq, x < y))
        eq = jnp.logical_and(eq, x == y)
    return jnp.logical_or(lt, eq)  # ties keep a


def ell_f32_exact(ch: Channel, payload_bound: int) -> bool:
    """Integer payloads ride the kernel as float32, which is only exact up
    to 2**24 — past that, vertex-id-valued payloads (WCC labels) would be
    silently rounded.  Judged per ELL degree bin: ``payload_bound`` is the
    largest source gid feeding the bin, which bounds every monotone
    min-label payload flowing through it (a HashMin label never exceeds its
    carrier's own gid)."""
    (dt, _), = ch.components
    if not jnp.issubdtype(jnp.dtype(dt), jnp.integer):
        return True
    return payload_bound <= (1 << 24)


def ell_slices(graph: PartitionedGraph, edges: str) -> tuple[EllSlice, ...]:
    return graph.local_ell if edges == "local" else graph.remote_ell


def ell_channels(graph: PartitionedGraph, prog: VertexProgram,
                 out, send, edges: str = "local") -> list[Channel]:
    """Channels eligible for kernel-backed delivery of ``edges``
    ('local' | 'remote'): the graph carries that side's sliced-ELL layout
    and the channel declares a matching single-component semiring whose
    ``ell_payload`` hook is implemented (and whose payloads survive every
    bin's float32 carriage exactly — see :func:`ell_f32_exact`).  The
    decision is static (per program/channel/bin, not data-dependent)."""
    slices = ell_slices(graph, edges)
    if not slices:
        return []
    return [ch for ch in prog.channels
            if ch.semiring is not None and len(ch.components) == 1
            and all(ell_f32_exact(ch, s.payload_bound) for s in slices)
            and prog.ell_payload(ch, out, send) is not None]


def slice_flat(s: EllSlice, graph: PartitionedGraph, p: int):
    """Flattened (rows, idx, msk) views of one ELL slice for a p-partition
    block.  The build-time cache serves the host path (the block covers the
    whole graph); inside a shard_map block the block-ragged tiles are
    re-offset with block-local strides instead: the owning partition of
    each tile row is recovered from its block-relative row id
    (``p_rel = row // Vp``; the sentinel clips to the last partition of
    the block, where the mask discards it)."""
    kb = s.kb
    if p == graph.n_partitions:
        return s.flat_rows, s.flat_idx, s.msk.reshape(-1, kb)
    b = s.rows.shape[0]                   # block rows in this shard
    ppb = p // b
    bvp = ppb * graph.vp
    prel = jnp.clip(s.rows // graph.vp, 0, ppb - 1)
    pabs = jnp.arange(b, dtype=jnp.int32)[:, None] * ppb + prel
    idx = (s.idx + (pabs * s.stride)[..., None]).reshape(-1, kb)
    rows = jnp.where(
        s.rows < bvp,
        s.rows + (jnp.arange(b, dtype=jnp.int32) * bvp)[:, None],
        p * graph.vp).reshape(-1)
    return rows, idx, s.msk.reshape(-1, kb)


# ⊕-combination of per-bin partials into the per-destination output; the
# scatter indices carry an out-of-range sentinel on padded rows, dropped.
_SCATTER = {
    "add_mul": lambda y, r, v: y.at[r].add(v, mode="drop"),
    "min_add": lambda y, r, v: y.at[r].min(v, mode="drop"),
    "min_mul": lambda y, r, v: y.at[r].min(v, mode="drop"),
    "max_add": lambda y, r, v: y.at[r].max(v, mode="drop"),
    "max_min": lambda y, r, v: y.at[r].max(v, mode="drop"),
}


def ell_combine_bins(prog, ch, slices, views, x, y, p: int):
    """⊕-combine each bin's ``ell_spmv`` partials onto the flat destination
    vector ``y`` — the dense base bin via the semiring combine, spill bins
    via semiring scatter over their row lists.  The single source of truth
    for `deliver`'s kernel path and the fused local phases' spill operand."""
    from repro.kernels.ell_spmv import ell_spmv
    from repro.kernels.common import SEMIRINGS

    combine, _, _ = SEMIRINGS[ch.semiring]
    for s, (rows, idx, msk) in zip(slices, views):
        v = prog.ell_edge_values(ch, s.val).reshape(-1, s.kb)
        yb = ell_spmv(idx, v, msk, x, semiring=ch.semiring)
        if s.dense:
            y = combine(y, yb)
        else:
            y = _SCATTER[ch.semiring](y, rows, yb)
    return y


def ell_send_accounting(graph: PartitionedGraph, slices, views, send_flat,
                        p: int):
    """Exact parity with the dense local accounting, from the ELL layout:
    per-destination has-flags (one combined local group per messaged dst)
    and the raw in-memory message count (every valid sender edge slot).
    The single source of truth for both `deliver`'s kernel path and the
    fused local phases."""
    has = jnp.zeros((p * graph.vp,), bool)
    mem = jnp.zeros((), jnp.int32)
    for s, (rows, idx, msk) in zip(slices, views):
        tile = jnp.logical_and(send_flat[idx], msk)
        row_has = jnp.any(tile, axis=-1)
        if s.dense:
            has = jnp.logical_or(has, row_has)
        else:
            has = has.at[rows].max(row_has, mode="drop")
        with jax.named_scope("message_accounting"):
            mem += jnp.sum(tile).astype(jnp.int32)
    return has.reshape(p, graph.vp), mem


@jax.named_scope("message_accounting")
def ell_group_accounting(graph: PartitionedGraph, slices, views, send_flat,
                         p: int) -> jax.Array:
    """Combined-message count at the paper's Combine() granularity — one per
    (destination vertex, source partition) group with a sending edge — read
    straight off the ELL tiles via the per-slot ``grp`` ids.  This is the
    tile-resident replacement for the dense per-group segment reduction:
    exact parity, because the tiles hold exactly the delivering edge set and
    ``grp`` carries the same ids as ``PartitionedGraph.edge_group`` —
    block-relative flat, so each block row offsets by its row index times
    the shared group width.  Padded slots contribute False updates (their
    grp id is an arbitrary in-range slot), which a boolean ``max`` scatter
    ignores."""
    if not slices:
        return jnp.zeros((), jnp.int32)
    b = slices[0].grp.shape[0]
    offs = (jnp.arange(b, dtype=jnp.int32) * graph.gp)[:, None, None]
    sent = jnp.zeros((b * graph.gp,), bool)
    for s, (_, idx, msk) in zip(slices, views):
        tile = jnp.logical_and(send_flat[idx], msk)
        grp = (s.grp + offs).reshape(tile.shape)
        sent = sent.at[grp].max(tile)
    return jnp.sum(sent).astype(jnp.int32)


def _ell_deliver(graph, prog, chs, es, pending, delivered, collect_metrics,
                 edges: str):
    """Kernel-backed delivery for semiring channels along ``edges``.

    Local deliveries read the (P*Vp,) out-state frontier; remote deliveries
    read the concat(out, halo_out) frontier of stride Vp + H, with sources
    halo-encoded as Vp + halo_slot.  Each sliced-ELL degree bin runs one
    `ell_spmv` Pallas call over its flattened tiles; spill-bin partials are
    ⊕-scattered onto the dense base bin's output.  The has-message flags
    (and, when ``collect_metrics``, the paper counters) come from a cheap
    masked gather of the send flags through the same layout.
    """
    from repro.kernels.common import SEMIRINGS

    p, vp = es.send.shape
    slices = ell_slices(graph, edges)
    if edges == "local":
        out_tab, send_tab = es.out, es.send
    else:
        cat = lambda a, b: jnp.concatenate([a, b], axis=1)
        out_tab = jax.tree.map(cat, es.out, es.halo_out)
        send_tab = cat(es.send, es.halo_send)
    send_flat = send_tab.reshape(-1)

    # has-message flags per destination, shared by every kernel channel
    views = [slice_flat(s, graph, p) for s in slices]
    has_fresh, mem_edges = ell_send_accounting(graph, slices, views,
                                               send_flat, p)
    delivered = jnp.logical_or(delivered, jnp.any(has_fresh, axis=1))

    net = jnp.zeros((), jnp.int32)
    net_local = jnp.zeros((), jnp.int32)
    mem = jnp.zeros((), jnp.int32)
    for ch in chs:
        _, _, ident = SEMIRINGS[ch.semiring]
        x = prog.ell_payload(ch, out_tab, send_tab)
        # lane channels carry a trailing (L,) axis through the same kernel
        # dispatch (semiring SpMM): flatten partitions only, keep lanes
        x = x.reshape((-1,) + x.shape[2:]).astype(jnp.float32)
        y = jnp.full((p * vp,) + x.shape[1:], ident, jnp.float32)
        y = ell_combine_bins(prog, ch, slices, views, x, y, p)
        y = y.reshape((p, vp) + y.shape[1:])
        dt, ident_ch = ch.components[0]
        has_b = has_fresh.reshape(
            has_fresh.shape + (1,) * (y.ndim - has_fresh.ndim))
        payload = jnp.where(has_b, y.astype(dt), jnp.asarray(ident_ch, dt))
        pending[ch.name] = merge_inbox(ch, pending[ch.name],
                                       ((payload,), has_fresh))
        if collect_metrics and edges == "local":
            # local deliveries: one combine group per messaged destination
            # (same-partition source), every valid edge an in-memory message
            with jax.named_scope("message_accounting"):
                net_local += jnp.sum(has_fresh).astype(jnp.int32)
                mem += mem_edges

    if collect_metrics and edges == "remote" and chs:
        # remote deliveries count per (source-partition, destination) combine
        # group, exactly like the dense path's accounting — but read off the
        # ELL tiles' per-slot group ids instead of re-reducing the dense edge
        # arrays; semiring channels declare an always-valid emit, so one
        # tile pass covers every kernel channel identically.
        net += len(chs) * ell_group_accounting(graph, slices, views,
                                               send_flat, p)

    return pending, delivered, net, net_local, mem


def deliver(
    graph: PartitionedGraph,
    prog: VertexProgram,
    es: EngineState,
    edges: str,                  # 'all' | 'local' | 'remote'
    use_halo: bool = True,
    use_ell: bool = False,
    collect_metrics: bool = True,
) -> tuple[EngineState, jax.Array]:
    """Messages from the last apply travel along ``edges`` into pending.

    Returns (state', delivered_any (P,) bool).  Updates the message counters:
    remote deliveries count as combined network messages (one per
    (source-partition, destination-vertex) group, i.e. post-``Combine()``),
    local deliveries as in-memory messages.

    ``use_ell`` dispatches semiring-declared channels of a 'local' or
    'remote' delivery to the Pallas ELL kernels (see :func:`ell_channels`);
    other channels — and every channel of 'all' deliveries — keep the dense
    gather/segment path.  ``collect_metrics=False`` skips the paper's
    message-accounting reductions entirely (the perf path pays nothing; the
    counters then stay at their previous values).
    """
    vp = graph.vp

    kernel_chs = ell_channels(graph, prog, es.out, es.send, edges) \
        if (use_ell and edges in ("local", "remote")
            and (use_halo or edges == "local")) else []
    dense_chs = [ch for ch in prog.channels if ch not in kernel_chs]

    pending = dict(es.pending)
    delivered = jnp.zeros((es.send.shape[0],), bool)
    net = jnp.zeros((), jnp.int32)
    net_local = jnp.zeros((), jnp.int32)
    mem = jnp.zeros((), jnp.int32)

    if kernel_chs:
        pending, delivered, nt, nl, mm = _ell_deliver(
            graph, prog, kernel_chs, es, pending, delivered, collect_metrics,
            edges)
        net += nt
        net_local += nl
        mem += mm

    if dense_chs:
        # per-edge source out-state and send flag (local then halo slots)
        def cat(local_leaf, halo_leaf):
            return jnp.concatenate([local_leaf, halo_leaf], axis=1)

        if use_halo:
            src_tab = jax.tree.map(cat, es.out, es.halo_out)
            send_tab = cat(es.send, es.halo_send)
        else:
            src_tab = jax.tree.map(
                lambda l: jnp.concatenate(
                    [l, jnp.zeros((l.shape[0], graph.hp) + l.shape[2:], l.dtype)],
                    axis=1),
                es.out)
            send_tab = cat(es.send, jnp.zeros((es.send.shape[0], graph.hp), bool))

        # the edge family is block-ragged (B block rows of p // B
        # consecutive partitions side by side), so gathers and segment
        # combines run flat: `edge_part` recovers each slot's absolute
        # partition, from which source-table and destination indices
        # follow
        p = es.send.shape[0]
        bsz = graph.edge_src.shape[0]
        ppb = p // bsz
        epart = (graph.edge_part
                 + (jnp.arange(bsz, dtype=jnp.int32) * ppb)[:, None])
        width = vp + graph.hp
        flat_src = (epart * width + graph.edge_src).reshape(-1)
        out_src = jax.tree.map(
            lambda l: l.reshape((p * width,) + l.shape[2:])[flat_src]
            .reshape(graph.edge_src.shape + l.shape[2:]), src_tab)
        send_e = send_tab.reshape(-1)[flat_src].reshape(graph.edge_src.shape)

        if edges == "all":
            sel = graph.edge_mask
        elif edges == "local":
            sel = jnp.logical_and(graph.edge_mask, graph.edge_local)
        elif edges == "remote":
            sel = jnp.logical_and(graph.edge_mask,
                                  jnp.logical_not(graph.edge_local))
        else:  # pragma: no cover
            raise ValueError(edges)
        base_valid = jnp.logical_and(sel, send_e)

        dst_flat = (epart * vp + graph.edge_dst).reshape(-1)
        gseg = (graph.edge_group
                + (jnp.arange(bsz, dtype=jnp.int32) * graph.gp)[:, None]
                ).reshape(-1)
        for ch in dense_chs:
            payloads, valid = prog.emit(
                ch, out_src, graph.edge_w, graph.edge_src_gid, graph.edge_dst_gid)
            valid = jnp.logical_and(valid, base_valid)
            valid_flat = valid.reshape(-1)
            comb_pl, comb_has = combine_segments(
                ch, tuple(x.reshape((-1,) + x.shape[2:]) for x in payloads),
                valid_flat, dst_flat, p * vp)
            fresh = (tuple(x.reshape((p, vp) + x.shape[1:]) for x in comb_pl),
                     comb_has.reshape(p, vp))
            pending[ch.name] = merge_inbox(ch, pending[ch.name], fresh)
            delivered = jnp.logical_or(
                delivered,
                jnp.zeros((p,), bool).at[epart.reshape(-1)].max(valid_flat))
            if not collect_metrics:
                continue
            # --- paper metrics ---------------------------------------------
            with jax.named_scope("message_accounting"):
                grp_sent = jax.ops.segment_max(
                    valid_flat.astype(jnp.int32), gseg,
                    num_segments=bsz * graph.gp).reshape(bsz, graph.gp) > 0
                grp_sent = jnp.logical_and(grp_sent, graph.group_mask)
                net += jnp.sum(jnp.logical_and(
                    grp_sent, graph.group_remote)).astype(jnp.int32)
                net_local += jnp.sum(jnp.logical_and(
                    grp_sent, jnp.logical_not(graph.group_remote))
                ).astype(jnp.int32)
                mem += jnp.sum(jnp.logical_and(
                    valid, graph.edge_local)).astype(jnp.int32)

    c = es.counters
    counters = dataclasses.replace(
        c, net_messages=c.net_messages + net,
        net_local_messages=c.net_local_messages + net_local,
        mem_messages=c.mem_messages + mem)
    return dataclasses.replace(es, pending=pending, counters=counters), delivered


# ---------------------------------------------------------------------------
# apply: run Compute() on a masked vertex set, consuming pending inboxes.
# ---------------------------------------------------------------------------

def _has_any_pending(prog: VertexProgram, pending) -> jax.Array:
    flags = [pending[ch.name][1] for ch in prog.channels]
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_or(out, f)
    return out


def apply_phase(
    graph: PartitionedGraph,
    prog: VertexProgram,
    es: EngineState,
    phase_mask: jax.Array,       # (P, Vp) bool — vertices allowed in this phase
    info: StepInfo,
    vdata: Any,
) -> EngineState:
    """Compute() on ``phase_mask ∧ (active ∨ has-message)`` vertices."""
    has_msg = _has_any_pending(prog, es.pending)
    compute = jnp.logical_and(graph.vertex_mask, phase_mask)
    compute = jnp.logical_and(compute, jnp.logical_or(es.active, has_msg))

    new_state, new_out, new_send, new_active = prog.apply(
        es.state, es.pending, graph.vertex_gid, graph.vertex_mask, vdata, info)

    def sel(new, old):
        m = compute.reshape(compute.shape + (1,) * (new.ndim - compute.ndim))
        return jnp.where(m, new, old)

    state = jax.tree.map(sel, new_state, es.state)
    out = jax.tree.map(sel, new_out, es.out)
    send = jnp.logical_and(jnp.logical_and(new_send, compute), graph.vertex_mask)
    active = jnp.where(compute, jnp.logical_and(new_active, graph.vertex_mask),
                       es.active)

    # consumed inboxes reset to the channel identity
    pending = {}
    for ch in prog.channels:
        payloads, has = es.pending[ch.name]
        keep = jnp.logical_not(compute)
        ident = ch.identity_like(has.shape)
        payloads = tuple(
            jnp.where(keep.reshape(keep.shape + (1,) * (p.ndim - keep.ndim)), p, i)
            for p, i in zip(payloads, ident))
        pending[ch.name] = (payloads, jnp.logical_and(has, keep))

    # export accumulation (SourceCombine) — only freshly computed sends count
    export_out, export_send = prog.accumulate_export(
        es.export_out, es.export_send, out, send)

    return dataclasses.replace(
        es, state=state, out=out, send=send, active=active, pending=pending,
        export_out=export_out, export_send=export_send)


def quiescent(prog: VertexProgram, es: EngineState) -> jax.Array:
    """Termination: no active vertex, nothing pending, nothing left to export."""
    return jnp.logical_not(
        jnp.any(es.active)
        | jnp.any(_has_any_pending(prog, es.pending))
        | jnp.any(es.export_send))
