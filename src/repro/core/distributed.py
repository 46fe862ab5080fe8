"""Distributed GraphHP execution: one partition block per device via
shard_map over the production mesh.

This is the faithful lowering of the paper's architecture: the local phase's
``lax.while_loop`` runs *per device with no collectives in its body* — every
device truly iterates pseudo-supersteps to its own partition's convergence,
decoupled from the others — and the only cross-device communication is the
once-per-global-iteration export all-gather, the psum of the iteration's
message counters, and the quiescence vote the paper's master collects from
its workers.

:func:`block_policy` is the per-block body; the executor's device loop
(:func:`repro.exec.driver.run_engine`, the path ``run_hybrid`` takes) runs
it through :func:`shard_loop` when the graph lies on a mesh, and
:func:`make_dist_hybrid_step` wraps one step of it for callers that step
by hand.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.graph import PartitionedGraph
from repro.core.runtime import Counters, EngineState
from repro.core.vertex_program import VertexProgram
from repro.exec.policy import EnginePolicy, hybrid_policy

AXES = ("data", "model")
#: the counters each block counts for its own partitions, summed over the
#: blocks; ``iterations`` is the same on every block and
#: ``pseudo_supersteps`` is per partition, sharded like the partitions
SUMMED = ("net_messages", "net_local_messages", "mem_messages")


def shard0_specs(tree, axes) -> Any:
    """Every array leaf sharded on dim 0 over the flattened device axes."""
    return jax.tree.map(lambda l: P(axes), tree)


def check_blocks(graph: PartitionedGraph, devices: int) -> None:
    """Raise unless the graph splits into one equal block per device."""
    if graph.n_partitions % devices or graph.n_blocks % devices:
        raise ValueError(
            f"distributed step needs n_partitions ({graph.n_partitions})"
            f" and n_blocks ({graph.n_blocks}) divisible by the device "
            f"count ({devices}); build with edge_blocks={devices} (or a "
            f"multiple)")


def sum_counters(c: Counters, axes, since: Counters | None = None
                 ) -> Counters:
    """The master's aggregation of the paper's message counters: each
    block's counts since ``since`` (all of them where None) summed over
    ``axes`` in one ``psum``, added to the replicated totals of
    ``since``."""
    with jax.named_scope("message_accounting"):
        base = [0 if since is None else getattr(since, f) for f in SUMMED]
        total = jax.lax.psum(tuple(getattr(c, f) - b
                                   for f, b in zip(SUMMED, base)), axes)
        return dataclasses.replace(c, **{f: b + t for f, b, t
                                         in zip(SUMMED, base, total)})


def block_policy(policy: EnginePolicy, axes) -> EnginePolicy:
    """``policy`` as the body of one block of a shard_map over ``axes``:
    its init and step run on the block's partitions, the step's exchange
    shares the export table by ``all_gather`` (the policy's
    ``gather_table``), and the message counters are summed over the
    blocks after the init and after each step (:func:`sum_counters`)."""
    def gather_table(x):
        # local (Pb, X, ...) -> global (P, X, ...): the one exchange
        return jax.lax.all_gather(x, axes, axis=0, tiled=True)

    inner = policy.step.with_knobs(gather_table=gather_table)

    def init(graph, prog, vdata):
        es = policy.init(graph, prog, vdata)
        return dataclasses.replace(es, counters=sum_counters(es.counters,
                                                             axes))

    def step(graph, prog, es, vdata):
        c0 = es.counters            # replicated totals from last iteration
        es = inner(graph, prog, es, vdata)
        return dataclasses.replace(
            es, counters=sum_counters(es.counters, axes, since=c0))

    return EnginePolicy(policy.name, init, step)


def shard_loop(loop: Callable, policy: EnginePolicy, prog: VertexProgram,
               mesh: Mesh, graph: PartitionedGraph, vdata: Any,
               es: EngineState | None) -> EngineState:
    """``loop(policy, graph, vdata, es, vote)``, the executor's device
    loop, run as one shard_map over ``mesh``: the graph and the state
    partition-sharded on dim 0, ``vdata`` replicated, ``policy`` as
    :func:`block_policy` makes it, and ``vote`` reducing each block's "not
    quiescent" flag over the mesh, so every block halts at the same
    iteration.  ``es`` None runs the init inside the shard_map too."""
    axes = tuple(mesh.axis_names)
    check_blocks(graph, mesh.size)
    block = block_policy(policy, axes)
    specs = _es_specs(es if es is not None else jax.eval_shape(
        lambda g, v: policy.init(g, prog, v), graph, vdata), axes)

    def vote(busy):
        return jax.lax.psum(busy.astype(jnp.int32), axes) > 0

    return jax.shard_map(
        lambda g, v, e: loop(block, g, v, e, vote), mesh=mesh,
        in_specs=(shard0_specs(graph, axes), P(),
                  P() if es is None else specs),
        out_specs=specs, check_vma=False)(graph, vdata, es)


def make_dist_hybrid_step(prog: VertexProgram, mesh: Mesh,
                          axes: tuple = AXES, vdata: Any = None,
                          max_local_steps: int = 10_000,
                          wire_dtype=None, use_ell: bool = True,
                          collect_metrics: bool = True, tracer=None):
    """Returns a jittable step: (graph, es) -> es, running one global
    iteration on a mesh where dim 0 of every array is the partition axis:
    one step of :func:`block_policy`'s hybrid body, the body
    ``run_hybrid``'s device loop runs on a graph placed over a mesh.
    ``wire_dtype=jnp.bfloat16`` halves exchange bytes (§Perf);
    ``use_ell``/``collect_metrics`` select the kernel-backed local phase
    (the ELL tiles shard on dim 0 like every other partition-major array).

    ``use_ell=True`` is the default here exactly as on the single-host
    engines: the shard_map block path runs the same fused/ELL kernels on
    block-local partition slices (``runtime.slice_flat`` re-offsets), the
    multi-device CI matrix pins it bit-exact against the host dense run,
    and ``collect_metrics=True`` costs no dense fallback — remote group
    accounting rides the ELL tiles' per-slot group ids.

    ``tracer`` (a :class:`repro.obs.trace.Tracer`) wraps the returned step
    with host-side span recording — one ``dist_step`` span per global
    iteration carrying per-device-block exchange bytes, halo sizes, and
    pseudo-superstep counts.  The wrapped step blocks between iterations
    (honest timing) and is *not* meant to be re-jitted by the caller;
    ``tracer=None`` (the default) returns the bare jittable step with no
    observability import at all."""
    block = block_policy(hybrid_policy(
        use_ell=use_ell, collect_metrics=collect_metrics,
        max_local_steps=max_local_steps, wire_dtype=wire_dtype), axes)

    def step(graph, es):
        check_blocks(graph, mesh.size)
        specs = _es_specs(es, axes)
        return jax.shard_map(
            lambda g, e: block.step(g, prog, e, vdata), mesh=mesh,
            in_specs=(shard0_specs(graph, axes), specs), out_specs=specs,
            check_vma=False)(graph, es)

    if tracer is not None:
        from repro.obs.trace import traced_dist_step   # lazy: opt-in only
        return traced_dist_step(step, tracer, mesh.size,
                                wire_dtype=wire_dtype)
    return step


def _es_specs(es: EngineState, axes) -> Any:
    """EngineState specs: arrays partition-sharded on dim 0; the counters are
    scalars — replicated (they are psum'd/identical across devices)."""
    def spec(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
        if "counters" in " ".join(keys):
            return P(axes) if getattr(leaf, "ndim", 0) >= 1 else P()
        return P(axes)
    return jax.tree_util.tree_map_with_path(spec, es)


def block_graph_shapes(n_partitions: int, vp: int, ep: int, xp: int, hp: int,
                       gp: int | None = None, kl: int = 0,
                       n_blocks: int | None = None) -> PartitionedGraph:
    """ShapeDtypeStruct stand-in graph (dry-run; no allocation).  ``kl`` > 0
    adds a single dense-base ELL bin of that slice width per side.
    ``ep``/``gp`` are per-*block* widths of the block-ragged edge layout;
    ``n_blocks`` defaults to one block per partition (the legacy padded
    shape, one partition per device)."""
    from repro.core.graph import EllSlice

    gp = gp or vp
    nb = n_partitions if n_blocks is None else n_blocks
    if n_partitions % nb:
        raise ValueError(f"n_blocks={nb} must divide "
                         f"n_partitions={n_partitions}")
    ppb = n_partitions // nb
    f = jax.ShapeDtypeStruct
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_

    def ell(stride):
        if kl == 0:
            return ()
        return (EllSlice(
            rows=f((nb, ppb * vp), i32),
            idx=f((nb, ppb * vp, kl), i32),
            val=f((nb, ppb * vp, kl), f32),
            msk=f((nb, ppb * vp, kl), b),
            grp=f((nb, ppb * vp, kl), i32),
            flat_rows=f((n_partitions * vp,), i32),
            flat_idx=f((n_partitions * vp, kl), i32),
            nb=ppb * vp, kb=kl, lo=0, dense=True, stride=stride,
            payload_bound=n_partitions * vp - 1),)

    pg = PartitionedGraph(
        vertex_gid=f((n_partitions, vp), i32),
        vertex_mask=f((n_partitions, vp), b),
        is_boundary=f((n_partitions, vp), b),
        out_degree=f((n_partitions, vp), i32),
        edge_src=f((nb, ep), i32),
        edge_dst=f((nb, ep), i32),
        edge_w=f((nb, ep), f32),
        edge_mask=f((nb, ep), b),
        edge_local=f((nb, ep), b),
        edge_src_gid=f((nb, ep), i32),
        edge_dst_gid=f((nb, ep), i32),
        edge_part=f((nb, ep), i32),
        edge_group=f((nb, ep), i32),
        group_remote=f((nb, gp), b),
        group_mask=f((nb, gp), b),
        export_slot=f((n_partitions, xp), i32),
        export_mask=f((n_partitions, xp), b),
        export_fanout=f((n_partitions, xp), i32),
        halo_ptr=f((n_partitions, hp), i32),
        halo_mask=f((n_partitions, hp), b),
        local_ell=ell(vp), remote_ell=ell(vp + hp),
        n_partitions=n_partitions, n_vertices=n_partitions * vp,
        n_edges=n_partitions * ep, vp=vp, ep=ep, xp=xp, hp=hp, gp=gp,
        n_blocks=nb, ep_by_p=(ep // ppb,) * n_partitions,
        gp_by_p=(gp // ppb,) * n_partitions,
    )
    return pg


def engine_state_shapes(prog: VertexProgram, graph: PartitionedGraph,
                        value_dtype=jnp.float32) -> EngineState:
    """ShapeDtypeStruct EngineState matching SSSP-like single-value apps."""
    p, vp, hp = graph.n_partitions, graph.vp, graph.hp
    f = jax.ShapeDtypeStruct
    val = {"dist": f((p, vp), value_dtype)}
    halo = {"dist": f((p, hp), value_dtype)}
    pend = {ch.name: (tuple(f((p, vp), dt) for dt, _ in ch.components),
                      f((p, vp), jnp.bool_))
            for ch in prog.channels}
    return EngineState(
        state=val, out=dict(val), send=f((p, vp), jnp.bool_),
        active=f((p, vp), jnp.bool_),
        export_out=dict(val), export_send=f((p, vp), jnp.bool_),
        pending=pend, halo_out=halo, halo_send=f((p, hp), jnp.bool_),
        counters=Counters(
            iterations=f((), jnp.int32),
            pseudo_supersteps=f((p,), jnp.int32),
            net_messages=f((), jnp.int32),
            net_local_messages=f((), jnp.int32),
            mem_messages=f((), jnp.int32)),
    )
