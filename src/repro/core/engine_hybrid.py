"""GraphHP hybrid engine — the paper's contribution (§4.2, §5.2, Algorithm 2).

One *global iteration* =
  1. distributed exchange of the export buffers (the ONLY cross-partition
     communication + the only synchronization point),
  2. **global phase**: each active boundary vertex runs Compute() exactly
     once, consuming the messages buffered since the previous iteration,
  3. **local phase**: pseudo-supersteps iterated *per partition, in memory,
     with zero collectives* until every participating vertex is inactive and
     no local message is in transit (Algorithm 2's inner while loop).

Messages to remote vertices produced anywhere in the iteration accumulate in
the export buffer through ``SourceCombine()`` and ride the next exchange.

This module is configuration only: the iteration body lives in
:mod:`repro.exec.iteration` (re-exported here), the local phase and its
fused Pallas kernels in :mod:`repro.exec.local_phase`, and the loop in
:mod:`repro.exec.driver` — ``run_hybrid`` is the executor under
:func:`repro.exec.policy.hybrid_policy`, with ``device_loop=True`` lowering
the whole outer loop into one jitted ``lax.while_loop``.
"""

from __future__ import annotations

from typing import Any

from repro.core.runtime import EngineState
from repro.core.vertex_program import VertexProgram
from repro.exec.driver import run_engine
from repro.exec.iteration import hybrid_iteration, init_hybrid
from repro.exec.local_phase import fused_local_kernel, fused_step_fn

__all__ = ["hybrid_iteration", "run_hybrid", "init_hybrid", "fused_step_fn"]

# back-compat alias (kernel tests poke the fused-dispatch gate directly)
_fused_local_kernel = fused_local_kernel


def run_hybrid(
    graph,
    prog: VertexProgram,
    vdata: Any = None,
    max_iters: int = 100_000,
    max_local_steps: int = 100_000,
    use_ell: bool = True,
    collect_metrics: bool = True,
    device_loop: bool = True,
) -> tuple[EngineState, int]:
    """Run global iterations to quiescence.

    ``device_loop=True`` (default) runs the init and the whole outer loop
    as one jitted device-side ``lax.while_loop`` — the per-iteration
    ``bool(quiescent(...))`` host round-trip disappears and the host syncs
    exactly once at the end.
    ``device_loop=False`` keeps the host-driven loop (useful when
    stepping/debugging iteration by iteration).  Either loop is jitted once
    per ``prog`` object and set of knobs and reused by later calls, so a
    program object is treated as immutable once it has run: build a new
    one for other constants, and pass per-job inputs in ``vdata``.

    Args:
        graph: the ``PartitionedGraph`` to iterate over.  Placed over a
            mesh of several devices (each array leaf a ``NamedSharding``
            on dim 0, as ``repro.core.distributed.shard0_specs`` gives),
            the device loop runs as one shard_map over that mesh: one
            block of partitions a device, the export table shared by
            all-gather, the counters summed by psum; the results equal
            the one-device run's.
        prog: the ``VertexProgram``; its channels decide kernel dispatch
            (semiring / ``fused_kernel`` / lane width).
        vdata: optional per-run auxiliary arrays handed to the program's
            hooks (e.g. ``{"sources": (K,) int32}`` for the K-lane
            multi-query programs); traced, so varying it does not recompile.
        max_iters: upper bound on global iterations; the loop stops early
            at quiescence (no active vertices, no pending or in-flight
            messages).
        max_local_steps: per-iteration cap on local pseudo-supersteps
            before the local phase cuts off (with rollback semantics for
            monotone fused kernels).
        use_ell: dispatch delivery through the sliced-ELL Pallas kernels
            where the program qualifies; ``False`` forces the dense
            gather/segment path (identical results and counters).
        collect_metrics: maintain the paper's per-iteration I/M message
            counters; ``False`` drops the accounting work from the hot
            loop (only ``iterations`` / ``pseudo_supersteps`` count).
        device_loop: see above.

    Returns:
        ``(es, iterations)`` — the final ``EngineState`` (per-channel
        state stacked ``(P, Vp[, L])``; read it back in global vertex
        order via ``graph.unpack_vertex``) and the number of global
        iterations executed, ``int(es.counters.iterations)``.
    """
    from repro.exec.policy import hybrid_policy

    policy = hybrid_policy(use_ell=use_ell, collect_metrics=collect_metrics,
                           max_local_steps=max_local_steps)
    ctx = run_engine(graph, prog, policy, vdata, max_iters=max_iters,
                     device_loop=device_loop)
    return ctx.es, ctx.iteration
