"""Fused GraphHP pseudo-superstep for incremental PageRank (Pallas).

One local-phase pseudo-superstep of Algorithm 5 is, per partition:

    delta_in[r] = Σ_k  0.85 · w[r,k] · (send[s] ? delta[s] : 0),  s = idx[r,k]
    rank'       = rank + delta_in
    send'       = delta_in > Δ

The unfused engine path runs gather → segment-sum → add → compare as four HLO
ops with HBM round-trips between them; since the local phase iterates this
to convergence (the paper's whole point is that it iterates *a lot*), fusing
the chain into one VMEM-resident kernel removes three HBM round-trips per
pseudo-superstep.  Same blocking scheme as ell_spmv: the senders' deltas
are gathered in XLA into slot-major tiles (`kernels.common`), the kernel
sums the slots in slot order across the K grid axis and runs the epilogue
on the final K step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (fold_slots, gather_lanes, map_lanes,
                                  rows_to_tiles, slot_fold_call, slot_tiles,
                                  tiles_to_rows)


def _kernel(g_ref, msk_ref, val_ref, rank_ref, extra_ref, acc_ref,
            rank_out_ref, send_out_ref, *, damping: float, tol: float):
    def slot(j):
        # a slot's sum term in slot order: the same association with or
        # without a lane axis, so a lane column is bit-identical to the
        # single-frontier dispatch of that lane
        return jnp.where(msk_ref[j] != 0, damping * val_ref[j] * g_ref[j],
                         0.0)

    acc = fold_slots(acc_ref, g_ref.shape[0], slot, jnp.add, 0.0)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _epilogue():
        # fold in the sliced-ELL spill bins' pre-combined contributions so
        # the returned delta_in covers every edge slot of the row
        d_in = acc + extra_ref[...]
        acc_ref[...] = d_in
        rank_out_ref[...] = rank_ref[...] + d_in
        send_out_ref[...] = (d_in > tol).astype(send_out_ref.dtype)


def fused_pr_step_pallas(idx, val, msk, delta, send, rank, extra, *,
                         damping: float = 0.85, tol: float = 1e-4):
    """-> (rank', delta_in, send').  With an (N, L) lane frontier ``delta``
    (per-seed personalized PageRank), ``send``/``rank``/``extra`` carry the
    same trailing L axis and all three outputs are (R, L)."""
    tiles = slot_tiles(idx, val, msk)

    def call(delta, send, rank, extra):
        lane_shape = delta.shape[1:]        # () SpMV or (L,) lane SpMM
        acc, rank_out, send_out = slot_fold_call(
            functools.partial(_kernel, damping=damping, tol=tol), tiles,
            lane_shape[0] if lane_shape else 1,
            name="pr_step",
            # non-senders ride the gather as 0: damping * val * 0, as in
            # the oracle, so no per-lane sender gate is needed
            lane_edge=(gather_lanes(jnp.where(send, delta, 0.0), tiles),),
            shared_edge=(tiles.msk, tiles.val),
            row_ops=(rows_to_tiles(rank, tiles), rows_to_tiles(extra, tiles)),
            out_dtypes=(rank.dtype, rank.dtype, jnp.int32))
        back = lambda t: tiles_to_rows(t, tiles, lane_shape)
        return back(rank_out), back(acc), back(send_out) != 0

    return map_lanes(call, (delta, send, rank, extra), tiles)
