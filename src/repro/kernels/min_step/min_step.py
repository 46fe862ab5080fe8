"""Fused GraphHP pseudo-superstep for monotone-semiring programs (Pallas).

One local-phase pseudo-superstep of the monotone propagation family — SSSP's
relax loop (min,+), WCC's HashMin (min,+ over zeroed edges), bottleneck /
widest paths (max,min), odds or log-likelihood walks ((min,*) / (max,+)) —
is, per partition:

    d_in[r] = ⊕_k  send[s] ? x[s] ⊗ val[r,k] : identity(⊕),   s = idx[r,k]
    x'[r]   = x[r] ⊕ d_in[r]
    send'   = d_in improves x      (re-send only on strict improvement)

with (⊕, ⊗) any `kernels.common.MONOTONE_SEMIRINGS` entry — ⊕ ∈ {min, max}
is a selection, so the state update is a monotone adopt-if-better and the
whole family shares one kernel.  The unfused engine path runs gather →
segment-⊕ → ⊕ → compare as four HLO ops with HBM round-trips between them;
the local phase iterates this chain to per-partition convergence, so fusing
it into one VMEM-resident kernel removes three HBM round-trips per
pseudo-superstep — the monotone twin of `pr_step`.

``extra`` carries spill-bin contributions of the sliced-ELL layout (the
⊕-partials of the high-degree rows' overflow slots, pre-combined outside)
and is folded in during the epilogue, so degree-binned power-law graphs fuse
exactly like single-bin graphs.  Same blocking scheme as `ell_spmv`: the
senders' values are gathered in XLA into slot-major tiles (`kernels.common`)
— with a per-lane sender gate beside them where the ⊕-identity does not
absorb ⊗ — and the kernel folds the slots across the K grid axis and runs
the epilogue on the final K step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (MONOTONE_SEMIRINGS, SEMIRINGS, fold_slots,
                                  gather_lanes, map_lanes, rows_to_tiles,
                                  semiring_improves, slot_fold_call,
                                  slot_tiles, tiles_to_rows)


# Semirings whose ⊕-identity absorbs ⊗ with any finite edge value
# (inf + v = inf, -inf + v = -inf, min(-inf, v) = -inf): a non-sender can
# ride the gather as the identity and needs no per-lane sender gate.
# min_mul does not (inf * 0 is nan), so it keeps the gate.
_IDENT_ABSORBS = frozenset({"min_add", "max_add", "max_min"})


def _kernel(g_ref, m_ref, val_ref, xrow_ref, extra_ref, acc_ref, x_out_ref,
            send_out_ref, *, semiring: str):
    combine, times, ident = SEMIRINGS[semiring]
    improves = semiring_improves(semiring)
    ident = jnp.asarray(ident, acc_ref.dtype)

    def slot(j):
        cand = times(g_ref[j], val_ref[j])
        return jnp.where(m_ref[j] != 0, cand, ident)

    acc = fold_slots(acc_ref, g_ref.shape[0], slot, combine, ident)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _epilogue():
        d_in = combine(acc, extra_ref[...])
        acc_ref[...] = d_in
        xr = xrow_ref[...]
        x_out_ref[...] = combine(xr, d_in)
        send_out_ref[...] = improves(d_in, xr).astype(send_out_ref.dtype)


def fused_min_step_pallas(idx, val, msk, x, send, xrow, extra, *,
                          semiring: str = "min_add"):
    """-> (x', d_in, send').  ``x`` is the (N,) frontier — or (N, L) for L
    independent query lanes, in which case ``send``/``xrow``/``extra`` carry
    the same trailing lane axis and all three outputs are (R, L).  ``xrow``
    is the per-row state the epilogue compares against (the same array when
    rows and frontier share the vertex slot space), ``extra`` a pre-combined
    spill contribution (the ⊕-identity where none)."""
    if semiring not in MONOTONE_SEMIRINGS:
        raise ValueError(f"{semiring} is not a monotone semiring")
    tiles = slot_tiles(idx, val, msk)

    def call(x, send, xrow, extra):
        lane_shape = x.shape[1:]            # () SpMV or (L,) lane SpMM
        if semiring in _IDENT_ABSORBS:      # one gather: senders' values
            ident = jnp.asarray(SEMIRINGS[semiring][2], x.dtype)
            edge = dict(lane_edge=(gather_lanes(jnp.where(send, x, ident),
                                                tiles),),
                        shared_edge=(tiles.msk, tiles.val))
        else:                               # values and per-lane int32 gate
            gate = gather_lanes(send, tiles,
                                lambda s: tiles.msk * s.astype(jnp.int32))
            edge = dict(lane_edge=(gather_lanes(x, tiles), gate),
                        shared_edge=(tiles.val,))
        acc, x_out, send_out = slot_fold_call(
            functools.partial(_kernel, semiring=semiring), tiles,
            lane_shape[0] if lane_shape else 1,
            name="min_step", **edge,
            row_ops=(rows_to_tiles(xrow, tiles), rows_to_tiles(extra, tiles)),
            out_dtypes=(x.dtype, x.dtype, jnp.int32))
        back = lambda t: tiles_to_rows(t, tiles, lane_shape)
        return back(x_out), back(acc), back(send_out) != 0

    return map_lanes(call, (x, send, xrow.astype(x.dtype),
                            extra.astype(x.dtype)), tiles)
