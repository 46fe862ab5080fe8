"""Semiring SpMV Pallas kernel in sliced-ELL layout — the GraphHP local-phase
hot loop, adapted for TPU.

The paper's pseudo-superstep iterates "gather messages along in-edges, combine
per destination" over a partition's adjacency lists.  A CPU worker chases
pointers; a TPU needs a dense, VMEM-tileable layout, so the in-edges of a
partition are packed as ELL slices:

    idx  (R, K) int32   source slot of the k-th in-edge of row r
    val  (R, K) f32     edge weight
    msk  (R, K) bool    slot occupancy

and one pseudo-superstep's combine is a blocked reduce

    y[r] = ⊕_k  msk[r,k] ? (val[r,k] ⊗ x[idx[r,k]]) : identity(⊕)

over semirings (⊕, ⊗) ∈ {(+,*) PageRank, (min,+) SSSP, (max,+) best-score
paths, (min,*) odds propagation, (max,min) bottleneck capacity} — the shared
table in `kernels.common.SEMIRINGS`.

The frontier ``x`` is either a vector (N,) — the classic SpMV — or a stacked
frontier *matrix* (N, L) of L independent query lanes (multi-source SSSP,
landmark tables, per-seed personalized PageRank), in which case the same
gather indices serve every lane and the product/reduce broadcast over the
trailing lane axis: one dispatch computes a semiring SpMM, y (R, L).  A
vector frontier is the one-lane case of the same kernel, so a lane column
is bit-identical to the single-frontier dispatch of that lane.

Blocking: the frontier gather runs in XLA (Mosaic cannot gather from a
frontier inside a kernel), producing the slot-major tile of
`kernels.common`; the kernel applies the masked ⊗ and folds the slots of
each (bk, 8, bc) block, in slot order, into an (8, bc)
output block revisited along the K grid axis.  Nothing frontier-sized
enters VMEM, so the frontier's size and lane count are not bounded by it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import (SEMIRINGS, fold_slots, gather_lanes,
                                  map_lanes, segment_count, slot_fold_call,
                                  slot_tiles, tiles_to_rows)


def _kernel(g_ref, val_ref, msk_ref, y_ref, *, semiring: str):
    combine, times, ident = SEMIRINGS[semiring]
    ident = jnp.asarray(ident, y_ref.dtype)

    def slot(j):
        prod = times(val_ref[j], g_ref[j])
        return jnp.where(msk_ref[j] != 0, prod, ident)

    fold_slots(y_ref, g_ref.shape[0], slot, combine, ident)


def ell_spmv_pallas(
    idx: jax.Array,
    val: jax.Array,
    msk: jax.Array,
    x: jax.Array,
    *,
    semiring: str = "add_mul",
) -> jax.Array:
    """y = ⊕_k val ⊗ x[idx] per row.  Returns (R,) for an (N,) frontier and
    (R, L) for a stacked (N, L) lane frontier, in x.dtype."""
    r, k = idx.shape
    seg = segment_count(r, k)
    if seg > 1:                             # a hub bin: fold row segments
        ks = -(-k // seg)
        cut = lambda a: jnp.pad(a, ((0, 0), (0, seg * ks - k))).reshape(
            r * seg, ks)
        idx, val, msk = cut(idx), cut(val), cut(msk)
    tiles = slot_tiles(idx, val, msk)

    def call(x):
        lane_shape = x.shape[1:]            # () SpMV or (L,) SpMM
        (y,) = slot_fold_call(
            functools.partial(_kernel, semiring=semiring), tiles,
            lane_shape[0] if lane_shape else 1,
            name="ell_spmv",
            lane_edge=(gather_lanes(x, tiles),),
            shared_edge=(tiles.val, tiles.msk),
            out_dtypes=(x.dtype,))
        return (tiles_to_rows(y, tiles, lane_shape),)

    (y,) = map_lanes(call, (x,), tiles)
    lane_shape = x.shape[1:]
    if seg > 1:                             # ⊕ the segments in slot order
        y = y.reshape((r, seg) + lane_shape)
        acc = y[:, 0]
        for s in range(1, seg):
            acc = SEMIRINGS[semiring][0](acc, y[:, s])
        y = acc
    return y
