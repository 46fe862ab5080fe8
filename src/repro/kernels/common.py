"""Shared ELL kernel plumbing: the semiring table, the slot-major tile
layout and the one grid every kernel runs on, the backend-dependent
interpret default, and the vectorized destination-major ELL packer.

The engine stores ELL bins row-major, ``(R, K)``: row r, slot k.  The TPU's
compiler cannot gather from a frontier inside a kernel (Mosaic lowers only
gathers within one vreg), so each call gathers the source values in XLA and
hands the kernel a *slot-major* tile instead::

    tile[k, s, c]   = value of slot k of row  r = s * (Rp // 8) + c

i.e. ``(Kp, 8, Rp // 8)``: slots on an untiled leading axis, rows spread over
full (8, 128·m) vreg slabs.  A grid step reads a (bk, 8, bc) block and
folds its slots into an (8, bc) accumulator in slot order
0, 1, …, K-1 — one dense VPU op per slot, and the same association for every
blocking, so sums stay bit-identical across tile shapes and lane counts.
A stacked (N, L) lane frontier gathers to ``(Kp, 8, L * Rp // 8)`` (lane l
owns columns [l·Rp/8, (l+1)·Rp/8)), and the edge tiles shared by all lanes
are read through the block index modulo the per-lane block count, so one
dispatch serves every lane without copying the edge arrays L times.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["SEMIRINGS", "MONOTONE_SEMIRINGS", "semiring_improves",
           "default_interpret", "SlotTiles", "slot_tiles", "gather_lanes",
           "segment_count", "map_lanes", "rows_to_tiles", "tiles_to_rows",
           "slot_fold_call", "fold_slots",
           "ell_pack_numpy", "ell_bin_widths", "sliced_ell_pack_numpy"]

#: Most slots per grid step (the untiled leading block axis the kernel folds).
BLOCK_SLOTS = 128
#: Elements of one operand block, bk * 8 * bc: 1 MiB of f32, so a step's
#: few double-buffered operands stay well inside the default scoped VMEM.
BLOCK_ELEMS = 1 << 18
#: Elements one lane-stacked gathered tile may hold (256 MiB of f32).  A
#: wider lane batch runs through ``lax.map`` in lane groups (see
#: :func:`map_lanes`), whose buffers one group after another reuses.
LANE_TILE_ELEMS = 1 << 26


#: Semiring table: ``name -> (⊕ combine, ⊗ times, ⊕-identity)``.
#:
#: Every kernel (``ell_spmv``, fused ``pr_step``/``min_step``), the engine
#: dispatch in ``runtime.deliver``, and the reference oracles are generic
#: over this table.  The entries:
#:
#: - ``add_mul``  (+, ×, 0)        — PageRank mass propagation
#: - ``min_add``  (min, +, +inf)   — shortest paths / HashMin WCC
#: - ``max_add``  (max, +, -inf)   — best-score / log-likelihood paths
#: - ``min_mul``  (min, ×, +inf)   — odds propagation
#: - ``max_min``  (max, min, -inf) — bottleneck / widest-path capacity
#:
#: ``⊕`` folds edge products per destination row, ``⊗`` combines a source
#: value with an edge weight, and the identity fills masked ELL slots so
#: padding never contributes.  Adding an entry here is all a new semiring
#: needs (plus a `_SCATTER` rule in runtime for its spill bins).  A
#: ``Channel(semiring=...)`` naming an entry opts that channel into the
#: kernel delivery path; monotone entries (see ``MONOTONE_SEMIRINGS``)
#: additionally unlock the fused ``min_step`` local phase.
SEMIRINGS = {
    "add_mul": (jnp.add, jnp.multiply, 0.0),
    "min_add": (jnp.minimum, jnp.add, jnp.inf),
    "max_add": (jnp.maximum, jnp.add, -jnp.inf),
    "min_mul": (jnp.minimum, jnp.multiply, jnp.inf),
    "max_min": (jnp.maximum, jnp.minimum, -jnp.inf),
}

# Semirings whose ⊕ is a selection (min/max) rather than an accumulation:
# vertex state under these evolves monotonically (new = x ⊕ d_in, re-send on
# strict improvement), which is exactly the contract the fused `min_step`
# pseudo-superstep kernel generalizes over.
MONOTONE_SEMIRINGS = frozenset({"min_add", "min_mul", "max_add", "max_min"})


def semiring_improves(semiring: str):
    """Strict-improvement predicate of a monotone semiring: did ``new``
    beat ``old`` under ⊕?  (< for the min family, > for the max family.)"""
    if semiring not in MONOTONE_SEMIRINGS:  # pragma: no cover
        raise ValueError(f"{semiring} has no improvement direction")
    return jnp.less if semiring.startswith("min") else jnp.greater


def default_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: never on a TPU (Mosaic
    lowering), always elsewhere.  The one place the flag is derived."""
    return jax.default_backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@dataclasses.dataclass(frozen=True)
class SlotTiles:
    """One ELL bin in the slot-major kernel layout (see module docstring).

    ``idx``/``val``/``msk`` are ``(Kp, 8, Rp // 8)``; padded rows and slots
    carry index 0 and mask 0.  ``msk`` is int32 (Mosaic takes no bool
    memrefs).  Built inside the jitted wrappers, never stored."""

    idx: jax.Array
    val: jax.Array
    msk: jax.Array
    rows: int        # R, the caller's row count
    bk: int          # slots per grid step
    bc: int          # columns per grid step (a multiple of 128)

    @property
    def cols(self) -> int:
        """Rp // 8: columns of one lane's slab."""
        return self.idx.shape[2]


def slot_tiles(idx, val, msk) -> SlotTiles:
    """Row-major (R, K) ELL arrays -> slot-major :class:`SlotTiles`.  The
    block shape follows the bin's: up to ``BLOCK_SLOTS`` slots, and as many
    128-column slabs as ``BLOCK_ELEMS`` allows and the rows need; rows and
    slots pad to whole blocks."""
    r, k = idx.shape
    bk = min(k, BLOCK_SLOTS)
    cols = _round_up(-(-max(r, 1) // 8), 128)
    bc = min(cols, max(128, BLOCK_ELEMS // (8 * bk) // 128 * 128))
    kp, rp = _round_up(k, bk), 8 * _round_up(cols, bc)

    def tile(a):
        a = jnp.pad(a, ((0, rp - r), (0, kp - k)))
        return a.T.reshape(kp, 8, rp // 8)

    return SlotTiles(idx=tile(idx), val=tile(val),
                     msk=tile(msk.astype(jnp.int32)), rows=r, bk=bk, bc=bc)


def gather_lanes(src, tiles: SlotTiles, fn=None):
    """``fn(src[idx])`` in the slot-major layout: (Kp, 8, L * Rp // 8) for
    an (N,) (L = 1) or (N, L) source — the gather the kernels cannot do.
    One gather per lane on the shared index tile, written lane by lane into
    one buffer, so no lane-sized index array is ever built; padded slots
    index 0, so every index is in bounds."""
    def take(col):
        g = col.at[tiles.idx].get(mode="promise_in_bounds")
        return g if fn is None else fn(g)

    if src.ndim == 1:
        return take(src)
    return jnp.concatenate([take(src[:, l]) for l in range(src.shape[1])],
                           axis=2)


def segment_count(r: int, k: int) -> int:
    """How many row segments to cut a wide, short (R, K) bin into.

    Tiles pad rows to whole (8, 128) slabs, so a hub bin of a few dozen
    rows and thousands of slots would spend most of every tile — and of
    every lane's gathered copy — on padding.  Such a bin is folded as
    (R·S, K/S) segments instead and the S partials ⊕-combined per row;
    bins with at most 8·BLOCK_SLOTS slots or rows filling half the padded
    slab keep the one sequential fold."""
    rp = 8 * _round_up(-(-max(r, 1) // 8), 128)
    if k <= 8 * BLOCK_SLOTS or 2 * r > rp:
        return 1
    return max(1, min(rp // max(r, 1), k // BLOCK_SLOTS))


def map_lanes(fn, arrays, tiles: SlotTiles):
    """``fn(*arrays)`` for arrays that share a trailing lane axis (or none),
    run over lane groups small enough that a group's gathered tiles fit
    :data:`LANE_TILE_ELEMS`.  Lanes are independent, so every lane's result
    is bit-identical to one dispatch of all of them; ``fn`` returns a tuple
    of (R, g) arrays for a group of g lanes."""
    if arrays[0].ndim == 1:
        return fn(*arrays)
    lanes = arrays[0].shape[1]
    per_lane = tiles.idx.size
    group = max(d for d in range(1, lanes + 1)
                if lanes % d == 0 and (d == 1 or d * per_lane
                                       <= LANE_TILE_ELEMS))
    if group == lanes:
        return fn(*arrays)
    split = lambda a: jnp.moveaxis(
        a.reshape(a.shape[0], lanes // group, group), 1, 0)
    merge = lambda o: jnp.moveaxis(o, 0, 1).reshape(o.shape[1], lanes)
    outs = jax.lax.map(lambda xs: fn(*xs), tuple(split(a) for a in arrays))
    return tuple(merge(o) for o in outs)


def rows_to_tiles(a, tiles: SlotTiles):
    """Per-row (R,) or (R, L) array -> the kernel's (8, L * Rp // 8) row
    layout (padded rows are 0)."""
    c = tiles.cols
    a = jnp.pad(a, ((0, 8 * c - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))
    if a.ndim == 1:
        return a.reshape(8, c)
    lanes = a.shape[1]
    return a.reshape(8, c, lanes).transpose(0, 2, 1).reshape(8, lanes * c)


def tiles_to_rows(t, tiles: SlotTiles, lane_shape: tuple):
    """Inverse of :func:`rows_to_tiles`: (R,) or (R, L) per ``lane_shape``."""
    c = tiles.cols
    if not lane_shape:
        return t.reshape(8 * c)[:tiles.rows]
    (lanes,) = lane_shape
    return (t.reshape(8, lanes, c).transpose(0, 2, 1)
            .reshape(8 * c, lanes)[:tiles.rows])


def fold_slots(acc_ref, n_slots: int, slot, combine, ident):
    """⊕-fold ``slot(j)`` for the block's slots j = 0..n_slots-1 onto the
    accumulator carried in ``acc_ref`` across the K grid axis (axis 1),
    starting from the ⊕-identity on the first K step.  Returns the folded
    (8, bc) value (also stored)."""
    acc = jnp.where(pl.program_id(1) == 0,
                    jnp.asarray(ident, acc_ref.dtype), acc_ref[...])
    unroll = 8          # Mosaic loops unroll fully or not at all: by hand

    def body(i, a):
        for t in range(unroll):
            a = combine(a, slot(i * unroll + t))
        return a

    acc = jax.lax.fori_loop(0, n_slots // unroll, body, acc)
    for j in range(n_slots - n_slots % unroll, n_slots):
        acc = combine(acc, slot(j))
    acc_ref[...] = acc
    return acc


def slot_fold_call(kernel, tiles: SlotTiles, lanes: int, *, name: str,
                   lane_edge=(), shared_edge=(), row_ops=(), out_dtypes=()):
    """One ``pallas_call`` named ``name`` on the slot-major grid
    (L·C/bc, Kp/bk): the name is the kernel's in a device trace.

    Operands, in the kernel's argument order: ``lane_edge`` tiles
    (Kp, 8, L·C), ``shared_edge`` tiles (Kp, 8, C) read by every lane,
    ``row_ops`` in the (8, L·C) row layout; outputs are (8, L·C) of
    ``out_dtypes``, each block revisited along the K axis.  Interpret mode
    follows :func:`default_interpret`."""
    c, bk, bc = tiles.cols, tiles.bk, tiles.bc
    nrb = c // bc
    grid = (lanes * nrb, tiles.idx.shape[0] // bk)
    lane_spec = pl.BlockSpec((bk, 8, bc), lambda j, k: (k, 0, j))
    shared_spec = pl.BlockSpec((bk, 8, bc), lambda j, k: (k, 0, j % nrb))
    row_spec = pl.BlockSpec((8, bc), lambda j, k: (0, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=([lane_spec] * len(lane_edge)
                  + [shared_spec] * len(shared_edge)
                  + [row_spec] * len(row_ops)),
        out_specs=[row_spec] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct((8, lanes * c), dt)
                   for dt in out_dtypes],
        interpret=default_interpret(),
        name=name,
    )(*lane_edge, *shared_edge, *row_ops)


def ell_pack_numpy(src: np.ndarray, dst: np.ndarray, w: np.ndarray,
                   n_rows: int, k_slices: int):
    """Vectorized destination-major ELL pack (host-side, numpy).

    Slot k of row d holds the k-th edge of destination d in stable
    dst-sorted input order — identical layout to a per-edge scatter loop,
    but O(E) vectorized: after the stable sort by destination the slot of
    each edge is its rank within its destination run (arange minus the run's
    first index via searchsorted on the sorted keys).

    Returns (idx (n_rows, k_slices) int32, val float32, msk bool).
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    w = np.asarray(w, dtype=np.float32)
    idx = np.zeros((n_rows, k_slices), dtype=np.int32)
    val = np.zeros((n_rows, k_slices), dtype=np.float32)
    msk = np.zeros((n_rows, k_slices), dtype=bool)
    if len(dst) == 0:
        return idx, val, msk
    order = np.argsort(dst, kind="stable")
    src_s, dst_s, w_s = src[order], dst[order], w[order]
    slot = np.arange(len(dst_s)) - np.searchsorted(dst_s, dst_s, side="left")
    idx[dst_s, slot] = src_s
    val[dst_s, slot] = w_s
    msk[dst_s, slot] = True
    return idx, val, msk


def ell_bin_widths(kmax: int, base_slices: int, pad: int,
                   growth: int = 8) -> list[tuple[int, int]]:
    """Slot ranges ``(lo, kb)`` of the sliced-ELL degree bins for a row set
    whose maximum in-degree is ``kmax``.

    Bin 0 holds slots [0, K0) of *every* row (dense, no row indirection);
    spill bins hold the overflow slots of the high-degree rows only.  When
    the padded max degree fits ``base_slices`` this degenerates to the
    single dense bin of the unbinned layout; otherwise spill widths grow
    geometrically (``growth``× per bin) until ``kmax`` is covered, so a
    row pads to at most ``growth``× the slots it fills in any bin and the
    bin count grows only with log(kmax / base) — a power-law hub never
    pads every spilling row to its own degree.
    """
    if kmax <= 0:
        return []
    rup = lambda n: ((n + pad - 1) // pad) * pad if n > 0 else pad
    base = rup(base_slices)
    if rup(kmax) <= base:
        return [(0, rup(kmax))]
    bins = [(0, base)]
    lo = base
    while kmax > lo:
        kb = min(rup(kmax - lo), rup(base * growth ** len(bins)))
        bins.append((lo, kb))
        lo += kb
    return bins


def sliced_ell_pack_numpy(src: np.ndarray, dst: np.ndarray, w: np.ndarray,
                          n_rows: int, widths: list[tuple[int, int]],
                          order_rank: tuple[np.ndarray, np.ndarray] | None
                          = None,
                          extras: tuple[np.ndarray, ...] = ()):
    """Pack a destination-major edge set into sliced-ELL degree bins.

    ``widths`` comes from :func:`ell_bin_widths`: bin b owns each row's edge
    slots [lo_b, lo_b + kb_b) in stable dst-sorted order.  Bin 0 (lo == 0)
    is packed dense over all ``n_rows``; spill bins carry only the rows
    whose degree exceeds their ``lo``, as a (rows, idx, val, msk) quadruple
    where ``rows`` lists the destination row ids in ascending order.

    ``order_rank`` optionally supplies the stable dst argsort and the
    per-edge rank within its destination run, when the caller has already
    computed them over the same edge set.

    ``extras`` are additional per-edge int payloads (e.g. accounting group
    ids) packed into the same slots, zero on padding; each appends one
    (nb, kb) int32 array to every bin's tuple.

    Returns ``[(rows (nb,) int32, idx (nb, kb) int32, val f32, msk bool,
    *extras)]`` per bin (``rows`` is None for the dense base bin).
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    w = np.asarray(w, dtype=np.float32)
    if order_rank is None:
        order = np.argsort(dst, kind="stable")
        rank = None
    else:
        order, rank = order_rank
    src_s, dst_s, w_s = src[order], dst[order], w[order]
    extras_s = tuple(np.asarray(e, dtype=np.int64)[order] for e in extras)
    if rank is None:
        rank = (np.arange(len(dst_s))
                - np.searchsorted(dst_s, dst_s, side="left"))
    degree = np.zeros(n_rows, dtype=np.int64)
    if len(dst_s):
        np.add.at(degree, dst_s, 1)

    out = []
    for lo, kb in widths:
        sel = (rank >= lo) & (rank < lo + kb)
        if lo == 0:
            rows = None
            nb = n_rows
            r = dst_s[sel]
        else:
            rows = np.nonzero(degree > lo)[0].astype(np.int32)
            row_of = np.zeros(n_rows, dtype=np.int64)
            row_of[rows] = np.arange(len(rows))
            nb = len(rows)
            r = row_of[dst_s[sel]]
        idx = np.zeros((nb, kb), dtype=np.int32)
        val = np.zeros((nb, kb), dtype=np.float32)
        msk = np.zeros((nb, kb), dtype=bool)
        ext = tuple(np.zeros((nb, kb), dtype=np.int32) for _ in extras_s)
        s = rank[sel] - lo
        idx[r, s] = src_s[sel]
        val[r, s] = w_s[sel]
        msk[r, s] = True
        for packed, e in zip(ext, extras_s):
            packed[r, s] = e[sel]
        out.append((rows, idx, val, msk) + ext)
    return out
