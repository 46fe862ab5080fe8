"""Jitted wrapper + layout conversion for the ELL semiring SpMV kernel."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.common import ell_pack_numpy
from repro.kernels.ell_spmv.ell_spmv import ell_spmv_pallas


@functools.partial(jax.jit, static_argnames=("semiring",))
def ell_spmv(idx, val, msk, x, *, semiring: str = "add_mul") -> jax.Array:
    """Jitted semiring SpMV/SpMM: y[r] = ⊕_k val[r,k] ⊗ x[idx[r,k]].

    ``x`` is an (N,) frontier vector (SpMV, returns (R,)) or an (N, L)
    stacked frontier of L query lanes (semiring SpMM, returns (R, L) — one
    dispatch answers L simultaneous sources over the same edge tiles).

    The kernel lowers to Mosaic on a TPU and runs in interpret mode
    elsewhere (``kernels.common.default_interpret``).
    """
    return ell_spmv_pallas(idx, val, msk, x, semiring=semiring)


def to_ell(edges: np.ndarray, n_rows: int,
           weights: np.ndarray | None = None,
           pad_rows: int = 8, pad_slices: int = 128):
    """Pack a COO edge list (src, dst) into destination-major ELL arrays.

    Returns (idx (R,K) int32, val (R,K) f32, msk (R,K) bool) with
    R = n_rows rounded up to ``pad_rows`` and K = max in-degree rounded up to
    ``pad_slices`` (TPU lane alignment).
    """
    edges = np.asarray(edges)
    if weights is None:
        weights = np.ones(len(edges), dtype=np.float32)
    indeg = np.bincount(edges[:, 1], minlength=n_rows)
    kmax = int(indeg.max()) if len(indeg) else 1
    K = max(pad_slices, ((kmax + pad_slices - 1) // pad_slices) * pad_slices)
    R = ((n_rows + pad_rows - 1) // pad_rows) * pad_rows
    idx, val, msk = ell_pack_numpy(edges[:, 0], edges[:, 1], weights, R, K)
    return jnp.asarray(idx), jnp.asarray(val), jnp.asarray(msk)
