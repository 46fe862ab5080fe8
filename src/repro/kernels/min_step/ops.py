"""Jitted wrapper for the fused monotone-semiring pseudo-superstep kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import SEMIRINGS
from repro.kernels.min_step.min_step import fused_min_step_pallas


@functools.partial(jax.jit, static_argnames=("semiring",))
def fused_min_step(idx, val, msk, x, send, xrow=None, extra=None, *,
                   semiring: str = "min_add"):
    """Jitted fused monotone pseudo-superstep -> (x', d_in, send').

    ``semiring`` is any ``MONOTONE_SEMIRINGS`` entry (default the historic
    'min_add'); ``xrow`` defaults to ``x`` (rows and frontier share the
    vertex slot space, the engine case); ``extra`` defaults to the
    ⊕-identity (no spill bins).  With an (N, L) lane frontier every operand
    and output carries the trailing L axis (K-lane SpMM dispatch).
    """
    if xrow is None:
        xrow = x
    if extra is None:
        _, _, ident = SEMIRINGS[semiring]
        extra = jnp.full(idx.shape[:1] + x.shape[1:], ident, x.dtype)
    return fused_min_step_pallas(idx, val, msk, x, send, xrow, extra,
                                 semiring=semiring)
