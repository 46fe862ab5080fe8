"""Jitted wrapper for the fused PageRank pseudo-superstep kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pr_step.pr_step import fused_pr_step_pallas


@functools.partial(jax.jit, static_argnames=("damping", "tol"))
def fused_pr_step(idx, val, msk, delta, send, rank, extra=None, *,
                  damping: float = 0.85, tol: float = 1e-4):
    """``extra`` carries the sliced-ELL spill bins' pre-combined per-row
    contributions (zeros / omitted when the layout has a single bin).  With
    an (N, L) lane frontier every operand and output carries the trailing L
    axis (K-lane SpMM dispatch)."""
    if extra is None:
        extra = jnp.zeros(idx.shape[:1] + delta.shape[1:], rank.dtype)
    return fused_pr_step_pallas(idx, val, msk, delta, send, rank, extra,
                                damping=damping, tol=tol)
