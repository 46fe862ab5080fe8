"""Pallas kernel validation: shape/dtype sweeps + hypothesis property tests
against the pure-jnp oracles (interpret mode executes kernel bodies on CPU)."""

import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.kernels.ell_spmv import ell_spmv, ell_spmv_ref, to_ell
from repro.kernels.min_step import fused_min_step, fused_min_step_ref
from repro.kernels.pr_step import fused_pr_step, fused_pr_step_ref


def _random_ell(rng, r, k, n, density=0.5, dtype=np.float32):
    idx = rng.randint(0, n, size=(r, k)).astype(np.int32)
    val = rng.uniform(0.1, 2.0, size=(r, k)).astype(dtype)
    msk = rng.uniform(size=(r, k)) < density
    x = rng.uniform(0.0, 3.0, size=(n,)).astype(dtype)
    return jnp.asarray(idx), jnp.asarray(val), jnp.asarray(msk), jnp.asarray(x)


SHAPES = [(8, 16, 32), (64, 128, 100), (256, 130, 511), (300, 257, 1024),
          (1024, 128, 64)]
SEMIRINGS = ["add_mul", "min_add", "max_add", "min_mul", "max_min"]
MONOTONE = ["min_add", "min_mul", "max_add", "max_min"]


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("shape", SHAPES)
def test_ell_spmv_matches_ref(shape, semiring):
    r, k, n = shape
    rng = np.random.RandomState(hash((r, k, n)) % 2**31)
    idx, val, msk, x = _random_ell(rng, r, k, n)
    got = ell_spmv(idx, val, msk, x, semiring=semiring)
    want = ell_spmv_ref(idx, val, msk, x, semiring=semiring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_ell_spmv_dtypes(dtype):
    rng = np.random.RandomState(0)
    idx, val, msk, x = _random_ell(rng, 64, 32, 50, dtype=np.float32)
    x = x.astype(dtype)
    val = val.astype(dtype)
    got = ell_spmv(idx, val, msk, x, semiring="add_mul")
    want = ell_spmv_ref(idx, val, msk, x, semiring="add_mul")
    assert got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@settings(max_examples=25, deadline=None)
@given(
    r=st.integers(1, 90),
    k=st.integers(1, 140),
    n=st.integers(1, 200),
    semiring=st.sampled_from(SEMIRINGS),
    seed=st.integers(0, 2**16),
)
def test_ell_spmv_property(r, k, n, semiring, seed):
    rng = np.random.RandomState(seed)
    idx, val, msk, x = _random_ell(rng, r, k, n)
    got = ell_spmv(idx, val, msk, x, semiring=semiring)
    want = ell_spmv_ref(idx, val, msk, x, semiring=semiring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ell_spmv_all_masked_rows_yield_identity():
    rng = np.random.RandomState(1)
    idx, val, msk, x = _random_ell(rng, 16, 8, 10)
    msk = jnp.zeros_like(msk)
    y = ell_spmv(idx, val, msk, x, semiring="min_add")
    assert bool(jnp.all(jnp.isinf(y)))
    y = ell_spmv(idx, val, msk, x, semiring="add_mul")
    np.testing.assert_array_equal(np.asarray(y), 0.0)


def test_to_ell_roundtrip_spmv_equals_dense():
    """COO -> ELL -> spmv == dense matvec (the PageRank contraction)."""
    rng = np.random.RandomState(3)
    n = 37
    edges = np.unique(rng.randint(0, n, size=(200, 2)), axis=0)
    w = rng.uniform(0.1, 1.0, size=len(edges)).astype(np.float32)
    idx, val, msk = to_ell(np.asarray(edges), n, weights=w)
    x = rng.uniform(size=(n,)).astype(np.float32)
    a = np.zeros((n, n), np.float32)
    a[edges[:, 1], edges[:, 0]] = w       # A[dst, src]
    want = a @ x
    got = np.asarray(ell_spmv(idx, val, msk, jnp.asarray(x)))[:n]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# fused PageRank pseudo-superstep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(16, 8, 16), (128, 128, 128),
                                   (260, 140, 300)])
def test_fused_pr_step_matches_ref(shape):
    r, k, n = shape
    rng = np.random.RandomState(5)
    idx = jnp.asarray(rng.randint(0, n, size=(r, k)).astype(np.int32))
    val = jnp.asarray(rng.uniform(0, 1, size=(r, k)).astype(np.float32))
    msk = jnp.asarray(rng.uniform(size=(r, k)) < 0.4)
    delta = jnp.asarray(rng.uniform(0, 0.1, size=(n,)).astype(np.float32))
    send = jnp.asarray(rng.uniform(size=(n,)) < 0.5)
    rank = jnp.asarray(rng.uniform(0, 2, size=(r,)).astype(np.float32))
    got = fused_pr_step(idx, val, msk, delta, send, rank, tol=1e-3)
    want = fused_pr_step_ref(idx, val, msk, delta, send, rank, tol=1e-3)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(r=st.integers(1, 64), k=st.integers(1, 96), n=st.integers(1, 128),
       seed=st.integers(0, 2**16))
def test_fused_pr_step_property(r, k, n, seed):
    rng = np.random.RandomState(seed)
    idx = jnp.asarray(rng.randint(0, n, size=(r, k)).astype(np.int32))
    val = jnp.asarray(rng.uniform(0, 1, size=(r, k)).astype(np.float32))
    msk = jnp.asarray(rng.uniform(size=(r, k)) < 0.5)
    delta = jnp.asarray(rng.uniform(0, 0.1, size=(n,)).astype(np.float32))
    send = jnp.asarray(rng.uniform(size=(n,)) < 0.5)
    rank = jnp.asarray(rng.uniform(0, 2, size=(r,)).astype(np.float32))
    got = fused_pr_step(idx, val, msk, delta, send, rank)
    want = fused_pr_step_ref(idx, val, msk, delta, send, rank)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


def test_fused_pr_step_extra_folds_spill_bins():
    """The ``extra`` operand (sliced-ELL spill contributions) lands in the
    returned delta_in, rank and send decisions."""
    rng = np.random.RandomState(7)
    r, k, n = 64, 16, 64
    idx = jnp.asarray(rng.randint(0, n, size=(r, k)).astype(np.int32))
    val = jnp.asarray(rng.uniform(0, 1, size=(r, k)).astype(np.float32))
    msk = jnp.asarray(rng.uniform(size=(r, k)) < 0.4)
    delta = jnp.asarray(rng.uniform(0, 0.1, size=(n,)).astype(np.float32))
    send = jnp.asarray(rng.uniform(size=(n,)) < 0.5)
    rank = jnp.asarray(rng.uniform(0, 2, size=(r,)).astype(np.float32))
    extra = jnp.asarray(rng.uniform(0, 0.01, size=(r,)).astype(np.float32))
    got = fused_pr_step(idx, val, msk, delta, send, rank, extra, tol=1e-3)
    want = fused_pr_step_ref(idx, val, msk, delta, send, rank, extra,
                             tol=1e-3)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# fused min-semiring pseudo-superstep
# ---------------------------------------------------------------------------

def _random_min_problem(rng, r, k, n, density=0.5):
    idx = jnp.asarray(rng.randint(0, n, size=(r, k)).astype(np.int32))
    val = jnp.asarray(rng.uniform(0.1, 2.0, size=(r, k)).astype(np.float32))
    msk = jnp.asarray(rng.uniform(size=(r, k)) < density)
    x = jnp.asarray(np.where(rng.uniform(size=n) < 0.8,
                             rng.uniform(0, 10, size=n),
                             np.inf).astype(np.float32))
    send = jnp.asarray(rng.uniform(size=(n,)) < 0.5)
    return idx, val, msk, x, send


@pytest.mark.parametrize("shape", [(16, 8, 16), (128, 128, 128),
                                   (260, 140, 300)])
def test_fused_min_step_matches_ref(shape):
    r, k, n = shape
    rng = np.random.RandomState(9)
    idx, val, msk, x, send = _random_min_problem(rng, r, k, n)
    xrow = jnp.asarray(rng.uniform(0, 10, size=(r,)).astype(np.float32))
    got = fused_min_step(idx, val, msk, x, send, xrow)
    want = fused_min_step_ref(idx, val, msk, x, send, xrow)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_fused_min_step_extra_and_defaults():
    """xrow defaults to the frontier (the engine case: rows == vertex
    slots) and ``extra`` min-folds spill-bin partials, +inf when absent."""
    rng = np.random.RandomState(3)
    r = n = 48
    idx, val, msk, x, send = _random_min_problem(rng, r, 12, n)
    extra = jnp.asarray(np.where(rng.uniform(size=r) < 0.3,
                                 rng.uniform(0, 1, size=r),
                                 np.inf).astype(np.float32))
    got = fused_min_step(idx, val, msk, x, send, extra=extra)
    want = fused_min_step_ref(idx, val, msk, x, send, x, extra)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # no senders at all -> d_in is +inf everywhere, state unchanged
    x2, d2, s2 = fused_min_step(idx, val, msk, x, jnp.zeros_like(send))
    assert bool(jnp.all(jnp.isinf(d2)))
    np.testing.assert_array_equal(np.asarray(x2), np.asarray(x))
    assert not bool(jnp.any(s2))


@settings(max_examples=15, deadline=None)
@given(r=st.integers(1, 64), k=st.integers(1, 96), n=st.integers(1, 128),
       seed=st.integers(0, 2**16))
def test_fused_min_step_property(r, k, n, seed):
    rng = np.random.RandomState(seed)
    idx, val, msk, x, send = _random_min_problem(rng, r, k, n)
    xrow = jnp.asarray(rng.uniform(0, 10, size=(r,)).astype(np.float32))
    got = fused_min_step(idx, val, msk, x, send, xrow)
    want = fused_min_step_ref(idx, val, msk, x, send, xrow)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _random_monotone_problem(rng, r, k, n, semiring, density=0.5):
    """Frontier/state draws matching the semiring's domain: unreached
    vertices sit at the ⊕ identity, edge values in the app's range."""
    idx = jnp.asarray(rng.randint(0, n, size=(r, k)).astype(np.int32))
    lo, hi = (1.0, 3.0) if semiring == "min_mul" else (0.1, 2.0)
    val = jnp.asarray(rng.uniform(lo, hi, size=(r, k)).astype(np.float32))
    msk = jnp.asarray(rng.uniform(size=(r, k)) < density)
    ident = np.inf if semiring.startswith("min") else -np.inf
    sign = -1.0 if semiring == "max_add" else 1.0
    x = jnp.asarray(np.where(rng.uniform(size=n) < 0.8,
                             sign * rng.uniform(0.1, 10, size=n),
                             ident).astype(np.float32))
    send = jnp.asarray(rng.uniform(size=(n,)) < 0.5)
    xrow = jnp.asarray((sign * rng.uniform(0.1, 10, size=r))
                       .astype(np.float32))
    return idx, val, msk, x, send, xrow


@pytest.mark.parametrize("semiring", MONOTONE)
@pytest.mark.parametrize("shape", [(16, 8, 16), (260, 140, 300)])
def test_fused_step_generalized_semirings(shape, semiring):
    """The fused pseudo-superstep kernel is one implementation for the whole
    monotone family: every (⊕, ⊗) pair matches its oracle bit-exactly,
    including the extra (spill) operand and the send'-improvement flags."""
    r, k, n = shape
    rng = np.random.RandomState(17)
    idx, val, msk, x, send, xrow = _random_monotone_problem(
        rng, r, k, n, semiring)
    got = fused_min_step(idx, val, msk, x, send, xrow, semiring=semiring)
    want = fused_min_step_ref(idx, val, msk, x, send, xrow,
                              semiring=semiring)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # an explicit ⊕-identity extra must be a no-op (the no-spill-bins case)
    ident = np.inf if semiring.startswith("min") else -np.inf
    extra = jnp.full((r,), ident, jnp.float32)
    got2 = fused_min_step(idx, val, msk, x, send, xrow, extra=extra,
                          semiring=semiring)
    for g, w in zip(got2, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@settings(max_examples=12, deadline=None)
@given(r=st.integers(1, 48), k=st.integers(1, 64), n=st.integers(1, 96),
       semiring=st.sampled_from(MONOTONE), seed=st.integers(0, 2**16))
def test_fused_step_generalized_property(r, k, n, semiring, seed):
    rng = np.random.RandomState(seed)
    idx, val, msk, x, send, xrow = _random_monotone_problem(
        rng, r, k, n, semiring)
    got = fused_min_step(idx, val, msk, x, send, xrow, semiring=semiring)
    want = fused_min_step_ref(idx, val, msk, x, send, xrow,
                              semiring=semiring)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# lane frontiers (SpMM): an (N, L) frontier is L queries in one dispatch
# ---------------------------------------------------------------------------

def _assert_kernel_eq(got, want, semiring):
    """Monotone (⊕ = min/max) is order-insensitive, so bit-exact; add_mul
    sums float products, so the kernel's fold and the oracle's jnp.sum may
    round differently."""
    if semiring in MONOTONE:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(r=st.integers(1, 64), k=st.integers(1, 96), n=st.integers(1, 128),
       lanes=st.integers(1, 5), semiring=st.sampled_from(SEMIRINGS),
       seed=st.integers(0, 2**16))
def test_ell_spmm_lanes_property(r, k, n, lanes, semiring, seed):
    """(N, L) frontier: matches the oracle, and every lane column is
    bit-identical to dispatching that lane's (N,) frontier alone (the
    micro-batching parity contract — the kernel folds the slice axis in
    the same order with or without a lane axis)."""
    rng = np.random.RandomState(seed)
    idx, val, msk, _ = _random_ell(rng, r, k, n)
    x = jnp.asarray(rng.uniform(0.0, 3.0, size=(n, lanes)).astype(np.float32))
    got = ell_spmv(idx, val, msk, x, semiring=semiring)
    assert got.shape == (r, lanes)
    _assert_kernel_eq(got, ell_spmv_ref(idx, val, msk, x, semiring=semiring),
                      semiring)
    for j in range(lanes):
        single = ell_spmv(idx, val, msk, x[:, j], semiring=semiring)
        np.testing.assert_array_equal(np.asarray(got[:, j]),
                                      np.asarray(single))


@pytest.mark.parametrize("semiring", MONOTONE)
@pytest.mark.parametrize("lanes", [1, 3])
def test_fused_min_step_lanes(semiring, lanes):
    """Fused monotone pseudo-superstep with lane frontiers: oracle parity
    plus per-lane bit-identity to single-lane dispatch, including per-lane
    ``extra`` spill operands and per-lane send' decisions."""
    r, k, n = 96, 24, 96
    rng = np.random.RandomState(11)
    idx, _, msk, _, _, _ = _random_monotone_problem(rng, r, k, n, semiring)
    lo, hi = (1.0, 3.0) if semiring == "min_mul" else (0.1, 2.0)
    val = jnp.asarray(rng.uniform(lo, hi, size=(r, k)).astype(np.float32))
    ident = np.inf if semiring.startswith("min") else -np.inf
    sign = -1.0 if semiring == "max_add" else 1.0
    x = jnp.asarray(np.where(rng.uniform(size=(n, lanes)) < 0.8,
                             sign * rng.uniform(0.1, 10, size=(n, lanes)),
                             ident).astype(np.float32))
    send = jnp.asarray(rng.uniform(size=(n, lanes)) < 0.5)
    xrow = jnp.asarray((sign * rng.uniform(0.1, 10, size=(r, lanes)))
                       .astype(np.float32))
    extra = jnp.asarray(np.where(rng.uniform(size=(r, lanes)) < 0.3,
                                 sign * rng.uniform(0.1, 1, size=(r, lanes)),
                                 ident).astype(np.float32))
    got = fused_min_step(idx, val, msk, x, send, xrow, extra,
                         semiring=semiring)
    want = fused_min_step_ref(idx, val, msk, x, send, xrow, extra,
                              semiring=semiring)
    for g, w in zip(got, want):
        assert g.shape == (r, lanes)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for j in range(lanes):
        singles = fused_min_step(idx, val, msk, x[:, j], send[:, j],
                                 xrow[:, j], extra[:, j], semiring=semiring)
        for g, s in zip(got, singles):
            np.testing.assert_array_equal(np.asarray(g[:, j]), np.asarray(s))


@pytest.mark.parametrize("lanes", [1, 3])
def test_fused_pr_step_lanes(lanes):
    """Fused PageRank pseudo-superstep with lane frontiers: oracle parity
    (allclose — additive folds) AND bit-identical lane columns vs
    single-lane dispatch (exact — the kernel's sequential slice-axis fold
    reduces each lane in single-frontier order)."""
    r, k, n = 96, 24, 96
    rng = np.random.RandomState(13)
    idx = jnp.asarray(rng.randint(0, n, size=(r, k)).astype(np.int32))
    val = jnp.asarray(rng.uniform(0, 1, size=(r, k)).astype(np.float32))
    msk = jnp.asarray(rng.uniform(size=(r, k)) < 0.4)
    delta = jnp.asarray(rng.uniform(0, 0.1, size=(n, lanes))
                        .astype(np.float32))
    send = jnp.asarray(rng.uniform(size=(n, lanes)) < 0.5)
    rank = jnp.asarray(rng.uniform(0, 2, size=(r, lanes)).astype(np.float32))
    extra = jnp.asarray(rng.uniform(0, 0.01, size=(r, lanes))
                        .astype(np.float32))
    got = fused_pr_step(idx, val, msk, delta, send, rank, extra, tol=1e-3)
    want = fused_pr_step_ref(idx, val, msk, delta, send, rank, extra,
                             tol=1e-3)
    for g, w in zip(got, want):
        assert g.shape == (r, lanes)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)
    for j in range(lanes):
        singles = fused_pr_step(idx, val, msk, delta[:, j], send[:, j],
                                rank[:, j], extra[:, j], tol=1e-3)
        for g, s in zip(got, singles):
            np.testing.assert_array_equal(np.asarray(g[:, j]), np.asarray(s))


@pytest.mark.parametrize("semiring", SEMIRINGS)
def test_ell_spmv_hub_bin_segments(semiring):
    """A short, wide bin (a few hub rows, thousands of slots) is folded as
    row segments: oracle parity, and lane columns still bit-identical to
    single-lane dispatch."""
    from repro.kernels.common import segment_count

    r, k, n, lanes = 12, 2100, 500, 2
    assert segment_count(r, k) > 1
    rng = np.random.RandomState(17)
    idx, val, msk, _ = _random_ell(rng, r, k, n)
    x = jnp.asarray(rng.uniform(0.0, 3.0, size=(n, lanes)).astype(np.float32))
    got = ell_spmv(idx, val, msk, x, semiring=semiring)
    _assert_kernel_eq(got, ell_spmv_ref(idx, val, msk, x, semiring=semiring),
                      semiring)
    for j in range(lanes):
        single = ell_spmv(idx, val, msk, x[:, j], semiring=semiring)
        np.testing.assert_array_equal(np.asarray(got[:, j]),
                                      np.asarray(single))


def test_lane_groups_bit_identical(monkeypatch):
    """Lane batches too wide for one gathered tile run in lane groups
    (``lax.map``); every output of all three kernels is bit-identical to
    the one-dispatch result."""
    import repro.kernels.common as kc
    from repro.kernels.ell_spmv.ell_spmv import ell_spmv_pallas
    from repro.kernels.min_step.min_step import fused_min_step_pallas
    from repro.kernels.pr_step.pr_step import fused_pr_step_pallas

    r, k, n, lanes = 40, 16, 40, 4
    rng = np.random.RandomState(19)
    idx, val, msk, _ = _random_ell(rng, r, k, n)
    x = jnp.asarray(rng.uniform(0.0, 3.0, size=(n, lanes)).astype(np.float32))
    send = jnp.asarray(rng.uniform(size=(n, lanes)) < 0.5)
    row = jnp.asarray(rng.uniform(0.0, 3.0, size=(r, lanes))
                      .astype(np.float32))
    calls = [
        lambda: (ell_spmv_pallas(idx, val, msk, x, semiring="add_mul"),),
        lambda: fused_min_step_pallas(idx, val, msk, x, send, row, row),
        lambda: fused_pr_step_pallas(idx, val, msk, x, send, row, 0 * row,
                                     tol=1e-3),
    ]
    whole = [call() for call in calls]
    monkeypatch.setattr(kc, "LANE_TILE_ELEMS", 1)     # one lane per group
    for call, want in zip(calls, whole):
        for g, w in zip(call(), want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
