"""Plain PageRank of Algorithm 5's dynamics: a float64 power iteration.

The fixed point of ``rank = (1 - d) + d * A^T (rank / out_degree)``, the
unnormalized recurrence whose mass at dangling vertices is not
redistributed.  Independent of the program: it takes the generated edge
list and nothing that the program built.
"""

from __future__ import annotations

import numpy as np


def ranks(edges: np.ndarray, n: int, damping: float = 0.85,
          rtol: float = 1e-12, max_iters: int = 1000) -> np.ndarray:
    """(n,) float64 fixed point, iterated until no rank moves by more than
    ``rtol`` of itself."""
    from scipy.sparse import csr_matrix

    deg = np.bincount(edges[:, 0], minlength=n).astype(np.float64)
    at = csr_matrix((1.0 / deg[edges[:, 0]], (edges[:, 1], edges[:, 0])),
                    shape=(n, n))
    r = np.full(n, 1.0 - damping)
    for _ in range(max_iters):
        nxt = (1.0 - damping) + damping * (at @ r)
        done = np.max(np.abs(nxt - r) / nxt) < rtol
        r = nxt
        if done:
            return r
    raise RuntimeError(f"power iteration did not reach rtol {rtol} in "
                       f"{max_iters} iterations")


def compare(got: np.ndarray, ref: np.ndarray) -> dict:
    """``rel_err``: the largest relative error of a vertex's rank, in
    either direction (every rank is at least 1 - d > 0)."""
    got = np.asarray(got, np.float64)
    return {"rel_err": float(np.max(np.abs(got - ref) / ref))}
