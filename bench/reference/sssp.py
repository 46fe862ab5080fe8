"""Plain single-source shortest paths: scipy's Dijkstra in float64.

Independent of the program: it takes the generated edge list and weights
and nothing that the program built.
"""

from __future__ import annotations

import numpy as np


def csr(edges: np.ndarray, weights: np.ndarray, n: int):
    """(n, n) float64 CSR matrix of the arcs; duplicate arcs keep the
    lightest weight, as a shortest path does."""
    from scipy.sparse import coo_matrix

    order = np.lexsort((weights, edges[:, 1], edges[:, 0]))
    e, w = edges[order], weights[order].astype(np.float64)
    first = np.ones(len(e), bool)
    first[1:] = np.any(e[1:] != e[:-1], axis=1)
    return coo_matrix((w[first], (e[first, 0], e[first, 1])),
                      shape=(n, n)).tocsr()


def distances(edges: np.ndarray, weights: np.ndarray, n: int, roots
              ) -> np.ndarray:
    """(len(roots), n) float64 distances, +inf where unreachable."""
    from scipy.sparse.csgraph import dijkstra

    return np.atleast_2d(dijkstra(csr(edges, weights, n), directed=True,
                                  indices=np.asarray(roots)))


def compare(got: np.ndarray, ref: np.ndarray) -> dict:
    """The numbers that decide a job's correctness: ``unreached``, the
    vertices whose reachability differs, and ``rel_err``, the largest
    relative error of a distance both sides reach."""
    got = np.asarray(got, np.float64)
    fin_got, fin_ref = np.isfinite(got), np.isfinite(ref)
    both = fin_got & fin_ref
    err = np.abs(got[both] - ref[both]) / np.maximum(np.abs(ref[both]),
                                                     np.finfo(np.float32).tiny)
    return {"unreached": int(np.sum(fin_got != fin_ref)),
            "rel_err": float(err.max()) if err.size else 0.0}
