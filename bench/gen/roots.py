"""Root sampling for single-source jobs.

Graph500 draws its search keys from the vertices of degree >= 1.  Here
they are drawn from the largest connected component, so that every job
of every seed traverses the same giant component and no seed's window is
padded with trivial jobs from tiny components.
"""

from __future__ import annotations

import numpy as np


def sample_roots(edges: np.ndarray, n: int, count: int, seed: int):
    """``count`` distinct seeded vertices of the largest connected
    component of the undirected view of ``edges``, in draw order."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components

    adj = csr_matrix((np.ones(len(edges), np.int8),
                      (edges[:, 0], edges[:, 1])), shape=(n, n))
    _, label = connected_components(adj, directed=True, connection="weak")
    giant = np.flatnonzero(label == np.argmax(np.bincount(label)))
    rng = np.random.default_rng([seed, 1])
    return rng.choice(giant, size=min(count, len(giant)), replace=False)
