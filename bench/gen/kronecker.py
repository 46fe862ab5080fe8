"""Graph500 Kronecker generator (graph500.org specification, section 3).

A copy kept with the benchmark, so that a change to the program's own
generators (``repro.data.graphs``) cannot move the yardstick.  It follows
the specification's reference code:

* ``M = edge_factor * 2**scale`` edges, each placed by ``scale`` draws of
  the 2x2 initiator ``[[A, B], [C, D]]``, one bit of each end per draw;
* vertex labels permuted by a random permutation;
* the graph is undirected: self-loops and duplicate edges are dropped and
  every remaining edge is stored in both directions;
* kernel 3's weights, uniform in ``[0, 1)``, one per undirected edge, the
  same in both directions.

Everything is drawn from one ``numpy`` PCG64 stream seeded by ``seed``.
"""

from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, edge_factor: int, initiator, seed: int):
    """Undirected Kronecker graph -> (edges (E, 2) int64 with both
    directions, weights (E,) float32, n_vertices).  Within each
    direction, rows are sorted by (source, destination)."""
    a, b, c, _ = (float(x) for x in initiator)
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = edge_factor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        ii = rng.random(m, dtype=np.float32) > ab
        jj = rng.random(m, dtype=np.float32) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << bit
        dst |= jj.astype(np.int64) << bit
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    key = np.unique(lo * n + hi)            # one undirected edge per pair
    lo, hi = key // n, key % n
    w = rng.random(len(key), dtype=np.float32)
    edges = np.concatenate([np.stack([lo, hi], axis=1),
                            np.stack([hi, lo], axis=1)])
    return edges, np.concatenate([w, w]), n
