"""Seeded 4-neighbour lattice: the road-network stand-in.

A ``rows x cols`` grid, every edge between horizontal and vertical
neighbours stored in both directions with one weight, uniform in
``[low, high)``, shared by the two directions.  A copy kept with the
benchmark (``repro.data.graphs.grid_graph`` is the program's own).
"""

from __future__ import annotations

import numpy as np


def lattice_edges(rows: int, cols: int, low: float, high: float, seed: int):
    """-> (edges (E, 2) int64 with both directions, weights (E,) float32,
    n_vertices)."""
    rng = np.random.default_rng(seed)
    vid = np.arange(rows * cols, dtype=np.int64).reshape(rows, cols)
    one_way = np.concatenate([
        np.stack([vid[:, :-1].ravel(), vid[:, 1:].ravel()], axis=1),
        np.stack([vid[:-1, :].ravel(), vid[1:, :].ravel()], axis=1)])
    w = rng.uniform(low, high, len(one_way)).astype(np.float32)
    edges = np.concatenate([one_way, one_way[:, ::-1]])
    return edges, np.concatenate([w, w]), rows * cols
