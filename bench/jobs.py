"""Job kinds: what a traffic file's ``"job"`` names.

A job kind turns the traffic file's parameters into the program a user
runs, the per-job inputs, the weights the graph is built with, and the
comparison with the plain reference.  A traffic file is data: adding a
cell of a kind listed here needs no code.
"""

from __future__ import annotations

import numpy as np

from bench.gen.roots import sample_roots
from bench.reference import pagerank as pagerank_ref
from bench.reference import sssp as sssp_ref


class Sssp:
    """Single-source shortest paths from a seeded sequence of roots,
    through the program's data-driven source (``vdata["sources"]``), so
    one compiled program serves every root.  Job ``i`` of the window
    starts at ``roots[1 + i % (len(roots) - 1)]``; the warm-up job at
    ``roots[0]``."""

    state_key = "val"

    def __init__(self, traffic: dict, edges, weights, n: int, seed: int):
        self.traffic = traffic
        self.roots = sample_roots(edges, n, int(traffic["roots"]) + 1, seed)

    @staticmethod
    def weights(edges, weights, n):
        return weights

    def program(self):
        from repro.core.apps import MultiSourceMonotone
        return MultiSourceMonotone(lanes=1,
                                   semiring=self.traffic["semiring"])

    def root(self, job: int) -> int:
        """Root of window job ``job``; ``-1`` is the warm-up job."""
        if job < 0:
            return int(self.roots[0])
        return int(self.roots[1 + job % (len(self.roots) - 1)])

    def vdata(self, job: int):
        import jax.numpy as jnp
        return {"sources": jnp.asarray([self.root(job)], jnp.int32)}

    def check(self, outputs: dict, edges, weights, n) -> dict:
        """Readings of each window job in ``outputs`` (job -> (n, 1))."""
        jobs = sorted(outputs)
        ref = sssp_ref.distances(edges, weights, n,
                                 [self.root(j) for j in jobs])
        return {j: sssp_ref.compare(outputs[j][:, 0], r)
                for j, r in zip(jobs, ref)}


class PageRank:
    """Incremental PageRank (Algorithm 5) over 1/out-degree weights.
    Every job is the same job: the window repeats it."""

    state_key = "rank"

    def __init__(self, traffic: dict, edges, weights, n: int, seed: int):
        self.traffic = traffic

    @staticmethod
    def weights(edges, weights, n):
        deg = np.bincount(edges[:, 0], minlength=n).astype(np.float32)
        return (1.0 / deg[edges[:, 0]]).astype(np.float32)

    def program(self):
        from repro.core.apps import IncrementalPageRank
        return IncrementalPageRank(tolerance=self.traffic["tolerance"],
                                   damping=self.traffic["damping"])

    def vdata(self, job: int):
        return None

    def check(self, outputs: dict, edges, weights, n) -> dict:
        ref = pagerank_ref.ranks(edges, n, self.traffic["damping"])
        return {j: pagerank_ref.compare(out, ref)
                for j, out in outputs.items()}


JOBS = {"sssp": Sssp, "pagerank": PageRank}


def message_bytes(prog, graph) -> int:
    """Bytes one message must move: its value (every payload component of
    every lane), the arc's weight and the neighbour's index."""
    (ch,) = prog.channels
    value = sum(np.dtype(dt).itemsize for dt, _ in ch.components)
    return (value * max(ch.lanes, 1) + graph.edge_w.dtype.itemsize
            + graph.edge_src.dtype.itemsize)
