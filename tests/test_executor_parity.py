"""Golden executor-parity suite: the superstep-executor refactor must be
bit-invisible.

``tests/data/golden_executor.json`` holds, for every app × engine ×
delivery path (dense / ELL), a sha256 digest of the final engine state
(state channels + send/active masks) plus the iteration count and every
paper counter, captured from the pre-refactor ``run_bsp`` / ``run_am`` /
``run_hybrid``.  The tests below re-run the same workloads through the
current engines and assert bit-identity — state, iterations, and every
counter — so any drift the unification introduces (a reordered reduction,
a counter bumped in the wrong place, a changed halt rule) fails loudly.

Regenerate (only when a change is *supposed* to move the fixed points):

    PYTHONPATH=src python tests/test_executor_parity.py --regen
"""

import hashlib
import json
import os

import numpy as np
import pytest

GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "golden_executor.json")

ENGINES = ("bsp", "am", "hybrid")
DELIVERY = (("dense", False), ("ell", True))


def _workloads():
    """Deterministic small fixtures: one per app family."""
    from repro.core import bfs_partition, build_partitioned_graph, \
        hash_partition
    from repro.core.apps import (SSSP, WCC, BipartiteMatching,
                                 IncrementalPageRank, RandomWalk, WidestPath)
    from repro.core.apps.pagerank import pagerank_edge_weights
    from repro.core.apps.random_walk import random_walk_edge_weights
    from repro.data.graphs import (bipartite_graph, grid_graph, rmat_graph,
                                   symmetrize)

    out = {}

    edges, w, n = grid_graph(6, 30, seed=3)
    part = bfs_partition(edges, n, 4, seed=1)
    out["sssp"] = (build_partitioned_graph(edges, n, part, weights=w),
                   lambda: SSSP(source=0), None)

    edges, n = rmat_graph(200, avg_degree=5, seed=7)
    part = hash_partition(n, 4, seed=2)
    w = pagerank_edge_weights(edges, n)
    out["pagerank"] = (build_partitioned_graph(edges, n, part, weights=w),
                       lambda: IncrementalPageRank(tolerance=1e-4), None)

    rng = np.random.RandomState(0)
    blocks, off = [], 0
    for size in (30, 25):
        e = rng.randint(0, size, size=(size * 3, 2)) + off
        p = np.stack([np.arange(size - 1), np.arange(1, size)], axis=1) + off
        blocks.append(np.concatenate([e, p], axis=0))
        off += size
    edges = symmetrize(np.concatenate(blocks, axis=0))
    edges = edges[edges[:, 0] != edges[:, 1]]
    part = hash_partition(off, 4, seed=3)
    out["wcc"] = (build_partitioned_graph(edges, off, part),
                  lambda: WCC(), None)

    edges, n = rmat_graph(150, avg_degree=5, seed=9)
    w = (np.random.RandomState(19).uniform(0.5, 8.0, size=len(edges))
         .astype(np.float32))
    part = hash_partition(n, 4, seed=1)
    out["widest"] = (build_partitioned_graph(edges, n, part, weights=w),
                     lambda: WidestPath(source=0), None)

    edges, n = rmat_graph(150, avg_degree=5, seed=15)
    part = bfs_partition(edges, n, 4, seed=2)
    w = random_walk_edge_weights(edges, n, "odds")
    out["random_walk"] = (build_partitioned_graph(edges, n, part, weights=w),
                          lambda: RandomWalk(source=0, mode="odds"), None)

    edges, n_left, n = bipartite_graph(30, 25, avg_degree=3, seed=11)
    part = hash_partition(n, 4, seed=4)
    g = build_partitioned_graph(edges, n, part)
    vdata = {"is_left": g.vertex_gid < n_left, "degree": g.out_degree}
    out["bipartite"] = (g, lambda: BipartiteMatching(seed=1), vdata)
    return out


def _digest(es) -> str:
    """sha256 over the final state channels + send/active, in a fixed
    order, shape/dtype included (so a silent transpose or cast changes the
    digest)."""
    h = hashlib.sha256()
    for name in sorted(es.state):
        a = np.asarray(es.state[name])
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    for name, a in (("send", es.send), ("active", es.active)):
        a = np.asarray(a)
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _snapshot(graph, prog, vdata, engine: str, use_ell: bool) -> dict:
    from repro.core import run_am, run_bsp, run_hybrid
    runners = {"bsp": run_bsp, "am": run_am, "hybrid": run_hybrid}
    es, iters = runners[engine](graph, prog, vdata=vdata, max_iters=500,
                                use_ell=use_ell)
    c = es.counters
    return {
        "digest": _digest(es),
        "iterations": iters,
        "counters": {
            "iterations": int(c.iterations),
            "pseudo_supersteps": np.asarray(c.pseudo_supersteps).tolist(),
            "net_messages": int(c.net_messages),
            "net_local_messages": int(c.net_local_messages),
            "mem_messages": int(c.mem_messages),
        },
    }


def _load_golden() -> dict:
    with open(GOLDEN_PATH) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def workloads():
    return _workloads()


@pytest.fixture(scope="module")
def golden():
    return _load_golden()


@pytest.mark.parametrize("delivery,use_ell", DELIVERY)
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("app", ["sssp", "pagerank", "wcc", "widest",
                                 "random_walk", "bipartite"])
def test_golden_parity(workloads, golden, app, engine, delivery, use_ell):
    graph, make_prog, vdata = workloads[app]
    got = _snapshot(graph, make_prog(), vdata, engine, use_ell)
    want = golden[app][engine][delivery]
    assert got["iterations"] == want["iterations"], (got, want)
    assert got["counters"] == want["counters"], (got, want)
    assert got["digest"] == want["digest"], \
        f"{app}/{engine}/{delivery}: final state drifted from the golden " \
        f"snapshot"


@pytest.mark.parametrize("app", ["sssp", "pagerank", "widest"])
def test_golden_parity_inside_a_profiler_session(workloads, golden, app,
                                                 tmp_path):
    """The phase scopes, kernel names and engine spans change metadata
    only: a hybrid run recorded by an open profiler session still lands
    on the golden state, iterations and counters."""
    import jax
    graph, make_prog, vdata = workloads[app]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path / "profile"),
                            profiler_options=opts):
        got = _snapshot(graph, make_prog(), vdata, "hybrid", True)
    assert got == golden[app]["hybrid"]["ell"]


def regen() -> None:
    golden = {}
    for app, (graph, make_prog, vdata) in _workloads().items():
        golden[app] = {}
        for engine in ENGINES:
            golden[app][engine] = {}
            for delivery, use_ell in DELIVERY:
                golden[app][engine][delivery] = _snapshot(
                    graph, make_prog(), vdata, engine, use_ell)
                print(f"{app}/{engine}/{delivery}: "
                      f"{golden[app][engine][delivery]['digest'][:12]}")
    with open(GOLDEN_PATH, "w") as f:
        json.dump(golden, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {GOLDEN_PATH}")


if __name__ == "__main__":
    import sys
    if "--regen" in sys.argv:
        regen()
    else:
        print(__doc__)
