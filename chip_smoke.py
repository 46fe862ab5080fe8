"""Chip smoke test: the hybrid engine's main path, once, on one TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # run_hybrid over a 2x2 mesh

On one chip it builds a Graph500-style R-MAT graph (scale 20, edge factor
16, initiator 0.57/0.19/0.19) with seeded uniform weights and a hash
partition, then drives the entry points a user calls:

* ``run_hybrid`` SSSP (fused ``min_step`` local phase), checked against
  ``scipy.sparse.csgraph.dijkstra``;
* ``run_hybrid`` incremental PageRank (fused ``pr_step``), checked against a
  scipy power iteration of the same unnormalized 0.15-base dynamics;
* ``ServeEngine`` answering 32 SSSP point queries at lane width 16, each
  checked against Dijkstra from its source;
* the compiled hybrid step, which must hold a Mosaic kernel
  (``tpu_custom_call``).

``--four-chips`` runs only ``run_hybrid`` on the graph placed over a 2x2
mesh (its device loop one shard_map over the mesh) and compares it with
``run_hybrid`` on one device: fixed point, iteration count and every paper
counter bit-exact.

The last line of standard output is one JSON object naming the device.
Without a TPU the script exits non-zero before any phase runs; any phase
that fails makes it exit non-zero.  The compile cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache/``
beside this file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

SCALE = 20               # Graph500 scale: 2**20 vertices
EDGE_FACTOR = 16         # Graph500 edge factor: 16 * 2**20 edges generated
PARTITIONS = 8
BASE_SLICES = 16         # dense-base ELL bin width; hubs spill to wider bins
WEIGHT_RANGE = (1.0, 10.0)
PR_TOLERANCE = 1e-4
N_QUERIES = 32
LANE_WIDTH = 16
SSSP_RTOL = 1e-5         # float32 path sums against float64 Dijkstra


def build_graph(scale: int, edge_factor: int, seed: int, *,
                pagerank: bool = False, edge_blocks: int = 1,
                partitions: int = PARTITIONS):
    """Seeded R-MAT graph, uniform (or 1/out-degree PageRank) weights and a
    hash partition -> (PartitionedGraph, edges, weights, host seconds)."""
    from repro.core import build_partitioned_graph, hash_partition
    from repro.core.apps.pagerank import pagerank_edge_weights
    from repro.data.graphs import rmat_graph

    t0 = time.perf_counter()
    edges, n = rmat_graph(1 << scale, avg_degree=edge_factor, seed=seed)
    if pagerank:
        w = pagerank_edge_weights(edges, n)
    else:
        w = (np.random.default_rng(seed).uniform(*WEIGHT_RANGE, len(edges))
             .astype(np.float32))
    part = hash_partition(n, partitions, seed=seed)
    graph = build_partitioned_graph(edges, n, part, weights=w,
                                    ell_base_slices=BASE_SLICES,
                                    edge_blocks=edge_blocks)
    return graph, edges, w, time.perf_counter() - t0


def device_bytes(tree) -> int:
    """Bytes of every array leaf of a pytree (a PartitionedGraph)."""
    import jax
    return int(sum(leaf.nbytes for leaf in jax.tree.leaves(tree)))


def pick_sources(edges: np.ndarray, n: int, count: int, seed: int):
    """``count`` distinct seeded vertices with at least one out-edge."""
    has_out = np.unique(edges[:, 0])
    rng = np.random.default_rng(seed + 1)
    return rng.choice(has_out, size=min(count, len(has_out)),
                      replace=False).astype(np.int64)


def _csr(edges: np.ndarray, w: np.ndarray, n: int):
    from scipy.sparse import csr_matrix
    return csr_matrix((w.astype(np.float64), (edges[:, 0], edges[:, 1])),
                      shape=(n, n))


def dijkstra(edges: np.ndarray, w: np.ndarray, n: int, sources):
    """Reference distances (len(sources), n), +inf where unreachable."""
    from scipy.sparse.csgraph import dijkstra as _dijkstra
    return np.atleast_2d(_dijkstra(_csr(edges, w, n), directed=True,
                                   indices=np.asarray(sources)))


def check_distances(got: np.ndarray, ref: np.ndarray, what: str) -> dict:
    """Same reachable set; finite distances within float32 rounding."""
    reach_got, reach_ref = np.isfinite(got), np.isfinite(ref)
    if not np.array_equal(reach_got, reach_ref):
        raise AssertionError(
            f"{what}: reachable sets differ in "
            f"{int(np.sum(reach_got != reach_ref))} vertices")
    np.testing.assert_allclose(got[reach_ref], ref[reach_ref],
                               rtol=SSSP_RTOL, err_msg=what)
    err = np.abs(got[reach_ref] - ref[reach_ref]) / np.maximum(
        np.abs(ref[reach_ref]), 1e-30)
    return {"reached": int(reach_ref.sum()),
            "max_rel_err": float(err.max()) if err.size else 0.0}


def pagerank_reference(edges: np.ndarray, n: int, iters: int = 500):
    """Fixed point of rank = 0.15 + 0.85 * A^T (rank / out-degree), dangling
    mass not redistributed — Algorithm 5's dynamics — in float64."""
    deg = np.bincount(edges[:, 0], minlength=n).astype(np.float64)
    at = _csr(edges, 1.0 / deg[edges[:, 0]], n).T.tocsr()
    r = np.full(n, 0.15)
    for _ in range(iters):
        nxt = 0.15 + 0.85 * (at @ r)
        done = np.max(np.abs(nxt - r)) < 1e-12
        r = nxt
        if done:
            break
    return r


def check_pagerank(got: np.ndarray, ref: np.ndarray, tol: float) -> dict:
    """Algorithm 5 with drop threshold ``tol`` against the exact fixed
    point.  A vertex keeps, unpropagated, every combined delta <= tol it
    receives, so the program only withholds mass: ``got <= ref`` up to
    float32 rounding.  The withheld share of a vertex's rank stays below
    200 * tol (on R-MAT graphs of scales 10 to 14 it measured at most
    77 * tol, for tol 1e-4 and 1e-5 alike)."""
    over = got - ref * (1 + 1e-6) - 1e-6
    if np.any(over > 0):
        raise AssertionError(f"pagerank exceeds the exact fixed point at "
                             f"{int(np.sum(over > 0))} vertices")
    rel = (ref - got) / ref
    if rel.max() > 200 * tol:
        raise AssertionError(f"pagerank withholds {rel.max():.3g} of a "
                             f"rank, more than 200 * tol = {200 * tol:.3g}")
    return {"max_rel_err": float(rel.max()),
            "l1_rel_err": float(np.sum(ref - got) / np.sum(ref))}


def counters(es) -> dict:
    c = es.counters
    return {"iterations": int(c.iterations),
            "pseudo_supersteps": int(np.sum(np.asarray(c.pseudo_supersteps))),
            "net_messages": int(c.net_messages),
            "net_local_messages": int(c.net_local_messages),
            "mem_messages": int(c.mem_messages)}


def phase_sssp(graph, source: int):
    """``run_hybrid`` SSSP to its fixed point -> (record, distances)."""
    import jax
    from repro.core import run_hybrid
    from repro.core.apps import SSSP
    from repro.core.graph import unpack_vertex

    t0 = time.perf_counter()
    es, _ = run_hybrid(graph, SSSP(source=int(source)))
    jax.block_until_ready(es.state)
    wall = time.perf_counter() - t0
    return ({"source": int(source), "wall_s": wall, **counters(es)},
            unpack_vertex(graph, es.state["dist"]))


def phase_pagerank(graph, tol: float = PR_TOLERANCE):
    """``run_hybrid`` incremental PageRank -> (record, ranks)."""
    import jax
    from repro.core import run_hybrid
    from repro.core.apps import IncrementalPageRank
    from repro.core.graph import unpack_vertex

    t0 = time.perf_counter()
    es, _ = run_hybrid(graph, IncrementalPageRank(tolerance=tol))
    jax.block_until_ready(es.state)
    wall = time.perf_counter() - t0
    return ({"tolerance": tol, "wall_s": wall, **counters(es)},
            unpack_vertex(graph, es.state["rank"]).astype(np.float64))


def phase_serve(graph, sources, lane_width: int = LANE_WIDTH):
    """``ServeEngine`` SSSP point queries -> (record, (len(sources), V)
    distances in submission order)."""
    from repro.serve import ServeEngine

    eng = ServeEngine(graph, lane_widths=(lane_width,))
    queries = [eng.submit("sssp", source=int(s)) for s in sources]
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    if len(done) != len(queries) or not all(q.done for q in queries):
        raise AssertionError(f"served {len(done)} of {len(queries)}")
    return ({"queries": len(done), "lane_width": lane_width,
             "batches": -(-len(done) // lane_width), "wall_s": wall},
            np.stack([np.asarray(q.result) for q in queries]))


def check_serve(got: np.ndarray, ref: np.ndarray, sources) -> dict:
    errs = [check_distances(g, r, f"query from {s}")
            for g, r, s in zip(got, ref, sources)]
    return {"max_rel_err": max(e["max_rel_err"] for e in errs)}


def phase_kernel_path(graph, source: int) -> dict:
    """The hybrid step as compiled for this device: which fused kernel the
    local phase names, and whether a Mosaic kernel is in the program."""
    import jax
    from repro.core.apps import SSSP
    from repro.exec.local_phase import fused_local_kernel
    from repro.exec.policy import hybrid_policy

    prog = SSSP(source=int(source))
    policy = hybrid_policy()            # run_hybrid's defaults
    fused = fused_local_kernel(graph, prog, True, 100_000)
    es = policy.init(graph, prog, None)
    text = (jax.jit(lambda g, e: policy.step(g, prog, e, None))
            .lower(graph, es).compile().as_text())
    return {"fused_kernel": fused,
            "tpu_custom_calls": text.count("tpu_custom_call")}


def phase_four_chips(graph, source: int, mesh) -> dict:
    """SSSP through ``run_hybrid`` on the graph placed over ``mesh`` (its
    device loop runs one shard_map over the mesh) against ``run_hybrid``
    on one device: state, iterations and every counter bit-exact, with the
    graph and state spread over every device of the mesh."""
    import jax
    from jax.sharding import NamedSharding
    from repro.core import run_hybrid
    from repro.core.apps import SSSP
    from repro.core.distributed import shard0_specs

    prog = SSSP(source=int(source))
    t0 = time.perf_counter()
    es_ref, iters_ref = run_hybrid(graph, prog)
    jax.block_until_ready(es_ref.state)
    wall_ref = time.perf_counter() - t0

    graph_d = jax.device_put(graph, jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        shard0_specs(graph, tuple(mesh.axis_names))))
    t0 = time.perf_counter()
    es_d, iters = run_hybrid(graph_d, prog)
    jax.block_until_ready(es_d.state)
    wall = time.perf_counter() - t0

    spread = {len(leaf.sharding.device_set)
              for leaf in jax.tree.leaves((graph_d, es_d.state))}
    if spread != {mesh.size}:
        raise AssertionError(f"arrays placed on {sorted(spread)} devices, "
                             f"not all {mesh.size}")
    got = np.asarray(jax.device_get(es_d.state["dist"]))
    np.testing.assert_array_equal(got, np.asarray(es_ref.state["dist"]))
    if iters != iters_ref:
        raise AssertionError(f"iterations {iters} != {iters_ref}")
    c_dist, c_ref = counters(es_d), counters(es_ref)
    if c_dist != c_ref:
        raise AssertionError(f"counters {c_dist} != {c_ref}")
    np.testing.assert_array_equal(
        np.asarray(es_d.counters.pseudo_supersteps),
        np.asarray(es_ref.counters.pseudo_supersteps))
    return {"devices": mesh.size, "iterations": iters,
            "wall_s": wall, "one_device_wall_s": wall_ref, **c_dist}


def _build_pagerank(seed: int):
    graph, edges, _, build_s = build_graph(SCALE, EDGE_FACTOR, seed,
                                           pagerank=True)
    return graph, pagerank_reference(edges, graph.n_vertices), build_s


def _smoke_one_chip(graph, edges, w, source: int, seed: int) -> None:
    """The one-chip phases.  The host-side work — the scipy references and
    the PageRank graph's build — runs in worker threads while the device
    runs the phases before it."""
    from concurrent.futures import ThreadPoolExecutor

    queries = pick_sources(edges, graph.n_vertices, N_QUERIES, seed)
    with ThreadPoolExecutor(max_workers=2) as pool:
        dij = pool.submit(dijkstra, edges, w, graph.n_vertices,
                          [source, *queries])
        pr = pool.submit(_build_pagerank, seed)

        rec, got = phase_sssp(graph, source)
        ref = dij.result()
        _report("sssp", {**rec, **check_distances(got, ref[0], "sssp")})

        graph_pr, ref_pr, build_s = pr.result()
        rec, got = phase_pagerank(graph_pr)
        _report("pagerank", {"host_build_s": build_s,
                             "device_bytes": device_bytes(graph_pr), **rec,
                             **check_pagerank(got, ref_pr, PR_TOLERANCE)})
        del graph_pr

        rec, got = phase_serve(graph, queries, LANE_WIDTH)
        _report("serve", {**rec, **check_serve(got, ref[1:], queries)})

    kern = phase_kernel_path(graph, source)
    _report("kernel_path", kern)
    if kern["fused_kernel"] != "min_step" or not kern["tpu_custom_calls"]:
        raise AssertionError(f"kernel path not taken: {kern}")


def _use_compile_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))


def _report(name: str, rec: dict) -> None:
    print(f"[{name}] " + json.dumps(rec), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only run_hybrid over a 2x2 mesh and its "
                         "one-device comparison")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"no TPU found: JAX sees {len(devices)} "
              f"{devices[0].platform} device(s), this smoke needs {need} "
              f"TPU chip(s)", file=sys.stderr)
        return 2
    _use_compile_cache()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    _report("device", dev)

    graph, edges, w, build_s = build_graph(
        SCALE, EDGE_FACTOR, args.seed, edge_blocks=need)
    _report("graph", {"vertices": graph.n_vertices, "edges": len(edges),
                      "edges_generated": EDGE_FACTOR << SCALE,
                      "partitions": graph.n_partitions,
                      "local_bins": len(graph.local_ell),
                      "remote_bins": len(graph.remote_ell),
                      "device_bytes": device_bytes(graph),
                      "host_build_s": build_s})
    source = int(pick_sources(edges, graph.n_vertices, 1, args.seed)[0])

    if args.four_chips:
        from repro.launch.mesh import make_host_mesh
        _report("four_chips", phase_four_chips(graph, source,
                                               make_host_mesh(2, 2)))
        _report("memory", {f"device_{d.id}_peak_bytes":
                           d.memory_stats()["peak_bytes_in_use"]
                           for d in devices[:need]})
    else:
        _smoke_one_chip(graph, edges, w, source, args.seed)
        _report("memory", {"peak_bytes_in_use":
                           devices[0].memory_stats()["peak_bytes_in_use"]})
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
