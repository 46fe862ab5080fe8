"""The plain references against brute force on tiny graphs, and the
generators they are fed."""

import numpy as np
import pytest

from bench.gen.kronecker import kronecker_edges
from bench.gen.lattice import lattice_edges
from bench.gen.roots import sample_roots
from bench.reference import pagerank, sssp


def _floyd_warshall(edges, w, n):
    d = np.full((n, n), np.inf)
    np.fill_diagonal(d, 0.0)
    for (u, v), x in zip(edges, w.astype(np.float64)):
        d[u, v] = min(d[u, v], x)
    for k in range(n):
        d = np.minimum(d, d[:, k:k + 1] + d[k:k + 1, :])
    return d


@pytest.mark.parametrize("seed", [0, 2**31 + 3])
def test_dijkstra_matches_floyd_warshall(seed):
    rng = np.random.default_rng(seed)
    n = 12
    edges = rng.integers(0, n, (40, 2))
    edges = edges[edges[:, 0] != edges[:, 1]]
    w = rng.random(len(edges)).astype(np.float32)
    # a duplicate arc: the lighter one is the road a path takes
    edges = np.concatenate([edges, edges[:1]])
    w = np.concatenate([w, w[:1] / 2])
    want = _floyd_warshall(edges, w, n)
    got = sssp.distances(edges, w, n, [0, 5, 11])
    np.testing.assert_allclose(got, want[[0, 5, 11]], rtol=1e-12)


def test_sssp_compare_counts_reachability_and_relative_error():
    ref = np.array([0.0, 1.0, 2.0, np.inf, np.inf])
    got = np.array([0.0, 1.0, 2.001, 7.0, np.inf], np.float32)
    r = sssp.compare(got, ref)
    assert r["unreached"] == 1
    assert r["rel_err"] == pytest.approx(0.0005, rel=1e-3)


def test_pagerank_matches_a_dense_solve():
    rng = np.random.default_rng(4)
    n = 15
    edges = np.unique(rng.integers(0, n, (60, 2)), axis=0)
    edges = edges[edges[:, 0] != edges[:, 1]]
    deg = np.bincount(edges[:, 0], minlength=n).astype(np.float64)
    a = np.zeros((n, n))
    for u, v in edges:
        a[v, u] += 1.0 / deg[u]
    want = np.linalg.solve(np.eye(n) - 0.85 * a, np.full(n, 0.15))
    np.testing.assert_allclose(pagerank.ranks(edges, n), want, rtol=1e-9)


def test_pagerank_compare_is_two_sided():
    ref = np.array([1.0, 2.0, 0.5])
    assert pagerank.compare(np.array([1.0, 2.0, 0.5]), ref)["rel_err"] == 0
    assert pagerank.compare(np.array([1.01, 2.0, 0.5]), ref)["rel_err"] \
        == pytest.approx(0.01)
    assert pagerank.compare(np.array([1.0, 1.9, 0.5]), ref)["rel_err"] \
        == pytest.approx(0.05)


def test_kronecker_is_undirected_simple_and_seeded():
    e, w, n = kronecker_edges(8, 16, (0.57, 0.19, 0.19, 0.05), 2**31 + 9)
    assert n == 256
    assert np.all(e[:, 0] != e[:, 1])
    key = e[:, 0] * n + e[:, 1]
    assert len(np.unique(key)) == len(key)
    rev = dict(zip(e[:, 1] * n + e[:, 0], w))
    assert all(rev[k] == x for k, x in zip(key, w))
    assert np.all((w >= 0) & (w < 1))
    e2, w2, _ = kronecker_edges(8, 16, (0.57, 0.19, 0.19, 0.05), 2**31 + 9)
    assert np.array_equal(e, e2) and np.array_equal(w, w2)
    e3, _, _ = kronecker_edges(8, 16, (0.57, 0.19, 0.19, 0.05), 5)
    assert not np.array_equal(e, e3)


def test_lattice_is_four_neighbour_and_symmetric():
    e, w, n = lattice_edges(5, 7, 1.0, 10.0, 3)
    assert n == 35 and len(e) == 2 * (5 * 6 + 4 * 7)
    assert np.all(np.isin(np.abs(e[:, 0] - e[:, 1]), [1, 7]))
    half = len(e) // 2
    assert np.array_equal(e[:half], e[half:, ::-1])
    assert np.array_equal(w[:half], w[half:])
    assert np.all((w >= 1) & (w < 10))


def test_roots_come_from_the_largest_component():
    # a 6-cycle and a separate edge: roots only from the cycle
    e = np.array([[i, (i + 1) % 6] for i in range(6)] + [[6, 7]])
    e = np.concatenate([e, e[:, ::-1]])
    roots = sample_roots(e, 8, 4, 2**31 + 1)
    assert len(set(roots.tolist())) == 4 and np.all(roots < 6)
    assert np.array_equal(roots, sample_roots(e, 8, 4, 2**31 + 1))


def test_graph_seed_gives_every_run_one_graph_and_its_own_weights():
    import jax

    from bench import harness
    from bench.tests import tiny
    assert "graph_seed" in harness.load_workload(tiny.CELL).config[
        "generator"]
    wl = tiny.tiny_workload(tiny.CELL)
    cfg = {**wl.config, "generator": {**wl.config["generator"],
                                      "graph_seed": 1}}
    runs = []
    for seed in (2**31 + 3, 2**31 + 5):
        e, w, n = harness.generate(cfg, seed)
        g, _ = harness.build(cfg, e, w, n, seed,
                             harness.Placement(tuple(jax.devices()[:1])))
        leaves, tree = jax.tree.flatten(g)
        runs.append((e, w, tree, [(x.shape, x.dtype) for x in leaves]))
    (e1, w1, t1, s1), (e2, w2, t2, s2) = runs
    assert np.array_equal(e1, e2) and t1 == t2 and s1 == s2
    assert not np.array_equal(w1, w2)
    half = len(e1) // 2
    for w in (w1, w2):
        assert w.dtype == np.float32 and np.all((w >= 0) & (w < 1))
        assert np.array_equal(w[:half], w[half:])
    # without a graph_seed the run's seed draws structure and weights
    e, w, _ = harness.generate(wl.config, 2**31 + 3)
    ref = kronecker_edges(7, 16, (0.57, 0.19, 0.19, 0.05), 2**31 + 3)
    assert np.array_equal(e, ref[0]) and np.array_equal(w, ref[1])
