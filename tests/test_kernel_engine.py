"""Kernel-backed delivery parity: `use_ell=True` routes local-phase message
combination through the Pallas ELL kernels (and the whole PageRank local
phase through the fused `pr_step` kernel); every app on every engine must
reach the same fixed point as the dense gather/segment path — bit-for-bit
for min/lexmin combiners, to float-reassociation tolerance for 'sum' — with
identical iteration counts and paper counters."""

import importlib
import re

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (bfs_partition, build_partitioned_graph,
                        hash_partition, run_am, run_bsp, run_hybrid)
from repro.core.apps import (SSSP, WCC, BipartiteMatching,
                             IncrementalPageRank, MultiSourceMonotone,
                             RandomWalk, WidestPath)
from repro.core.apps.pagerank import pagerank_edge_weights
from repro.core.apps.random_walk import random_walk_edge_weights
from repro.core.runtime import ell_channels
from repro.data.graphs import bipartite_graph, grid_graph, rmat_graph, symmetrize

from test_delivery_parity import assert_remote_delivery_matches as \
    _assert_remote_delivery_matches

# the module: ``repro.exec.local_phase`` names the function it exports
local_phase_mod = importlib.import_module("repro.exec.local_phase")

RUNNERS = {"bsp": run_bsp, "am": run_am, "hybrid": run_hybrid}
ENGINES = ["bsp", "am", "hybrid"]


def unpack(graph, es, field):
    gid = np.asarray(graph.vertex_gid).ravel()
    val = np.asarray(es.state[field]).reshape(gid.shape[0], -1).squeeze(-1)
    mask = gid >= 0
    out = np.zeros(graph.n_vertices, dtype=val.dtype)
    out[gid[mask]] = val[mask]
    return out


def assert_counters_equal(a, b):
    for f in ("iterations", "net_messages", "net_local_messages",
              "mem_messages"):
        assert int(getattr(a.counters, f)) == int(getattr(b.counters, f)), f
    np.testing.assert_array_equal(np.asarray(a.counters.pseudo_supersteps),
                                  np.asarray(b.counters.pseudo_supersteps))


def run_pair(engine, graph, make_prog, vdata=None, **kw):
    es_d, it_d = RUNNERS[engine](graph, make_prog(), vdata=vdata,
                                 use_ell=False, **kw)
    es_k, it_k = RUNNERS[engine](graph, make_prog(), vdata=vdata,
                                 use_ell=True, **kw)
    assert it_d == it_k, (it_d, it_k)
    return es_d, es_k


@pytest.fixture(scope="module")
def road():
    edges, w, n = grid_graph(6, 60, seed=3)
    part = bfs_partition(edges, n, 6, seed=1)
    return build_partitioned_graph(edges, n, part, weights=w), n


@pytest.fixture(scope="module")
def web():
    edges, n = rmat_graph(300, avg_degree=6, seed=7)
    part = hash_partition(n, 6, seed=2)
    w = pagerank_edge_weights(edges, n)
    return build_partitioned_graph(edges, n, part, weights=w), n


def test_graph_carries_ell_layout(road):
    graph, _ = road
    assert graph.has_ell and graph.has_remote_ell and graph.kl > 0
    base = graph.local_ell[0]
    assert base.dense and base.lo == 0 and base.stride == graph.vp
    ppb = graph.n_partitions // graph.n_blocks
    assert base.idx.shape == (graph.n_blocks, ppb * graph.vp, base.kb)
    assert base.flat_idx.shape == (graph.n_partitions * graph.vp, base.kb)
    # ELL slots reproduce exactly the local/remote splits of the dense arrays
    n_local = int(jnp.sum(jnp.logical_and(graph.edge_mask, graph.edge_local)))
    n_remote = int(jnp.sum(jnp.logical_and(graph.edge_mask,
                                           jnp.logical_not(graph.edge_local))))
    assert sum(int(jnp.sum(s.msk)) for s in graph.local_ell) == n_local
    assert sum(int(jnp.sum(s.msk)) for s in graph.remote_ell) == n_remote
    # remote sources are halo-encoded past the local slot space
    rbase = graph.remote_ell[0]
    assert rbase.stride == graph.vp + graph.hp
    assert bool(jnp.all(jnp.where(rbase.msk, rbase.idx >= graph.vp, True)))


def _skewed_edges():
    """An R-MAT graph plus 2,000 arcs into five hubs, whose rows spill past
    the local ELL base bin of width 16."""
    edges, n = rmat_graph(600, avg_degree=10, seed=11)
    rng = np.random.RandomState(5)
    hubs = np.stack([rng.randint(0, n, size=2000),
                     rng.randint(0, 5, size=2000)], axis=1)
    edges = np.unique(np.concatenate([edges, hubs]), axis=0)
    return edges[edges[:, 0] != edges[:, 1]], n


def test_skewed_graph_keeps_fast_path_with_bins():
    """Power-law in-degree no longer bails out to dense: hub rows spill into
    extra ELL bins and every engine still reaches the dense fixed point with
    identical counters."""
    edges, n = _skewed_edges()
    part = hash_partition(n, 4, seed=0)
    w = pagerank_edge_weights(edges, n)
    graph = build_partitioned_graph(edges, n, part, weights=w,
                                    ell_base_slices=16)
    assert len(graph.local_ell) >= 2, "skew should produce spill bins"
    assert not graph.local_ell[1].dense
    for engine in ENGINES:
        es_d, es_k = run_pair(engine, graph,
                              lambda: IncrementalPageRank(tolerance=1e-4))
        np.testing.assert_allclose(unpack(graph, es_d, "rank"),
                                   unpack(graph, es_k, "rank"),
                                   rtol=1e-5, atol=1e-6)
        assert_counters_equal(es_d, es_k)


def test_semiring_channels_are_eligible(road):
    graph, _ = road
    prog = SSSP(source=0)
    out = {"dist": jnp.zeros((graph.n_partitions, graph.vp))}
    send = jnp.zeros((graph.n_partitions, graph.vp), bool)
    assert [c.name for c in ell_channels(graph, prog, out, send)] == ["dist"]
    # bipartite matching declares no semirings -> everything falls back
    assert ell_channels(graph, BipartiteMatching(), {}, send) == []


@pytest.mark.parametrize("engine", ENGINES)
def test_sssp_parity(road, engine):
    graph, _ = road
    es_d, es_k = run_pair(engine, graph, lambda: SSSP(source=0))
    np.testing.assert_array_equal(unpack(graph, es_d, "dist"),
                                  unpack(graph, es_k, "dist"))
    assert_counters_equal(es_d, es_k)


@pytest.mark.parametrize("engine", ENGINES)
def test_wcc_parity(engine):
    rng = np.random.RandomState(0)
    edges = symmetrize(rng.randint(0, 90, size=(400, 2)))
    edges = edges[edges[:, 0] != edges[:, 1]]
    part = hash_partition(90, 5, seed=3)
    graph = build_partitioned_graph(edges, 90, part)
    es_d, es_k = run_pair(engine, graph, WCC)
    np.testing.assert_array_equal(unpack(graph, es_d, "label"),
                                  unpack(graph, es_k, "label"))
    assert_counters_equal(es_d, es_k)


@pytest.mark.parametrize("engine", ENGINES)
def test_pagerank_parity(web, engine):
    """'sum' channels reassociate float adds (ELL reduces along slices,
    segment-sum along edges) so ranks match to tolerance; the integer
    counters and iteration counts must still agree exactly."""
    graph, _ = web
    es_d, es_k = run_pair(engine, graph,
                          lambda: IncrementalPageRank(tolerance=1e-4))
    np.testing.assert_allclose(unpack(graph, es_d, "rank"),
                               unpack(graph, es_k, "rank"),
                               rtol=1e-5, atol=1e-6)
    assert_counters_equal(es_d, es_k)


@pytest.mark.parametrize("engine", ENGINES)
def test_bipartite_matching_fallback_parity(engine):
    """No BM channel is semiring-expressible (lexmin handshake, targeted
    grants) — use_ell must transparently keep the dense path bit-for-bit."""
    edges, n_left, n = bipartite_graph(50, 40, avg_degree=3, seed=11)
    part = hash_partition(n, 5, seed=4)
    graph = build_partitioned_graph(edges, n, part)
    vdata = {"is_left": graph.vertex_gid < n_left, "degree": graph.out_degree}
    es_d, es_k = run_pair(engine, graph, lambda: BipartiteMatching(seed=1),
                          vdata=vdata, max_iters=500)
    np.testing.assert_array_equal(unpack(graph, es_d, "matched"),
                                  unpack(graph, es_k, "matched"))
    assert_counters_equal(es_d, es_k)


@pytest.mark.parametrize("engine", ENGINES)
def test_widest_path_parity(road, engine):
    """max_min delivery (and the generalized fused local phase on hybrid)
    matches the dense path bit-for-bit — max/min never reassociates."""
    graph, _ = road
    es_d, es_k = run_pair(engine, graph, lambda: WidestPath(source=0))
    np.testing.assert_array_equal(unpack(graph, es_d, "cap"),
                                  unpack(graph, es_k, "cap"))
    assert_counters_equal(es_d, es_k)


@pytest.mark.parametrize("mode", ["odds", "logprob"])
@pytest.mark.parametrize("engine", ENGINES)
def test_random_walk_parity(engine, mode):
    """min_mul (odds) and max_add (log-prob) deliveries match the dense
    path bit-for-bit: ⊗ is evaluated identically on both paths and ⊕ is a
    selection."""
    edges, n = rmat_graph(220, avg_degree=5, seed=3)
    w = random_walk_edge_weights(edges, n, mode)
    part = hash_partition(n, 5, seed=1)
    graph = build_partitioned_graph(edges, n, part, weights=w)
    es_d, es_k = run_pair(engine, graph,
                          lambda: RandomWalk(source=0, mode=mode))
    np.testing.assert_array_equal(unpack(graph, es_d, "mass"),
                                  unpack(graph, es_k, "mass"))
    assert_counters_equal(es_d, es_k)


def test_new_apps_fuse_through_min_step(road):
    """The generalized fused gate engages for every monotone-semiring app
    and stays off when the channel combiner doesn't match the semiring ⊕."""
    from repro.core.engine_hybrid import _fused_local_kernel
    from repro.core.vertex_program import Channel
    graph, _ = road
    for prog in (WidestPath(source=0), RandomWalk(source=0, mode="odds"),
                 RandomWalk(source=0, mode="logprob")):
        assert _fused_local_kernel(graph, prog, use_ell=True,
                                   max_local_steps=10) == "min_step"
        assert _fused_local_kernel(graph, prog, use_ell=False,
                                   max_local_steps=10) is None
    # mismatched combiner/⊕ (min channel over a max semiring) must not fuse
    bad = WidestPath(source=0)
    bad.channels = (Channel("cap", "min", ((jnp.float32, -jnp.inf),),
                            semiring="max_min"),)
    assert _fused_local_kernel(graph, bad, use_ell=True,
                               max_local_steps=10) is None


def test_widest_path_fused_cutoff_parity(road):
    """max_local_steps cutoff rollback holds for the generalized (max, min)
    fusion exactly as for SSSP's (min, +)."""
    graph, _ = road
    for steps in (1, 3):
        es_d, it_d = run_hybrid(graph, WidestPath(source=0),
                                max_local_steps=steps, use_ell=False)
        es_k, it_k = run_hybrid(graph, WidestPath(source=0),
                                max_local_steps=steps, use_ell=True)
        assert it_d == it_k, (steps, it_d, it_k)
        np.testing.assert_array_equal(unpack(graph, es_d, "cap"),
                                      unpack(graph, es_k, "cap"))
        assert_counters_equal(es_d, es_k)


def test_hybrid_fused_pr_uses_kernel_and_matches(web):
    """The fused path is actually engaged for PageRank on the hybrid engine
    (fused_kernel declared + ELL present) and collect_metrics=False leaves
    the message counters untouched while converging to the same ranks."""
    from repro.core.engine_hybrid import _fused_local_kernel
    graph, _ = web
    prog = IncrementalPageRank(tolerance=1e-4)
    assert _fused_local_kernel(graph, prog, use_ell=True,
                               max_local_steps=10) == "pr_step"
    assert _fused_local_kernel(graph, prog, use_ell=False,
                               max_local_steps=10) is None

    es_ref, it_ref = run_hybrid(graph, IncrementalPageRank(tolerance=1e-4))
    es_perf, it_perf = run_hybrid(graph, IncrementalPageRank(tolerance=1e-4),
                                  use_ell=True, collect_metrics=False)
    assert it_ref == it_perf
    np.testing.assert_allclose(unpack(graph, es_ref, "rank"),
                               unpack(graph, es_perf, "rank"),
                               rtol=1e-5, atol=1e-6)
    assert int(es_perf.counters.net_messages) == 0
    assert int(es_perf.counters.mem_messages) == 0
    assert int(es_ref.counters.mem_messages) > 0


def test_remote_ell_matches_dense_bitexact(road):
    graph, _ = road
    rng = np.random.RandomState(21)
    p, vp = graph.n_partitions, graph.vp
    dist = jnp.asarray(np.where(rng.uniform(size=(p, vp)) < 0.8,
                                rng.uniform(0, 50, size=(p, vp)),
                                np.inf).astype(np.float32))
    _assert_remote_delivery_matches(graph, SSSP(source=0), {"dist": dist}, 3)
    labels = jnp.asarray(rng.randint(0, graph.n_vertices,
                                     size=(p, vp)).astype(np.int32))
    _assert_remote_delivery_matches(graph, WCC(), {"label": labels}, 4)


def test_remote_ell_skewed_bins_engage_and_match():
    """Deterministic hub graph: the remote layout must actually spill into
    extra bins (the case the old ``ell_max_slices`` bailout regressed to
    dense) and still match the dense path bit-exactly."""
    rng = np.random.RandomState(13)
    n = 120
    edges = np.stack([rng.randint(0, n, size=900),
                      rng.randint(0, 4, size=900)], axis=1)
    edges = np.concatenate([edges, rng.randint(0, n, size=(300, 2))])
    edges = np.unique(edges, axis=0)
    edges = edges[edges[:, 0] != edges[:, 1]]
    part = hash_partition(n, 4, seed=2)
    graph = build_partitioned_graph(edges, n, part, ell_base_slices=8)
    assert len(graph.remote_ell) >= 2
    assert not graph.remote_ell[1].dense
    p, vp = graph.n_partitions, graph.vp
    dist = jnp.asarray(rng.uniform(0, 50, size=(p, vp)).astype(np.float32))
    _assert_remote_delivery_matches(graph, SSSP(source=0), {"dist": dist}, 17)


def test_no_ell_layout_falls_back(road):
    """A graph built without the ELL layout keeps use_ell runs on the dense
    path (kl == 0 -> no eligible channels), same results."""
    edges, w, n = grid_graph(4, 30, seed=5)
    part = bfs_partition(edges, n, 4, seed=1)
    g = build_partitioned_graph(edges, n, part, weights=w, build_ell=False)
    assert not g.has_ell
    es_d, it_d = run_hybrid(g, SSSP(source=0))
    es_k, it_k = run_hybrid(g, SSSP(source=0), use_ell=True)
    assert it_d == it_k
    np.testing.assert_array_equal(unpack(g, es_d, "dist"),
                                  unpack(g, es_k, "dist"))


def test_device_loop_matches_host_loop(road):
    graph, _ = road
    es_h, it_h = run_hybrid(graph, SSSP(source=0), device_loop=False)
    es_d, it_d = run_hybrid(graph, SSSP(source=0), device_loop=True)
    assert it_h == it_d
    np.testing.assert_array_equal(np.asarray(es_h.state["dist"]),
                                  np.asarray(es_d.state["dist"]))
    assert_counters_equal(es_h, es_d)


def test_fused_pr_cutoff_parity(web):
    """A max_local_steps cutoff exits the local phase with the final
    delivery still pending; the fused kernel has already applied it, so the
    engine must roll the apply back — otherwise the next iteration's apply
    double-counts the deltas and ranks diverge from the dense path."""
    graph, _ = web
    for steps in (1, 3):
        es_d, it_d = run_hybrid(graph, IncrementalPageRank(tolerance=1e-4),
                                max_local_steps=steps)
        es_k, it_k = run_hybrid(graph, IncrementalPageRank(tolerance=1e-4),
                                max_local_steps=steps, use_ell=True)
        assert it_d == it_k, (steps, it_d, it_k)
        np.testing.assert_allclose(unpack(graph, es_d, "rank"),
                                   unpack(graph, es_k, "rank"),
                                   rtol=1e-5, atol=1e-6)
        assert_counters_equal(es_d, es_k)


def test_int_semiring_f32_exact_judged_per_bin(road):
    """Integer payloads (WCC labels) ride the kernel as float32, judged per
    ELL degree bin against the largest source gid feeding the bin: at the
    2**24 boundary the bin is still exact (2**24 is representable), one past
    it the channel must fall back to dense — in both delivery directions."""
    import dataclasses
    graph, _ = road
    out = {"label": jnp.zeros((graph.n_partitions, graph.vp), jnp.int32)}
    send = jnp.zeros((graph.n_partitions, graph.vp), bool)
    prog = WCC()
    for edges in ("local", "remote"):
        assert [c.name for c in
                ell_channels(graph, prog, out, send, edges)] == ["label"]

    def rebound(g, side, bound):
        slices = tuple(dataclasses.replace(s, payload_bound=bound)
                       for s in getattr(g, side))
        return dataclasses.replace(g, **{side: slices})

    for side, edges in (("local_ell", "local"), ("remote_ell", "remote")):
        at_edge = rebound(graph, side, 1 << 24)
        past = rebound(graph, side, (1 << 24) + 1)
        assert [c.name for c in
                ell_channels(at_edge, prog, out, send, edges)] == ["label"]
        assert ell_channels(past, prog, out, send, edges) == []
        # float payloads (SSSP distances) are never bound-limited
        assert [c.name for c in
                ell_channels(past, SSSP(source=0),
                             {"dist": out["label"].astype(jnp.float32)},
                             send, edges)] == ["dist"]
    # a poisoned *local* bin must not leak into remote eligibility
    poisoned_local = rebound(graph, "local_ell", (1 << 24) + 1)
    assert [c.name for c in
            ell_channels(poisoned_local, prog, out, send, "remote")] \
        == ["label"]


def test_fused_min_gate_falls_back_past_f32_exact(road):
    """The fused min_step loop keeps the whole int state in float32, so its
    gate needs every vertex id representable — stricter than the per-bin
    message judgment."""
    import dataclasses
    from repro.core.engine_hybrid import _fused_local_kernel
    graph, _ = road
    assert _fused_local_kernel(graph, WCC(), use_ell=True,
                               max_local_steps=10) == "min_step"
    assert _fused_local_kernel(graph, SSSP(source=0), use_ell=True,
                               max_local_steps=10) == "min_step"
    big = dataclasses.replace(graph, n_vertices=(1 << 24) + 2)
    assert _fused_local_kernel(big, WCC(), use_ell=True,
                               max_local_steps=10) is None
    # float states (SSSP) stay fused at any graph size
    assert _fused_local_kernel(big, SSSP(source=0), use_ell=True,
                               max_local_steps=10) == "min_step"


def test_hybrid_fused_min_uses_kernel_and_matches(road):
    """The fused min_step path engages for SSSP on the hybrid engine and
    collect_metrics=False leaves the message counters untouched while
    reaching the identical fixed point."""
    graph, _ = road
    es_ref, it_ref = run_hybrid(graph, SSSP(source=0), use_ell=False)
    es_perf, it_perf = run_hybrid(graph, SSSP(source=0),
                                  use_ell=True, collect_metrics=False)
    assert it_ref == it_perf
    np.testing.assert_array_equal(unpack(graph, es_ref, "dist"),
                                  unpack(graph, es_perf, "dist"))
    assert int(es_perf.counters.net_messages) == 0
    assert int(es_perf.counters.mem_messages) == 0
    assert int(es_ref.counters.mem_messages) > 0


def test_fused_min_cutoff_parity(road):
    """A max_local_steps cutoff exits the fused min local phase with the
    final delivery still pending; the kernel has already applied it, so the
    engine must roll the apply back — distances and counters must match the
    dense path bit-for-bit at every cutoff."""
    graph, _ = road
    for steps in (1, 2, 4):
        es_d, it_d = run_hybrid(graph, SSSP(source=0),
                                max_local_steps=steps, use_ell=False)
        es_k, it_k = run_hybrid(graph, SSSP(source=0),
                                max_local_steps=steps, use_ell=True)
        assert it_d == it_k, (steps, it_d, it_k)
        np.testing.assert_array_equal(unpack(graph, es_d, "dist"),
                                      unpack(graph, es_k, "dist"))
        assert_counters_equal(es_d, es_k)


def local_ell_slot_degrees(graph):
    """(P, Vp) count of the valid local-ELL slots, over every bin, whose
    source is each vertex."""
    size = graph.n_partitions * graph.vp
    deg = sum(np.bincount(np.asarray(s.flat_idx)[np.asarray(s.msk).reshape(
        -1, s.kb)], minlength=size) for s in graph.local_ell)
    return deg.reshape(graph.n_partitions, graph.vp)


@pytest.mark.parametrize("source", ["built", "ghp"])
def test_local_out_degree_counts_local_ell_slots(tmp_path, source):
    """The build-time slot degrees the fused local phases count messages
    with: in memory and through the out-of-core ``.ghp`` loader alike."""
    from repro.io import build_partitioned_graph_from_path, save_graph

    edges, n = _skewed_edges()
    part = hash_partition(n, 4, seed=0)
    if source == "built":
        graph = build_partitioned_graph(edges, n, part, ell_base_slices=16)
    else:
        path = str(tmp_path / "g.ghp")
        save_graph(path, edges, n, part)
        graph = build_partitioned_graph_from_path(path, chunk_edges=512,
                                                  ell_base_slices=16)
    assert len(graph.local_ell) > 1
    deg = np.asarray(graph.local_out_degree)
    assert deg.dtype == np.int32
    np.testing.assert_array_equal(deg, local_ell_slot_degrees(graph))
    local = part[edges[:, 0]] == part[edges[:, 1]]
    assert deg.sum() == local.sum() > 0


# (weights, program factory, vdata, fused kernel) of each fused local phase
_FUSED = {
    "sssp": ("uniform", lambda: SSSP(source=0), None, "min_step"),
    "wcc": (None, WCC, None, "min_step"),
    "widest_path": ("uniform", lambda: WidestPath(source=0), None,
                    "min_step"),
    "random_walk_min_mul": ("odds", lambda: RandomWalk(source=0, mode="odds"),
                            None, "min_step"),
    "pagerank": ("pagerank", lambda: IncrementalPageRank(tolerance=1e-4),
                 None, "pr_step"),
    "sssp_3_lanes": ("uniform",
                     lambda: MultiSourceMonotone(lanes=3, semiring="min_add"),
                     {"sources": jnp.asarray([0, 1, 2], jnp.int32)},
                     "min_step"),
}


def _skewed_weights(kind, edges, n):
    if kind == "uniform":
        return np.random.RandomState(2).uniform(
            0.1, 5.0, len(edges)).astype(np.float32)
    if kind == "pagerank":
        return pagerank_edge_weights(edges, n)
    return None if kind is None else random_walk_edge_weights(edges, n, kind)


def _outcome(es, lanes: bool):
    """(state, pending payload, pending flags) of a single-channel run.  A
    K-lane payload is read as the state it ⊕-combines into: the generic
    path's inbox holds a lane's non-improving value where the fused
    kernel, gating each lane, holds the identity."""
    (name, ((payload,), has)), = es.pending.items()
    state = es.state[next(iter(es.state))]
    if lanes:
        payload = jnp.minimum(state, payload)      # the min_add lanes' ⊕
    return np.asarray(state), np.asarray(payload), np.asarray(has)


@pytest.mark.parametrize("case", sorted(_FUSED))
def test_fused_local_phase_counts_equal_generic_path(monkeypatch, case):
    """With the counters on, each fused local phase gives the state, the
    pending messages and every counter of the generic local phase on the
    same ELL layout: to its fixed point, and cut mid-run with messages
    still pending.  The graph spills past the base bin, so the slot
    degrees have to count every bin.  Bit for bit, but PageRank's 'sum'
    values, which the two paths add in different orders."""
    kind, make, vdata, kern = _FUSED[case]
    edges, n = _skewed_edges()
    graph = build_partitioned_graph(edges, n, hash_partition(n, 4, seed=0),
                                    weights=_skewed_weights(kind, edges, n),
                                    ell_base_slices=16)
    assert len(graph.local_ell) > 1
    assert local_phase_mod.fused_local_kernel(graph, make(), True,
                                              100_000) == kern

    def run(generic, **kw):
        with monkeypatch.context() as m:
            if generic:
                m.setattr(local_phase_mod, "fused_local_kernel",
                          lambda *a: None)
            return run_hybrid(graph, make(), vdata=vdata, **kw)

    lanes = vdata is not None
    for kw in ({}, {"max_iters": 2, "max_local_steps": 1}):
        (es_f, it_f), (es_g, it_g) = run(False, **kw), run(True, **kw)
        assert it_f == it_g, kw
        assert_counters_equal(es_f, es_g)
        assert int(es_f.counters.mem_messages) > 0
        (x_f, m_f, has_f), (x_g, m_g, has_g) = (_outcome(es_f, lanes),
                                                _outcome(es_g, lanes))
        np.testing.assert_array_equal(has_f, has_g, err_msg=str(kw))
        if kind == "pagerank":
            np.testing.assert_allclose(x_f, x_g, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(m_f, m_g, rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(x_f, x_g, err_msg=str(kw))
            np.testing.assert_array_equal(m_f, m_g, err_msg=str(kw))
    assert bool(has_f.any()), "the cut run should leave messages pending"


def test_fused_local_phase_loop_reads_no_send_flag_slot():
    """The Graph500 cell's device loop at scale 10, counters on: inside the
    local phase's loop body the only gathers are the values' float32
    gathers, one per local ELL bin, and nothing boolean is gathered or
    scattered.  The fused local phase counts its messages from the slot
    degrees, not from a gather of the send flags over every slot."""
    from bench.gen.kronecker import kronecker_edges
    from repro.exec import driver
    from repro.exec.policy import hybrid_policy

    edges, w, n = kronecker_edges(10, 16, [0.57, 0.19, 0.19, 0.05], 1)
    graph = build_partitioned_graph(edges, n, hash_partition(n, 8, seed=3),
                                    weights=w, ell_base_slices=16)
    assert len(graph.local_ell) > 1
    prog = MultiSourceMonotone(lanes=1, semiring="min_add")
    loop, _ = driver.device_loop_jit(prog, hybrid_policy(), 100_000, True)
    hlo = loop.lower(graph, {"sources": jnp.asarray([0], jnp.int32)},
                     None).compile().as_text()
    ops = {"gather": [], "scatter": []}
    for line in hlo.splitlines():
        op = re.search(r"= (\w+)\[[^\]]*\]\S* (gather|scatter)\(", line)
        if op and "local_phase/while/body/" in line:
            ops[op.group(2)].append(op.group(1))
    assert ops["gather"] == ["f32"] * len(graph.local_ell), ops
    assert "pred" not in ops["scatter"], ops
