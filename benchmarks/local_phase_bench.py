"""Engine-level A/B benchmark of the GraphHP delivery hot loops.

The paper's entire speedup comes from iterating the local phase a lot
(Algorithm 2), so the metric that matters most is the cost of ONE
pseudo-superstep (apply_phase -> deliver(local)); the once-per-iteration
remote delivery (exchange -> deliver(remote) feeding the global phase) is
the second hot path.  Implementations timed per workload:

  dense        the seed path: gather over every padded edge +
               combine_segments, per-channel segment-max message accounting
               inside the loop,
  ell          kernel-backed delivery: semiring channels dispatch to the
               Pallas `ell_spmv` sliced-ELL kernels, counters hoisted out
               (collect_metrics=False),
  fused        the whole pseudo-superstep through the fused `pr_step`
               (PageRank) / `min_step` (SSSP) kernel — deliver + apply in
               one VMEM-resident pass,
  remote_*     deliver(edges='remote') over the halo-fed frontier, dense
               vs. the halo-encoded remote-ELL kernel path.

The pagerank_skew workload adds hub destinations so the sliced-ELL row
binning engages (2+ degree bins) — the regime that used to bail out to
dense past ``ell_max_slices``.

The ``dist_phase`` table (``bench_dist_phase``) A/Bs the same question one
level up: a full `make_dist_hybrid_step` global iteration (exchange ->
global phase -> local convergence loop) under a fake multi-device mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``), dense seed path
vs the now-default kernel path, with ``collect_metrics=True`` riding the
ELL tiles.  Emits BENCH_dist_phase.json.

Emits BENCH_local_phase.json (repo root by default) so the perf trajectory
is tracked per-PR, and returns harness CSV rows.

    PYTHONPATH=src python -m benchmarks.local_phase_bench [--out PATH]
    PYTHONPATH=src python -m benchmarks.run --fast --table dist_phase
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(REPO_ROOT, "BENCH_local_phase.json")
DIST_OUT = os.path.join(REPO_ROOT, "BENCH_dist_phase.json")


def _time_us(fn, *args, warmup=3, iters=20):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def _saturate(graph, prog, es, payload_value):
    """Fill the frontier: every vertex sent last step, has one pending
    message, and the halo table was filled by a real exchange — the
    steady-state shape of a busy iteration."""
    from repro.core.runtime import exchange

    vm = graph.vertex_mask
    pending = {}
    for ch in prog.channels:
        (dt, _), = ch.components
        pending[ch.name] = ((jnp.where(vm, payload_value, 0).astype(dt),), vm)
    es = dataclasses.replace(es, send=vm, pending=pending,
                             export_out=es.out, export_send=vm)
    return exchange(graph, es)


def _saturated_state(graph, prog, vdata, payload_value):
    from repro.core.engine_hybrid import init_hybrid

    return _saturate(graph, prog, init_hybrid(graph, prog, vdata),
                     payload_value)


def _pseudo_superstep(graph, prog, vdata, use_ell, collect_metrics):
    from repro.core.runtime import apply_phase, deliver
    from repro.core.vertex_program import StepInfo

    info = StepInfo(superstep=1, pseudo_step=1, phase="local")

    def step(es):
        es = apply_phase(graph, prog, es, graph.vertex_mask, info, vdata)
        es, _ = deliver(graph, prog, es, edges="local", use_ell=use_ell,
                        collect_metrics=collect_metrics)
        return es

    return jax.jit(step)


def _remote_deliver(graph, prog, use_ell, collect_metrics):
    from repro.core.runtime import deliver

    def step(es):
        es, _ = deliver(graph, prog, es, edges="remote", use_ell=use_ell,
                        collect_metrics=collect_metrics)
        return es

    return jax.jit(step)


def _fused_pr_step_fn(graph, prog):
    """One fused PageRank loop body: the engine's own fused step
    (`engine_hybrid.fused_step_fn`, the same closure
    `_fused_pr_local_phase` iterates) + the collect_metrics=False
    has/running/export bookkeeping."""
    from repro.core.engine_hybrid import fused_step_fn

    kstep = fused_step_fn(graph, prog, "pr_step", graph.n_partitions)

    def step(rank, delta, send, eo, esend):
        rank_n, d_in, send_n = kstep(rank, delta, send)
        eo = eo + jnp.where(send_n, d_in, 0.0)
        esend = jnp.logical_or(esend, send_n)
        running = jnp.any(d_in > 0, axis=1)
        return rank_n, d_in, send_n, eo, esend, running

    return jax.jit(step)


def _fused_min_step_fn(graph, prog):
    """One fused min-semiring loop body: the engine's own fused step + the
    collect_metrics=False bookkeeping of `_fused_min_local_phase`."""
    from repro.core.engine_hybrid import fused_step_fn

    kstep = fused_step_fn(graph, prog, "min_step", graph.n_partitions)

    def step(x, send, eo, esend):
        x_n, d_n, send_n = kstep(x, send)
        eo = jnp.where(send_n, x_n, eo)
        esend = jnp.logical_or(esend, send_n)
        running = jnp.any(d_n < jnp.inf, axis=1)
        return x_n, send_n, eo, esend, running

    return jax.jit(step)


def _bench_workload(results, name, graph, prog, payload_value, fused=None):
    """Dense/ELL/fused local pseudo-superstep + dense/ELL remote delivery."""
    es = _saturated_state(graph, prog, None, payload_value)
    rec = {"graph": graph.shape_summary, "kl": graph.kl,
           "bins": [len(graph.local_ell), len(graph.remote_ell)]}

    dense = _time_us(_pseudo_superstep(graph, prog, None, False, True), es)
    ell = _time_us(_pseudo_superstep(graph, prog, None, True, False), es)
    rec.update(dense_us=dense, ell_us=ell, speedup_ell=dense / ell)

    if fused == "pr_step":
        fstep = _fused_pr_step_fn(graph, prog)
        rec["fused_us"] = _time_us(
            fstep, es.state["rank"],
            jnp.where(graph.vertex_mask, payload_value, 0.0),
            graph.vertex_mask, jnp.zeros_like(es.state["rank"]),
            jnp.zeros_like(graph.vertex_mask))
    elif fused == "min_step":
        fstep = _fused_min_step_fn(graph, prog)
        ch_name = prog.channels[0].name
        rec["fused_us"] = _time_us(
            fstep, es.state[ch_name].astype(jnp.float32), graph.vertex_mask,
            es.state[ch_name].astype(jnp.float32),
            jnp.zeros_like(graph.vertex_mask))
    if "fused_us" in rec:
        rec["speedup_fused"] = dense / rec["fused_us"]
        rec["speedup_fused_vs_ell"] = ell / rec["fused_us"]

    rdense = _time_us(_remote_deliver(graph, prog, False, True), es)
    rell = _time_us(_remote_deliver(graph, prog, True, False), es)
    rec.update(remote_dense_us=rdense, remote_ell_us=rell,
               speedup_remote=rdense / rell)

    results["workloads"][name] = rec
    return rec


def bench_local_phase(out_path: str = DEFAULT_OUT) -> dict:
    from repro.core import (bfs_partition, build_partitioned_graph,
                            hash_partition)
    from repro.core.apps import SSSP, IncrementalPageRank
    from repro.core.apps.pagerank import pagerank_edge_weights
    from repro.data.graphs import grid_graph, rmat_graph

    results: dict = {"meta": {"backend": jax.default_backend(),
                              "mode": "interpret" if
                              jax.default_backend() != "tpu" else "mosaic"},
                     "workloads": {}}

    # --- PageRank, the --fast web workload -------------------------------
    edges, n = rmat_graph(1500, avg_degree=8, seed=1)
    w = pagerank_edge_weights(edges, n)
    part = bfs_partition(edges, n, 8, seed=1)
    graph = build_partitioned_graph(edges, n, part, weights=w)
    _bench_workload(results, "pagerank_fast", graph,
                    IncrementalPageRank(tolerance=1e-4), 0.01,
                    fused="pr_step")

    # --- PageRank with hub skew: sliced-ELL binning engaged --------------
    rng = np.random.RandomState(2)
    hubs = np.stack([rng.randint(0, n, size=4000),
                     rng.randint(0, 6, size=4000)], axis=1)
    edges_sk = np.unique(np.concatenate([edges, hubs]), axis=0)
    edges_sk = edges_sk[edges_sk[:, 0] != edges_sk[:, 1]]
    w_sk = pagerank_edge_weights(edges_sk, n)
    part_sk = hash_partition(n, 8, seed=2)
    graph_sk = build_partitioned_graph(edges_sk, n, part_sk, weights=w_sk,
                                       ell_base_slices=32)
    assert len(graph_sk.local_ell) > 1, "skew workload should spill bins"
    _bench_workload(results, "pagerank_skew", graph_sk,
                    IncrementalPageRank(tolerance=1e-4), 0.01,
                    fused="pr_step")

    # --- SSSP, the --fast road workload ----------------------------------
    edges, w, n = grid_graph(8, 110, seed=0)
    part = bfs_partition(edges, n, 8, seed=0)
    graph = build_partitioned_graph(edges, n, part, weights=w)
    _bench_workload(results, "sssp_fast", graph, SSSP(source=0), 1.0,
                    fused="min_step")

    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
    return results


# ---------------------------------------------------------------------------
# dist_phase: the distributed hybrid step under a fake multi-device mesh
# ---------------------------------------------------------------------------

def _dist_step(graph, prog, mesh, axes, payload_value, use_ell,
               collect_metrics):
    """Jitted sharded (graph, es) -> es distributed step + its operands."""
    import jax
    from jax.sharding import NamedSharding

    from repro.core.distributed import (_es_specs, make_dist_hybrid_step,
                                        shard0_specs)
    from repro.core.engine_hybrid import init_hybrid

    step = make_dist_hybrid_step(prog, mesh, axes=axes, use_ell=use_ell,
                                 collect_metrics=collect_metrics)
    es = init_hybrid(graph, prog, None, use_ell=use_ell,
                     collect_metrics=collect_metrics)
    es = _saturate(graph, prog, es, payload_value)
    gs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                      shard0_specs(graph, axes))
    ess = jax.tree.map(lambda s: NamedSharding(mesh, s), _es_specs(es, axes))
    graph_d = jax.device_put(graph, gs)
    es_d = jax.device_put(es, ess)
    return jax.jit(step, in_shardings=(gs, ess)), graph_d, es_d


def bench_dist_phase(out_path: str = DIST_OUT, fast: bool = True) -> dict:
    """A/B one full distributed global iteration (the `make_dist_hybrid_step`
    jittable), dense seed path vs the default kernel path, on a mesh over
    every available device.  Run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (benchmarks.run
    sets it for ``--table dist_phase``); the partition axis shards one
    partition per device."""
    import jax

    from repro.core import bfs_partition, build_partitioned_graph
    from repro.core.apps import SSSP, IncrementalPageRank
    from repro.core.apps.pagerank import pagerank_edge_weights
    from repro.data.graphs import grid_graph, rmat_graph

    n_dev = len(jax.devices())
    assert n_dev >= 2 and n_dev % 2 == 0, \
        f"dist_phase needs a multi-device mesh, got {n_dev} devices " \
        "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)"
    axes = ("data", "model")
    mesh = jax.make_mesh((2, n_dev // 2), axes)

    results: dict = {"meta": {"backend": jax.default_backend(),
                              "devices": n_dev,
                              "mesh": [2, n_dev // 2],
                              "mode": "interpret" if
                              jax.default_backend() != "tpu" else "mosaic"},
                     "workloads": {}}

    n_pr = 1500 if fast else 4000
    edges, n = rmat_graph(n_pr, avg_degree=8, seed=1)
    w = pagerank_edge_weights(edges, n)
    part = bfs_partition(edges, n, n_dev, seed=1)
    g_pr = build_partitioned_graph(edges, n, part, weights=w,
                                   edge_blocks=n_dev)  # one block per device

    rc = (8, 110) if fast else (8, 300)
    edges, w, n = grid_graph(*rc, seed=0)
    part = bfs_partition(edges, n, n_dev, seed=0)
    g_ss = build_partitioned_graph(edges, n, part, weights=w,
                                   edge_blocks=n_dev)

    for name, graph, prog, payload in (
            ("pagerank", g_pr, IncrementalPageRank(tolerance=1e-4), 0.01),
            ("sssp", g_ss, SSSP(source=0), 1.0)):
        rec = {"graph": graph.shape_summary,
               "bins": [len(graph.local_ell), len(graph.remote_ell)]}
        variants = {
            # the seed behavior: dense gather/segment everywhere
            "dense": dict(use_ell=False, collect_metrics=True),
            # the new default: kernel path, counters riding the tiles
            "ell": dict(use_ell=True, collect_metrics=True),
            # the perf configuration: kernel path, accounting dropped
            "ell_nometrics": dict(use_ell=True, collect_metrics=False),
        }
        for vname, kw in variants.items():
            step, graph_d, es_d = _dist_step(graph, prog, mesh, axes,
                                             payload, **kw)
            rec[f"{vname}_us"] = _time_us(step, graph_d, es_d,
                                          warmup=2, iters=10)
        rec["speedup_ell"] = rec["dense_us"] / rec["ell_us"]
        rec["speedup_ell_nometrics"] = rec["dense_us"] / rec["ell_nometrics_us"]
        results["workloads"][name] = rec

    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
    return results


def dist_csv_rows(results: dict) -> list[str]:
    rows = []
    mesh = "x".join(map(str, results["meta"]["mesh"]))
    for name, r in results["workloads"].items():
        meta = f"mesh={mesh};bins={r['bins']};graph={r['graph']}"
        for variant in ("dense", "ell", "ell_nometrics"):
            sp = {"dense": 1.0, "ell": r["speedup_ell"],
                  "ell_nometrics": r["speedup_ell_nometrics"]}[variant]
            rows.append(f"dist_phase/{name}/{variant},"
                        f"{r[f'{variant}_us']:.0f},speedup={sp:.2f};{meta}")
    return rows


def csv_rows(results: dict) -> list[str]:
    rows = []
    for name, r in results["workloads"].items():
        meta = f"kl={r['kl']};bins={r['bins']};graph={r['graph']}"
        for variant in ("dense", "ell", "fused", "remote_dense",
                        "remote_ell"):
            us = r.get(f"{variant}_us")
            if us is None:
                continue
            sp = {"remote_ell": r.get("speedup_remote", 1.0),
                  "remote_dense": 1.0}.get(
                      variant, r.get(f"speedup_{variant}", 1.0))
            rows.append(f"local_phase/{name}/{variant},{us:.0f},"
                        f"speedup={sp:.2f};{meta}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="where to write BENCH_local_phase.json")
    args = ap.parse_args()
    results = bench_local_phase(args.out)
    print("name,us_per_call,derived")
    for row in csv_rows(results):
        print(row)


if __name__ == "__main__":
    import sys
    sys.path.insert(0, REPO_ROOT)
    sys.path.insert(0, os.path.join(REPO_ROOT, "src"))
    main()
