"""Ingestion A/B: in-memory vs out-of-core partition+build, wall + peak RSS.

The survey literature (Ammar & Özsu) puts ingestion + partitioning at a
routinely *dominant* share of end-to-end time on real datasets, and memory
is what caps the in-memory builder's reach — so this table measures both,
honestly: each build runs in a **fresh subprocess** and reports

  * ``wall_s``        — partition (the workload's partitioner, seed 0) +
                        build, excluding imports and backend warmup,
  * ``peak_rss_mb``   — ``ru_maxrss`` *above* a post-import baseline
                        (imports + jax init + staged-dir open), i.e. the
                        memory the build itself added,
  * ``digest``        — :func:`repro.io.graph_digest` of the produced
                        ``PartitionedGraph``.

The in-memory side loads the staged edges into RAM and runs the classic
``make_partition`` + ``build_partitioned_graph``; the out-of-core side
runs ``build_partitioned_graph_from_path`` over the same staged directory.
Digest equality across the two subprocesses is the bit-identity check at
every size — no arrays cross the process boundary.

Workloads are R-MAT at ~10^5 / 10^6 / 10^7 edges (``--fast`` drops the
largest).  ELL layouts are built at the smallest size (cheap, keeps the
kernel-path arrays under the identity check) and skipped above it, where
the padded ELL product would dominate both sides identically and the
interesting number is the ingestion pipeline itself.

A second table — ``ragged_layout`` rows, named ``ragged_<partitioner>_<n>``
— A/Bs the block-ragged edge layout (``edge_blocks=1``, the default)
against the legacy shared-width one (``edge_blocks=P``) for hash / fennel /
multilevel labelings at 10^6 and 10^7 edges.  Each side builds in-memory in
a fresh subprocess, then runs one jitted hybrid SSSP iteration, so the
reported peak RSS and ``build+step`` wall cover both the array product and
the work the step does over it: with skew-prone labelings the shared width
is ``P * max_p Ep_p`` while the ragged layout pays ``sum_p Ep_p``
(``pad_waste`` is the ratio).  A third subprocess rebuilds the ragged side
out-of-core; ``bitexact`` is its digest against the in-memory build.

Emits ``BENCH_ingest.json`` (committed, trajectory-tracked);
``benchmarks/gates.json`` gates ``peak_rss_ooc_over_inmem < 0.95`` at the
largest size (the finished ragged graph is ~E rows and dominates both
sides; out-of-core saves the in-RAM edge list + labeling scratch) plus
digest equality everywhere (table ``ingest``), and the ragged-vs-padded
RSS ratio ``<= 0.6`` at 10^7 edges (table ``ragged``), via
``check_gates.py``.

    PYTHONPATH=src python -m benchmarks.run --table ingest [--fast]
    PYTHONPATH=src python -m benchmarks.ingest_bench [--fast] [--out PATH]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import subprocess
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(REPO_ROOT, "BENCH_ingest.json")

N_PARTITIONS = 8
AVG_DEGREE = 8
# name -> (n_vertices, partitioner, build_ell).  Every row — including the
# 10^7 RSS gate — runs fennel, the labeling the engine actually ships
# with: the block-ragged edge layout (edge_blocks=1, the default) sizes
# each partition's span to its own in-edge count, so a hub-clustering
# labeling no longer inflates a shared padded product on both sides of the
# A/B.  (The ragged_layout table below quantifies exactly that effect
# against the legacy shared-width edge_blocks=P layout.)
WORKLOADS = {
    "rmat_1e5": (12_500, "fennel", True),
    "rmat_1e6": (125_000, "fennel", False),
    "rmat_1e7": (1_250_000, "fennel", False),
}

# ragged_layout table: partitioners x sizes (size key -> n_vertices must
# match a staged WORKLOADS row so the staged dir is shared).
RAGGED_PARTITIONERS = ("hash", "fennel", "multilevel")
RAGGED_SIZES = {"1e6": "rmat_1e6", "1e7": "rmat_1e7"}


def _maxrss_mb() -> float:
    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return ru / 1024.0          # linux reports KiB


def run_child(mode: str, staged: str, k: int, partitioner: str,
              build_ell: bool, chunk_edges: int, n: int = 0,
              edge_blocks: int = 1, step: bool = False) -> None:
    """One measured build in this (fresh) process; JSON on stdout.
    (Subprocesses matter twice over: ru_maxrss is a per-process high-water
    mark that Linux carries across exec, so builds must not share a
    process with each other or with a fat parent.)"""
    import jax
    import jax.numpy as jnp

    from repro.io import graph_digest
    from repro.io.readers import StagedEdgeSource

    if mode == "stage":
        from repro.data.graphs import materialize
        src = materialize(staged, "rmat", n=n, avg_degree=AVG_DEGREE,
                          seed=1)
        print(json.dumps({"n_vertices": src.n_vertices,
                          "n_edges": src.n_edges}))
        return
    src = StagedEdgeSource(staged)
    jnp.zeros(8).block_until_ready()        # backend init lands in baseline
    gc.collect()
    rss0 = _maxrss_mb()
    t0 = time.perf_counter()
    if mode == "inmem":
        from repro.core import build_partitioned_graph
        from repro.partition import make_partition
        edges, w = src.load_arrays()                     # genuinely in RAM
        part = make_partition(partitioner, edges, src.n_vertices, k,
                              seed=0)
        graph = build_partitioned_graph(edges, src.n_vertices, part,
                                        weights=w, build_ell=build_ell,
                                        edge_blocks=edge_blocks)
    elif mode == "ooc":
        from repro.io import build_partitioned_graph_from_path
        graph = build_partitioned_graph_from_path(
            staged, partitioner, k, chunk_edges=chunk_edges,
            partition_seed=0, build_ell=build_ell, edge_blocks=edge_blocks)
    else:
        raise ValueError(mode)
    wall = time.perf_counter() - t0
    rec = {
        "mode": mode, "backend": jax.default_backend(),
        "wall_s": round(wall, 3),
        "shape": graph.shape_summary,
        "pad_waste": round(float(graph.pad_waste), 3),
        "digest": graph_digest(graph),
    }
    if step:
        # one jitted hybrid SSSP iteration over the freshly built layout:
        # the dense deliver walks the full edge arrays, so the step wall
        # (and its share of peak RSS) scales with the layout's edge-row
        # count — sum_p Ep_p ragged vs P * max_p Ep_p shared-width.
        from repro.core import run_hybrid
        from repro.core.apps import SSSP
        t1 = time.perf_counter()
        es, _ = run_hybrid(graph, SSSP(source=0), max_iters=1,
                           use_ell=build_ell, collect_metrics=False)
        jax.block_until_ready(es.state)
        rec["step_s"] = round(time.perf_counter() - t1, 3)
    rss1 = _maxrss_mb()
    rec["peak_rss_mb"] = round(max(rss1 - rss0, 0.0), 1)
    rec["baseline_rss_mb"] = round(rss0, 1)
    print(json.dumps(rec))


def _spawn(mode: str, staged: str, k: int, partitioner: str,
           build_ell: bool, chunk_edges: int, n: int = 0,
           edge_blocks: int = 1, step: bool = False) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO_ROOT, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    # builds measure host RSS: pinned to the CPU, so a child never competes
    # for an accelerator (which belongs to one process at a time)
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "benchmarks.ingest_bench", "--child", mode,
           "--staged", staged, "--k", str(k), "--partitioner", partitioner,
           "--chunk-edges", str(chunk_edges), "--n", str(n),
           "--edge-blocks", str(edge_blocks)]
    if build_ell:
        cmd.append("--build-ell")
    if step:
        cmd.append("--step")
    out = subprocess.run(cmd, cwd=REPO_ROOT, env=env, capture_output=True,
                         text=True)
    if out.returncode != 0:
        raise RuntimeError(f"ingest child {mode} failed:\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_ingest(out_path: str = DEFAULT_OUT, fast: bool = False,
                 chunk_edges: int = 1 << 20) -> dict:
    # the parent stays off JAX; the backend is the children's (see _spawn)
    results: dict = {"meta": {"n_partitions": N_PARTITIONS,
                              "avg_degree": AVG_DEGREE,
                              "chunk_edges": chunk_edges,
                              "fast": bool(fast),
                              "rss_metric": "ru_maxrss above post-import "
                                            "baseline, fresh subprocess "
                                            "per build"},
               "workloads": {}}
    names = list(WORKLOADS)[:2] if fast else list(WORKLOADS)
    with tempfile.TemporaryDirectory() as tmp:
        for name in names:
            n, partitioner, build_ell = WORKLOADS[name]
            staged = os.path.join(tmp, name)
            t0 = time.perf_counter()
            staged_meta = _spawn("stage", staged, N_PARTITIONS,
                                 partitioner, False, chunk_edges, n=n)
            stage_s = time.perf_counter() - t0
            rec: dict = {"graph": f"V={staged_meta['n_vertices']} "
                                  f"E={staged_meta['n_edges']} "
                                  f"k={N_PARTITIONS}",
                         "partitioner": partitioner,
                         "build_ell": build_ell,
                         "stage_s": round(stage_s, 3)}
            for mode in ("inmem", "ooc"):
                child = _spawn(mode, staged, N_PARTITIONS, partitioner,
                               build_ell, chunk_edges)
                results["meta"]["backend"] = child.pop("backend")
                rec[mode] = {k: v for k, v in child.items() if k != "mode"}
                print(f"{name}/{mode}: wall {child['wall_s']}s, "
                      f"peak rss +{child['peak_rss_mb']}MB "
                      f"(baseline {child['baseline_rss_mb']}MB)")
            rec["bitexact"] = rec["inmem"]["digest"] == rec["ooc"]["digest"]
            rec["ratios"] = {
                "peak_rss_ooc_over_inmem":
                    round(rec["ooc"]["peak_rss_mb"]
                          / max(rec["inmem"]["peak_rss_mb"], 1e-9), 3),
                "wall_ooc_over_inmem":
                    round(rec["ooc"]["wall_s"]
                          / max(rec["inmem"]["wall_s"], 1e-9), 3),
            }
            results["workloads"][name] = rec

        # ragged_layout table: edge_blocks=1 vs edge_blocks=P, in-memory
        # build + one hybrid step each, plus an out-of-core ragged rebuild
        # for the digest check.  Reuses the staged dirs from the loop above.
        for size, staged_name in RAGGED_SIZES.items():
            if staged_name not in names:
                continue                                 # --fast drops 1e7
            staged = os.path.join(tmp, staged_name)
            for pname in RAGGED_PARTITIONERS:
                name = f"ragged_{pname}_{size}"
                ragged = _spawn("inmem", staged, N_PARTITIONS, pname, False,
                                chunk_edges, edge_blocks=1, step=True)
                padded = _spawn("inmem", staged, N_PARTITIONS, pname, False,
                                chunk_edges, edge_blocks=N_PARTITIONS,
                                step=True)
                ooc = _spawn("ooc", staged, N_PARTITIONS, pname, False,
                             chunk_edges, edge_blocks=1)
                r_wall = ragged["wall_s"] + ragged["step_s"]
                p_wall = padded["wall_s"] + padded["step_s"]
                rec = {"graph": ragged["shape"],
                       "partitioner": pname,
                       "pad_waste": ragged["pad_waste"],
                       "ragged": {k: v for k, v in ragged.items()
                                  if k != "mode"},
                       "padded": {k: v for k, v in padded.items()
                                  if k != "mode"},
                       "ooc_digest": ooc["digest"],
                       "bitexact": ragged["digest"] == ooc["digest"],
                       "ratios": {
                           "peak_rss_ragged_over_padded": round(
                               ragged["peak_rss_mb"]
                               / max(padded["peak_rss_mb"], 1e-9), 3),
                           "build_step_wall_ragged_over_padded": round(
                               r_wall / max(p_wall, 1e-9), 3),
                       }}
                results["workloads"][name] = rec
                print(f"{name}: pad_waste {rec['pad_waste']}x, "
                      f"rss ragged/padded "
                      f"{rec['ratios']['peak_rss_ragged_over_padded']}, "
                      f"build+step ragged/padded "
                      f"{rec['ratios']['build_step_wall_ragged_over_padded']}"
                      f", bitexact {rec['bitexact']}")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
    return results


def csv_rows(results: dict) -> list[str]:
    rows = []
    for name, r in results["workloads"].items():
        if name.startswith("ragged_"):
            for mode in ("ragged", "padded"):
                m = r[mode]
                derived = (f"peak_rss_mb={m['peak_rss_mb']};"
                           f"pad_waste={r['pad_waste']};"
                           f"rss_ratio="
                           f"{r['ratios']['peak_rss_ragged_over_padded']};"
                           f"bitexact={r['bitexact']}")
                rows.append(f"ingest/{name}/{mode},"
                            f"{(m['wall_s'] + m['step_s']) * 1e6:.0f},"
                            f"{derived}")
            continue
        for mode in ("inmem", "ooc"):
            m = r[mode]
            derived = (f"peak_rss_mb={m['peak_rss_mb']};"
                       f"bitexact={r['bitexact']};"
                       f"rss_ratio={r['ratios']['peak_rss_ooc_over_inmem']};"
                       f"{r['graph'].replace(' ', ';')}")
            rows.append(f"ingest/{name}/{mode},{m['wall_s'] * 1e6:.0f},"
                        f"{derived}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", default=None,
                    choices=("inmem", "ooc", "stage"),
                    help="internal: run one measured build and print json")
    ap.add_argument("--staged", default=None)
    ap.add_argument("--k", type=int, default=N_PARTITIONS)
    ap.add_argument("--partitioner", default="fennel")
    ap.add_argument("--n", type=int, default=0,
                    help="internal: vertex count for --child stage")
    ap.add_argument("--build-ell", action="store_true")
    ap.add_argument("--edge-blocks", type=int, default=1,
                    help="internal: edge-block count for --child builds")
    ap.add_argument("--step", action="store_true",
                    help="internal: time one hybrid SSSP iteration too")
    ap.add_argument("--chunk-edges", type=int, default=1 << 20)
    ap.add_argument("--fast", action="store_true",
                    help="drop the 10^7-edge workload")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()
    if args.child:
        run_child(args.child, args.staged, args.k, args.partitioner,
                  args.build_ell, args.chunk_edges, n=args.n,
                  edge_blocks=args.edge_blocks, step=args.step)
        return
    results = bench_ingest(args.out, fast=args.fast,
                           chunk_edges=args.chunk_edges)
    print("name,us_per_call,derived")
    for row in csv_rows(results):
        print(row)


if __name__ == "__main__":
    sys.path.insert(0, REPO_ROOT)
    sys.path.insert(0, os.path.join(REPO_ROOT, "src"))
    main()
