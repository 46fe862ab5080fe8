"""Compile each cell's device loop for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <name> ...] [--seed N]
    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config <name> --mesh 2x2 \
        [--scale S] [--seed N]

For each named cell (every cell of ``BENCHMARK.json`` by default) this
builds the graph on the host at its real size, exactly as a run does,
then ahead-of-time compiles the program that ``run_hybrid`` jits (the
whole device loop of ``run_engine``) for one chip of a described
``v5e:2x2`` topology and prints the compiler's ``memory_analysis``.  It
runs nothing: it shows, before any chip time is spent, whether the
program compiles for the chip and how much HBM it asks for.

With ``--mesh`` it sizes a configuration for a cell on that mesh, before
the cell exists: the configuration ``bench/configs/<name>.json`` at
generator scale ``--scale``, its edge blocks raised to a multiple of the
chips, built on the host as a run does, with SSSP traffic.  It prints
the host's seconds to generate, partition and build, the process's peak
resident memory, the seconds of the check of one job against the plain
reference, and the graph's bytes on each chip of the mesh as the harness
places it, read from each leaf's sharding on the described chips.  It then tries the
compile of ``run_hybrid``'s device loop on that placed graph and prints
whether the chip's compiler takes it, or the first line of its refusal.
A script run by hand; it is no test.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def rehearse(name: str, seed: int) -> dict:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.common as common
    from bench import harness
    from bench.jobs import JOBS
    from repro.exec.driver import while_engine
    from repro.exec.policy import hybrid_policy

    wl = harness.load_workload(name)
    t0 = time.perf_counter()
    edges, weights, n = harness.generate(wl.config, seed)
    kind = JOBS[wl.traffic["job"]](wl.traffic, edges, weights, n, seed)
    weights = kind.weights(edges, weights, n)
    cpu = jax.devices("cpu")[0]
    graph, _ = harness.build(wl.config, edges, weights, n, seed,
                             harness.Placement((cpu,)))
    del edges, weights
    build_s = time.perf_counter() - t0
    graph_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(graph))

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shapes(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    prog = kind.program()
    policy = hybrid_policy()         # run_hybrid's defaults
    vdata = kind.vdata(-1)
    common.default_interpret = lambda: False    # Mosaic, as on the chip
    g, v = shapes(graph), shapes(vdata)
    es = shapes(jax.eval_shape(lambda g_, v_: policy.init(g_, prog, v_),
                               g, v))
    t0 = time.perf_counter()
    compiled = jax.jit(lambda g_, v_, e_: while_engine(
        prog, lambda x: policy.step(g_, prog, x, v_), e_, 100_000)
    ).lower(g, v, es).compile()
    mem = compiled.memory_analysis()
    return {"workload": name, "vertices": n, "arcs": graph.n_edges,
            "graph_bytes": graph_bytes, "host_build_s": build_s,
            "compile_s": time.perf_counter() - t0,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "tpu_custom_calls": compiled.as_text().count("tpu_custom_call")}


def rehearse_mesh(config: str, scale: int | None, shape: list[int],
                  seed: int) -> dict:
    """Size configuration ``config`` for a cell on a mesh of ``shape``
    (module docstring)."""
    import math
    import resource

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec

    import repro.kernels.common as common
    from bench import harness
    from bench.jobs import JOBS
    from repro.exec.driver import while_engine
    from repro.exec.policy import hybrid_policy

    chips = math.prod(shape)
    cfg = json.loads((harness.BENCH / "configs" / f"{config}.json")
                     .read_text())
    if scale is not None:
        cfg["generator"]["scale"] = scale
    cfg["build"]["edge_blocks"] = math.lcm(
        cfg["build"].get("edge_blocks", 1), chips)
    cfg["mesh"] = {"shape": shape, "axes": ["data", "model"][:len(shape)]}
    wl = harness.Workload(
        name=f"{config}.mesh", chips=chips, config=cfg,
        config_file=f"bench/configs/{config}.json",
        traffic=json.loads((harness.BENCH / "traffic" / "sssp.json")
                           .read_text()),
        limits={}, end_to_end=[], per_layer=[])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    place = harness.placement(wl, topo.devices[:chips])

    t = [time.perf_counter()]
    edges, weights, n = harness.generate(cfg, seed)
    kind = JOBS[wl.traffic["job"]](wl.traffic, edges, weights, n, seed)
    weights = kind.weights(edges, weights, n)
    t.append(time.perf_counter())
    cpu = jax.devices("cpu")[0]
    graph, split = harness.build(cfg, edges, weights, n, seed,
                                 harness.Placement((cpu,)))
    t.append(time.perf_counter())
    ref = kind.check({0: np.zeros((n, 1))}, edges, weights, n)
    t.append(time.perf_counter())
    del edges, weights, ref

    shardings = place.graph_shardings(graph)
    per_chip = sum(
        math.prod(s.shard_shape(leaf.shape)) * leaf.dtype.itemsize
        for leaf, s in zip(jax.tree.leaves(graph),
                           jax.tree.leaves(shardings)))
    out = {"config": config, "scale": cfg["generator"]["scale"],
           "mesh": shape, "edge_blocks": cfg["build"]["edge_blocks"],
           "vertices": n, "arcs": graph.n_edges,
           "graph_bytes": sum(leaf.nbytes for leaf in jax.tree.leaves(graph)),
           "graph_bytes_per_chip": per_chip,
           "generate_s": t[1] - t[0], **split,
           "host_build_s": t[2] - t[0], "check_s_one_job": t[3] - t[2],
           "peak_rss_gib": resource.getrusage(
               resource.RUSAGE_SELF).ru_maxrss / 2**20}

    # run_hybrid's device loop, init included, on the placed graph
    g = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                       sharding=s),
                     graph, shardings)
    rep = NamedSharding(place.mesh, PartitionSpec())
    v = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                    sharding=rep),
                     kind.vdata(-1))
    prog, policy = kind.program(), hybrid_policy()
    common.default_interpret = lambda: False    # Mosaic, as on the chip

    def loop(g_, v_):
        return while_engine(prog, lambda x: policy.step(g_, prog, x, v_),
                            policy.init(g_, prog, v_), 100_000)

    try:
        jax.jit(loop).lower(g, v).compile()
        out["run_hybrid_on_mesh"] = "compiles"
    except Exception as e:          # the compiler's refusal is the reading
        out["run_hybrid_on_mesh"] = (f"{type(e).__name__}: "
                                     f"{str(e).splitlines()[0]}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    ap.add_argument("--config", help="size this configuration for a mesh")
    ap.add_argument("--mesh", help="the mesh's shape, as 2x2")
    ap.add_argument("--scale", type=int, help="the generator's scale")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    if args.mesh:
        shape = [int(k) for k in args.mesh.split("x")]
        print(json.dumps(rehearse_mesh(args.config, args.scale, shape,
                                       args.seed)), flush=True)
        return 0
    names = args.workload or [w["name"] for w in json.loads(
        (REPO / "BENCHMARK.json").read_text())["workloads"]]
    for name in names:
        print(json.dumps(rehearse(name, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
