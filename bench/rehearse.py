"""Compile each cell's device loop for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <name> ...] [--seed N]

For each named cell (every cell of ``BENCHMARK.json`` by default) this
builds the graph on the host at its real size, exactly as a run does,
then ahead-of-time compiles the program that ``run_hybrid`` jits (the
whole device loop of ``run_engine``) for one chip of a described
``v5e:2x2`` topology and prints the compiler's ``memory_analysis``.  It
runs nothing: it shows, before any chip time is spent, whether the
program compiles for the chip and how much HBM it asks for.  A script
run by hand; it is no test.
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
    graph, _ = harness.build(wl.config, edges, weights, n, seed, cpu)
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    names = args.workload or [w["name"] for w in json.loads(
        (REPO / "BENCHMARK.json").read_text())["workloads"]]
    for name in names:
        print(json.dumps(rehearse(name, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
