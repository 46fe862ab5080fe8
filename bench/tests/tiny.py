"""The cell of BENCHMARK.json cut to a size a CPU test run can hold, and
three more on its configuration: SSSP on a lattice with bfs partitions,
and PageRank, for the generator and the job kind that no cell runs yet,
and SSSP over a 2x2 mesh of four chips, for the placement of a cell on
more than one."""

import functools
import json

import jax

from bench import hardware, harness

TINY = {
    "kronecker": {"kind": "kronecker", "scale": 7, "edge_factor": 16,
                  "initiator": [0.57, 0.19, 0.19, 0.05]},
    "lattice": {"kind": "lattice", "rows": 12, "cols": 12,
                "weight_low": 1.0, "weight_high": 10.0},
}
CELL = "g500-s16.sssp"
ROAD = "road"
PAGERANK = "pagerank"
MESH = "mesh"
#: the mesh cell's own keys: four chips of a 2x2 mesh, one block of two
#: partitions each
MESH_CONFIG = {"partitions": 8, "mesh": {"shape": [2, 2],
                                         "axes": ["data", "model"]}}
MESH_BUILD = {"edge_blocks": 4}
#: PageRank at tolerance 1e-6 read a largest relative error of 1.26e-4
#: against the float64 fixed point and the bfloat16 control 2.12e-3 (CPU,
#: scale 10)
PAGERANK_LIMITS = {"rel_err": 6e-4}


def _pagerank_workload() -> harness.Workload:
    wl = harness.load_workload(CELL)
    with open(harness.BENCH / "traffic" / "pagerank.json") as f:
        wl.traffic = json.load(f)
    wl.name, wl.limits = PAGERANK, dict(PAGERANK_LIMITS)
    return wl


def tiny_workload(name: str) -> harness.Workload:
    wl = (_pagerank_workload() if name == PAGERANK
          else harness.load_workload(CELL))
    kind = "lattice" if name == ROAD else wl.config["generator"]["kind"]
    wl.config["generator"] = TINY[kind]
    if name == ROAD:
        wl.name, wl.config["partitioner"] = ROAD, "bfs"
    if name == MESH:
        wl.name, wl.chips = MESH, 4
        wl.config.update(MESH_CONFIG)
        wl.config["build"] = {**wl.config["build"], **MESH_BUILD}
    if "roots" in wl.traffic:
        wl.traffic["roots"] = 8
    return wl


def run_tiny(monkeypatch, name: str, seed: int, runner=harness.run_hybrid,
             trace: bool = False) -> dict:
    """One run of the tiny cell on the CPU, past the harness's look for a
    chip, with no compile cache and the CPU given the v5e's peaks."""
    return run_tiny_workload(monkeypatch, tiny_workload(name), seed, runner,
                             trace)


def run_tiny_workload(monkeypatch, wl: harness.Workload, seed: int,
                      runner=harness.run_hybrid, trace: bool = False
                      ) -> dict:
    """One run of ``wl`` as :func:`run_tiny` makes it, on the first
    ``wl.chips`` CPU devices."""
    monkeypatch.setitem(hardware.HARDWARE, "cpu",
                        hardware.HARDWARE["TPU v5 lite"])
    monkeypatch.setattr(harness, "CACHE", harness.CACHE / "tests")
    return harness.run_cell(wl, seed, 0.0, trace,
                            jax.devices("cpu")[:wl.chips], runner=runner)


def mesh_runner(graph, prog, vdata):
    """Stand-in for ``run_hybrid`` on a graph placed over a mesh: the
    program's init, then its shard_map step (``make_dist_hybrid_step``)
    to quiescence, on the mesh the graph lies on.  The init runs on the
    placed graph as one program that the compiler partitions, so it takes
    the dense delivery (``use_ell=False``, the same results and counters):
    a Pallas kernel outside a shard_map cannot be partitioned for the
    chip."""
    from jax.sharding import NamedSharding
    from repro.core.distributed import _es_specs
    from repro.core.engine_hybrid import init_hybrid
    from repro.core.runtime import quiescent

    mesh = jax.tree.leaves(graph)[0].sharding.mesh
    es = init_hybrid(graph, prog, vdata, use_ell=False)
    es = jax.device_put(es, jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        _es_specs(es, tuple(mesh.axis_names))))
    while not bool(quiescent(prog, es)):
        es = _dist_step(prog, mesh, graph, es, vdata)
    return es


@functools.partial(jax.jit, static_argnums=(0, 1))
def _dist_step(prog, mesh, graph, es, vdata):
    # vdata is an argument, so that one compiled step serves every job
    from repro.core.distributed import make_dist_hybrid_step
    return make_dist_hybrid_step(prog, mesh, axes=tuple(mesh.axis_names),
                                 vdata=vdata)(graph, es)
