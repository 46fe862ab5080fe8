"""The cell of BENCHMARK.json cut to a size a CPU test run can hold, and
two more on its configuration: SSSP on a lattice with bfs partitions, and
PageRank, for the generator and the job kind that no cell runs yet."""

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
    if "roots" in wl.traffic:
        wl.traffic["roots"] = 8
    return wl


def run_tiny(monkeypatch, name: str, seed: int, runner=harness.run_hybrid,
             trace: bool = False) -> dict:
    """One run of the tiny cell on the CPU, past the harness's look for a
    chip, with no compile cache and the CPU given the v5e's peaks."""
    monkeypatch.setitem(hardware.HARDWARE, "cpu",
                        hardware.HARDWARE["TPU v5 lite"])
    monkeypatch.setattr(harness, "CACHE", harness.CACHE / "tests")
    return harness.run_cell(tiny_workload(name), seed, 0.0, trace,
                            jax.devices("cpu")[:1], runner=runner)
