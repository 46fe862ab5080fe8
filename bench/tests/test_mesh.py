"""A cell on a 2x2 mesh of four chips, taken from data alone: the graph
placed over the mesh, each job's inputs replicated, and a cell whose
``chips``, ``mesh``, partitions and edge blocks disagree refused before
any work.

The mesh run needs four devices, which the CPU backend gives only to a
process that asks before JAX starts: it runs in a subprocess of its own,
once for the module, through ``run_cell`` with the stand-in for
``run_hybrid`` on a placed graph (``tiny.mesh_runner``), and beside it
the same graph's run on one device through the program's ``run_hybrid``.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.tests.tiny import MESH, tiny_workload

REPO = Path(__file__).resolve().parents[2]
SEED = 2**31 + 307

_BODY = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
import pytest
from bench import harness
from bench.tests import tiny

jobs = {{}}


def recording(side, runner):
    def run(graph, prog, vdata):
        es = runner(graph, prog, vdata)
        c = harness._counters(es)
        jobs.setdefault(side, []).append({{
            "val": np.asarray(es.state["val"]).tobytes().hex(),
            "counters": {{k: np.asarray(v).tolist() for k, v in c.items()}},
            "graph_devices": sorted({{len(leaf.sharding.device_set)
                                     for leaf in jax.tree.leaves(graph)}}),
            "vdata_devices": sorted({{len(leaf.sharding.device_set)
                                     for leaf in jax.tree.leaves(vdata)}})}})
        return es
    return run


one = tiny.tiny_workload(tiny.MESH)
one.chips = 1
del one.config["mesh"]
out = {{}}
for side, wl, runner in (("mesh", tiny.tiny_workload(tiny.MESH),
                          tiny.mesh_runner),
                         ("one", one, harness.run_hybrid)):
    with pytest.MonkeyPatch.context() as mp:
        out[side] = tiny.run_tiny_workload(mp, wl, {SEED},
                                           recording(side, runner))
out["jobs"] = jobs

# a built graph's leaves, committed to the CPU device, placed on the mesh
place = harness.placement(tiny.tiny_workload(tiny.MESH), jax.devices()[:4])
leaves = {{"f": np.arange(32.0).reshape(8, 4), "i": np.arange(8)}}
built = jax.device_put(leaves, jax.devices()[0])
with harness.CompileCounter() as cc:
    jax.block_until_ready(place.graph(built))
out["placement_compiles"] = cc.compiles
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_run():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(REPO), str(REPO / "src")])}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_BODY)],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_mesh_cell_is_correct_on_four_devices(mesh_run):
    r = mesh_run["mesh"]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert r["device"]["count"] == 4


def test_mesh_cell_places_every_graph_leaf_on_all_four(mesh_run):
    # the warm-up job and every window job
    jobs = mesh_run["jobs"]["mesh"]
    assert len(jobs) == mesh_run["mesh"]["attempted"] + 1
    assert all(j["graph_devices"] == [4] for j in jobs)
    assert all(j["vdata_devices"] == [4] for j in jobs)
    assert all(j["graph_devices"] == [1]
               for j in mesh_run["jobs"]["one"])


def test_mesh_placement_compiles_nothing(mesh_run):
    """The graph goes to the mesh from host memory: resharding leaves
    committed to the CPU device would compile a program for each shape
    (about 20 s of set-up for ``g500-s16`` on a 2x2 v5e)."""
    assert mesh_run["placement_compiles"] == 0


def test_mesh_cell_equals_the_one_device_run_bit_for_bit(mesh_run):
    mesh, one = mesh_run["jobs"]["mesh"], mesh_run["jobs"]["one"]
    assert len(mesh) == len(one)
    for a, b in zip(mesh, one):
        assert a["val"] == b["val"]
        assert a["counters"] == b["counters"]
    assert mesh_run["one"]["checks"] == mesh_run["mesh"]["checks"]


def _bad(chips=4, mesh=(2, 2), axes=("data", "model"), edge_blocks=4,
         partitions=8):
    wl = tiny_workload(MESH)
    wl.chips = chips
    wl.config["partitions"] = partitions
    wl.config["build"]["edge_blocks"] = edge_blocks
    if mesh is None:
        del wl.config["mesh"]
    else:
        wl.config["mesh"] = {"shape": list(mesh), "axes": list(axes)}
    return wl


BAD = {
    "four_chips_no_mesh": (_bad, {"mesh": None}, "'mesh'"),
    "mesh_smaller_than_chips": (_bad, {"mesh": (2, 1)}, "'mesh'"),
    "mesh_larger_than_chips": (_bad, {"chips": 2, "edge_blocks": 2},
                               "'mesh'"),
    "axes_not_shape": (_bad, {"axes": ("data",)}, "'mesh'"),
    "edge_blocks_not_multiple": (_bad, {"edge_blocks": 2},
                                 "'build.edge_blocks'"),
    "partitions_not_multiple": (_bad, {"partitions": 6}, "'partitions'"),
}


@pytest.mark.parametrize("case", sorted(BAD))
def test_bad_mesh_cell_refused_before_any_work(monkeypatch, case):
    make, kw, key = BAD[case]
    wl = make(**kw)

    def no_work(*a, **k):
        raise AssertionError("the run began work on a bad cell")

    monkeypatch.setattr(harness, "generate", no_work)
    with pytest.raises(ValueError) as e:
        harness.run_cell(wl, SEED, 0.0, False, [object()] * wl.chips,
                         runner=no_work)
    assert wl.config_file in str(e.value) and key in str(e.value)


def test_bad_mesh_cell_refused_as_it_is_loaded(tmp_path):
    """``bench/run.py`` loads the cell before it looks for chips: a
    four-chip cell whose configuration names no mesh goes no further."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    wl = spec["workloads"][0]
    spec["workloads"] = [{**wl, "name": "bad.cell", "chips": 4}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for sub, name in (("configs", f"{wl['config']}.json"),
                      ("traffic", f"{wl['traffic']}.json")):
        (tmp_path / "bench" / sub).mkdir(parents=True)
        (tmp_path / "bench" / sub / name).write_bytes(
            (REPO / "bench" / sub / name).read_bytes())
    (tmp_path / "bench" / "limits").mkdir()
    (tmp_path / "bench" / "limits" / "bad.cell.json").write_text("{}")
    with pytest.raises(ValueError, match=r"graph500-s16\.json.*'mesh'"):
        harness.load_workload("bad.cell", tmp_path)


def test_one_chip_cell_has_no_mesh():
    wl = harness.load_workload("g500-s16.sssp")
    assert harness.mesh_spec(wl) is None
    place = harness.placement(wl, [object()])
    assert place.mesh is None
    tree = {"sources": np.arange(3)}
    assert place.replicated(tree) is tree
