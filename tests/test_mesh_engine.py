"""``run_hybrid`` on a graph placed over a 2x2 mesh: the executor's device
loop finds the mesh on the graph's leaves and runs the init and the whole
loop as one shard_map over it, with every Pallas call on one block of
partitions.  Its state, iterations and every counter equal the
one-device run's bit for bit, and SSSP agrees with the float64 Dijkstra
reference.

The mesh needs four devices, which the CPU backend gives only to a
process that asks before JAX starts: every run happens once, in a
subprocess of its own, and the tests read what it printed.  The CPU's
partitioner would accept a Pallas call outside a shard_map, the chip's
does not; the jaxpr test stands in for the chip's compiler.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_BODY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import glob
import json
import re
import tempfile

import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from bench.gen.kronecker import kronecker_edges
from bench.gen.roots import sample_roots
from bench.reference import sssp as sssp_ref
from repro.core import build_partitioned_graph, hash_partition, run_hybrid
from repro.core.apps import WCC, MultiSourceMonotone
from repro.core.distributed import _es_specs, shard0_specs
from repro.core.graph import unpack_vertex
from repro.exec import driver
from repro.exec.driver import run_engine
from repro.exec.policy import hybrid_policy
from repro.launch.mesh import make_host_mesh

MAX_ITERS = 100_000
edges, weights, n = kronecker_edges(7, 16, [0.57, 0.19, 0.19, 0.05], 1)
graph = build_partitioned_graph(edges, n, hash_partition(n, 8, seed=3),
                                weights=weights, ell_base_slices=16,
                                edge_blocks=4)
mesh = make_host_mesh(2, 2)
axes = tuple(mesh.axis_names)
placed = jax.device_put(graph, jax.tree.map(
    lambda s: NamedSharding(mesh, s), shard0_specs(graph, axes)))
roots = [int(r) for r in sample_roots(edges, n, 2, 11)]


def sources(root):
    return {"sources": jnp.asarray([root], jnp.int32)}


def run(g, prog, vdata, **kw):
    es, iters = run_hybrid(g, prog, vdata, **kw)
    c = es.counters
    return es, {
        "state": [np.asarray(x).tobytes().hex()
                  for x in jax.tree.leaves(es.state)],
        "iterations": iters,
        "counters": {f: np.asarray(getattr(c, f)).tolist()
                     for f in ("iterations", "net_messages",
                               "net_local_messages", "mem_messages",
                               "pseudo_supersteps")},
        "devices": sorted({len(x.sharding.device_set)
                           for x in jax.tree.leaves(es)})}


def walk(jaxpr, inside=False):
    # every equation, nested ones too, with whether a shard_map holds it
    for eqn in jaxpr.eqns:
        yield eqn, inside
        mapped = inside or eqn.primitive.name == "shard_map"
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(x, jcore.ClosedJaxpr):
                    x = x.jaxpr
                if isinstance(x, jcore.Jaxpr):
                    yield from walk(x, mapped)


def pallas_calls(jaxpr):
    # for each pallas_call equation: whether a shard_map holds it
    return [inside for eqn, inside in walk(jaxpr)
            if eqn.primitive.name == "pallas_call"]


def has_shard_map(jaxpr):
    return any(eqn.primitive.name == "shard_map" for eqn, _ in walk(jaxpr))


def collectives(loop, *args):
    # (kind, op_name) of each all-gather and all-reduce the compiler kept
    found = []
    for line in loop.lower(*args).compile().as_text().splitlines():
        kind = re.search(r"= .*?(all-reduce|all-gather)(-start)?\\(", line)
        if kind:
            name = re.search(r'op_name="([^"]*)"', line)
            found.append([kind.group(1), name.group(1) if name else ""])
    return found


def profiled(fn):
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(d, profiler_options=opts):
            fn()
        [path] = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                           recursive=True)
        data = ProfileData.from_file(path)
        return [(ev.name, dict(ev.stats)) for plane in data.planes
                for line in plane.lines for ev in line.events]


out = {"mesh_found": driver.graph_mesh(placed) is mesh,
       "one_device_mesh": driver.graph_mesh(graph) is None}

# the slot degrees over the mesh: each device holds its partitions' rows of
# the count of valid local-ELL slots, over every bin, by source
size = graph.n_partitions * graph.vp
want = sum(np.bincount(np.asarray(s.flat_idx)[np.asarray(s.msk).reshape(
    -1, s.kb)], minlength=size) for s in graph.local_ell).reshape(
    graph.n_partitions, graph.vp)
deg = placed.local_out_degree
out["local_out_degree"] = {
    "devices": len(deg.sharding.device_set),
    "rows": sorted(int(x.data.shape[0]) for x in deg.addressable_shards),
    "equal": all(np.array_equal(np.asarray(x.data), want[x.index])
                 for x in deg.addressable_shards)
    and np.array_equal(np.asarray(deg), want) and int(want.sum()) > 0}

# bit for bit: SSSP on the ELL and the dense path, and WCC
cases = {"sssp-ell": (lambda: MultiSourceMonotone(lanes=1,
                                                  semiring="min_add"),
                      sources(roots[0]), True),
         "sssp-dense": (lambda: MultiSourceMonotone(lanes=1,
                                                    semiring="min_add"),
                        sources(roots[0]), False),
         "wcc-ell": (WCC, None, True)}
out["pairs"] = {}
for name, (make, vdata, use_ell) in cases.items():
    _, one = run(graph, make(), vdata, use_ell=use_ell)
    _, mesh_run = run(placed, make(), vdata, use_ell=use_ell)
    out["pairs"][name] = {"one": one, "mesh": mesh_run}

# a state seeded by the caller, placed over the mesh
policy = hybrid_policy()
prog = MultiSourceMonotone(lanes=1, semiring="min_add")
es0 = policy.init(graph, prog, sources(roots[1]))
seeded = run_engine(placed, prog, policy, sources(roots[1]),
                    es=jax.device_put(es0, jax.tree.map(
                        lambda s: NamedSharding(mesh, s),
                        _es_specs(es0, axes))), device_loop=True)
fresh = run_engine(graph, prog, policy, sources(roots[1]), device_loop=True)
out["seeded_equal"] = all(
    np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(seeded.es), jax.tree.leaves(fresh.es)))

# two roots on one program object: the loop is traced once
prog = MultiSourceMonotone(lanes=1, semiring="min_add")
got = {}
events = profiled(lambda: got.update(
    {r: run_hybrid(placed, prog, sources(r))[0] for r in roots}))
out["loop_traces"] = sum(n == "engine.loop_trace" for n, _ in events)
out["dispatch"] = [{"cached": bool(s["cached"]), "chips": int(s["chips"])}
                   for n, s in events if n == "engine.dispatch"]
ref = sssp_ref.distances(edges, weights, n, roots)
out["reference"] = [sssp_ref.compare(unpack_vertex(placed,
                                                   got[r].state["val"])[:, 0],
                                     d)
                    for r, d in zip(roots, ref)]

# the loop's jaxpr on the mesh and on one device, and its cache keys
loop, _ = driver.device_loop_jit(prog, policy, MAX_ITERS, True, mesh)
jaxpr = jax.make_jaxpr(loop)(placed, sources(roots[0]), None).jaxpr
out["mesh_pallas"] = pallas_calls(jaxpr)
out["mesh_shard_map"] = has_shard_map(jaxpr)
out["collectives"] = collectives(loop, placed, sources(roots[0]), None)
events = profiled(lambda: run_hybrid(graph, prog, sources(roots[0])))
out["one_dispatch"] = [{"cached": bool(s["cached"]), "chips": int(s["chips"])}
                       for n, s in events if n == "engine.dispatch"]
loop, _ = driver.device_loop_jit(prog, policy, MAX_ITERS, True)
jaxpr = jax.make_jaxpr(loop)(graph, sources(roots[0]), None).jaxpr
out["one_pallas"] = len(pallas_calls(jaxpr))
out["one_shard_map"] = has_shard_map(jaxpr)
keys = driver._JITS[id(prog)]
out["keys"] = len(keys)
out["one_key"] = ("loop", policy, MAX_ITERS, True) in keys
out["mesh_key"] = ("loop", policy, MAX_ITERS, True, mesh) in keys
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_run():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(REPO), str(REPO / "src")])}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_BODY)],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_the_mesh_is_read_from_the_graph_leaves(mesh_run):
    assert mesh_run["mesh_found"] and mesh_run["one_device_mesh"]


@pytest.mark.parametrize("case", ["sssp-ell", "sssp-dense", "wcc-ell"])
def test_mesh_run_equals_one_device_bit_for_bit(mesh_run, case):
    """State, iterations, and every counter, ``pseudo_supersteps`` per
    partition included; the mesh run's state lies on all four devices."""
    one, mesh = (mesh_run["pairs"][case][k] for k in ("one", "mesh"))
    assert one["iterations"] == mesh["iterations"] > 1
    assert one["state"] == mesh["state"]
    assert one["counters"] == mesh["counters"]
    assert one["counters"]["net_messages"] > 0
    assert one["devices"] == [1] and mesh["devices"] == [4]


def test_mesh_devices_hold_their_partitions_slot_degrees(mesh_run):
    """The graph's local out-degree leaf, which the fused local phase
    counts messages with, splits on dim 0: two partitions' rows a device,
    each equal to its partitions' local-ELL slot counts."""
    assert mesh_run["local_out_degree"] == {"devices": 4, "rows": [2] * 4,
                                            "equal": True}


def test_mesh_run_from_a_seeded_state_equals_one_device(mesh_run):
    assert mesh_run["seeded_equal"]


def test_mesh_sssp_matches_the_dijkstra_reference(mesh_run):
    assert len(mesh_run["reference"]) == 2
    for r in mesh_run["reference"]:
        assert r["unreached"] == 0 and r["rel_err"] <= 1e-4


def test_every_pallas_call_on_the_mesh_is_inside_a_shard_map(mesh_run):
    calls = mesh_run["mesh_pallas"]
    assert calls and all(calls), calls
    assert mesh_run["mesh_shard_map"]


def test_mesh_collectives_run_under_their_phase_scopes(mesh_run):
    """The export table's all-gather under ``exchange``, the counters'
    psum under ``message_accounting`` (after the init and in each step),
    and the halt vote in the loop's condition."""
    gathers = [n for k, n in mesh_run["collectives"] if k == "all-gather"]
    reduces = [n for k, n in mesh_run["collectives"] if k == "all-reduce"]
    assert gathers and all("/exchange/" in n for n in gathers), gathers
    counted = [n for n in reduces if "/message_accounting/" in n]
    assert any("/while/body/" in n for n in counted), reduces
    assert any("/while/" not in n for n in counted), reduces
    assert [n for n in reduces if n not in counted] == [
        n for n in reduces if "/while/cond/" in n] != [], reduces


def test_a_second_root_on_the_mesh_traces_nothing(mesh_run):
    assert mesh_run["loop_traces"] == 1
    assert mesh_run["dispatch"] == [{"cached": False, "chips": 4},
                                    {"cached": True, "chips": 4}]


def test_one_device_graph_keeps_its_path_and_key(mesh_run):
    """No shard_map in its jaxpr, today's cache key beside the mesh's, and
    ``engine.dispatch`` says one chip."""
    assert mesh_run["one_pallas"] > 0 and not mesh_run["one_shard_map"]
    assert mesh_run["one_key"] and mesh_run["mesh_key"]
    assert mesh_run["keys"] == 2
    assert mesh_run["one_dispatch"] == [{"cached": False, "chips": 1}]
