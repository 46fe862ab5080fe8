"""One run of one cell: set-up, a measured window of jobs, the check.

Everything a cell needs is found by name: the workload in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic in ``bench/traffic/<traffic>.json``, the limits of its check
in ``bench/limits/<workload>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.

The run, in order: generate the graph from the seed and build it with the
program's own partitioner and builder; put it on the cell's chips; run
one warm-up job; run jobs back to back until ``seconds`` have passed (the
job in flight finishes); read the peak memory; compare the window's jobs
with the plain reference; report.

A cell on more than one chip names its mesh in its configuration,
``"mesh": {"shape": [2, 2], "axes": ["data", "model"]}``: the graph is
placed over it partition-sharded on dim 0, one block of partitions per
chip, with the program's own specs, and each job's inputs replicated.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench.jobs import JOBS, message_bytes

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
CACHE = BENCH / ".cache"
JOB_SPAN = "bench.job"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Workload:
    name: str
    chips: int
    config: dict
    config_file: str
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_workload(name: str, root: Path = REPO) -> Workload:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    spec = _load(root / "BENCHMARK.json")
    try:
        wl = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    bench = root / "bench"

    def mine(metric):
        return name in metric.get("workloads", [name])

    config_file = f"bench/configs/{wl['config']}.json"
    out = Workload(
        name=name, chips=int(wl["chips"]), config=_load(root / config_file),
        config_file=config_file,
        traffic=_load(bench / "traffic" / f"{wl['traffic']}.json"),
        limits=_load(bench / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)])
    mesh_spec(out)
    return out


def mesh_spec(wl: Workload) -> dict | None:
    """The cell's ``mesh`` from its configuration; None for a cell on one
    chip that names none.  A cell whose ``chips``, ``mesh``, partitions
    and edge blocks disagree raises ValueError, naming the file and key,
    before any work."""
    mesh = wl.config.get("mesh")
    where = f"{wl.config_file} (workload {wl.name!r}, chips {wl.chips})"
    if mesh is None:
        if wl.chips > 1:
            raise ValueError(f"{where}: key 'mesh' is missing; a cell on "
                             f"{wl.chips} chips places its graph over one")
        return None
    shape, axes = mesh.get("shape"), mesh.get("axes")
    if not (isinstance(shape, list) and isinstance(axes, list) and shape
            and len(shape) == len(axes)
            and all(isinstance(k, int) and k >= 1 for k in shape)):
        raise ValueError(f"{where}: key 'mesh' must be {{\"shape\": [int, "
                         f"...], \"axes\": [name, ...]}} of one length, not "
                         f"{mesh!r}")
    if math.prod(shape) != wl.chips:
        raise ValueError(f"{where}: key 'mesh' has shape {shape}, "
                         f"{math.prod(shape)} chips, where the cell's "
                         f"'chips' in BENCHMARK.json is {wl.chips}")
    blocks = wl.config.get("build", {}).get("edge_blocks", 1)
    if blocks % wl.chips:
        raise ValueError(f"{where}: key 'build.edge_blocks' is {blocks}, "
                         f"not a multiple of the cell's {wl.chips} chips")
    if wl.config["partitions"] % wl.chips:
        raise ValueError(f"{where}: key 'partitions' is "
                         f"{wl.config['partitions']}, not a multiple of "
                         f"the cell's {wl.chips} chips")
    return mesh


@dataclasses.dataclass(frozen=True)
class Placement:
    """Where a cell's arrays live: its chips, and the mesh over them for a
    cell whose configuration names one."""
    devices: tuple
    mesh: object = None

    def graph_shardings(self, graph):
        """Each graph leaf's sharding on the mesh: partition-sharded on
        dim 0 over every axis, the program's own specs."""
        import jax
        from jax.sharding import NamedSharding
        from repro.core.distributed import shard0_specs
        specs = shard0_specs(graph, tuple(self.mesh.axis_names))
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)

    def graph(self, graph):
        """The graph on the cell's chip, or over its mesh.  To the mesh it
        goes from host memory: a built leaf committed to the CPU device
        would be resharded by a program compiled for each shape."""
        import jax
        if self.mesh is None:
            return jax.device_put(graph, self.devices[0])
        return jax.device_put(jax.tree.map(np.asarray, graph),
                              self.graph_shardings(graph))

    def replicated(self, tree):
        """A job's inputs: as made (on the default device, the cell's
        chip) without a mesh, on every chip of the mesh with one."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        if self.mesh is None:
            return tree
        return jax.device_put(tree, NamedSharding(self.mesh,
                                                  PartitionSpec()))


def placement(wl: Workload, devices) -> Placement:
    """The cell's :class:`Placement` on ``devices``: the mesh its
    configuration names, laid over those chips."""
    spec = mesh_spec(wl)
    if spec is None:
        return Placement(tuple(devices))
    import jax
    from jax.sharding import AxisType
    axes = tuple(spec["axes"])
    mesh = jax.make_mesh(tuple(spec["shape"]), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=list(devices))
    return Placement(tuple(devices), mesh)


def chips(need: int):
    """The first ``need`` TPU devices; :class:`NoChip` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < need:
        raise NoChip(f"JAX sees {len(devs)} {devs[0].platform} device(s); "
                     f"this cell needs {need} TPU chip(s)")
    return devs[:need]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (unless ``JAX_COMPILATION_CACHE_DIR`` names one), every program kept,
    so that only a cell's first run in a checkout compiles."""
    import os

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Counts backend compiles and persistent-cache hits through
    ``jax.monitoring`` while it is open.  A backend compile that the
    cache answered is a hit, not a compile."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        self.backend = self.hits = 0
        self.retrieval_s = 0.0

    def _event(self, name, **_):
        if name == self.HIT:
            self.hits += 1

    def _duration(self, name, secs, **_):
        if name == self.BACKEND:
            self.backend += 1
        elif name == self.RETRIEVAL:
            self.retrieval_s += secs

    @property
    def compiles(self) -> int:
        return self.backend - self.hits

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_listener(self._event)
        monitoring.unregister_event_duration_listener(self._duration)

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.hits,
                "cache_load_s": self.retrieval_s}


def _sub_seed(seed: int, stream: int) -> int:
    """A 32-bit seed of its own for ``stream``, from any whole ``seed``."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def graph_seed(config: dict, seed: int) -> int:
    """The seed of the graph's structure and partitions: the generator's
    ``graph_seed`` where it names one, the run's ``seed`` otherwise."""
    return int(config["generator"].get("graph_seed", seed))


def generate(config: dict, seed: int):
    """The configuration's graph -> (edges, weights, n).  Where the
    generator names a ``graph_seed``, the structure comes from it, one
    graph with the same sizes in every run, and the weights from ``seed``;
    otherwise both come from ``seed``."""
    gen = config["generator"]
    shape_seed = graph_seed(config, seed)
    if gen["kind"] == "kronecker":
        from bench.gen.kronecker import kronecker_edges
        edges, weights, n = kronecker_edges(gen["scale"], gen["edge_factor"],
                                            gen["initiator"], shape_seed)
    elif gen["kind"] == "lattice":
        from bench.gen.lattice import lattice_edges
        edges, weights, n = lattice_edges(gen["rows"], gen["cols"],
                                          gen["weight_low"],
                                          gen["weight_high"], shape_seed)
    else:
        raise ValueError(f"unknown generator {gen['kind']!r}")
    if "graph_seed" in gen:
        weights = _edge_weights(len(edges), gen.get("weight_low", 0.0),
                                gen.get("weight_high", 1.0), seed)
    return edges, weights, n


def _edge_weights(n_arcs: int, low: float, high: float, seed: int):
    """One weight per undirected edge from ``seed``, uniform in ``[low,
    high)`` and the same both ways, as the generators lay out their arcs:
    every edge, then every edge reversed."""
    rng = np.random.default_rng([seed, 4])
    w = (low + (high - low) * rng.random(n_arcs // 2, dtype=np.float32)
         ).astype(np.float32)
    return np.concatenate([w, w])


def build(config: dict, edges, weights, n: int, seed: int, place):
    """The program's partitioner and builder, on the host, then the graph
    placed by ``place`` (a :class:`Placement`) -> (graph, {partition_s,
    build_s, transfer_s})."""
    import jax
    from repro.core import build_partitioned_graph
    from repro.partition import make_partition

    t = [time.perf_counter()]
    part = make_partition(config["partitioner"], edges, n,
                          config["partitions"],
                          seed=_sub_seed(graph_seed(config, seed), 3))
    t.append(time.perf_counter())
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:         # no CPU backend: build straight to device
        host = place.devices[0]
    with jax.default_device(host):
        graph = build_partitioned_graph(edges, n, part, weights=weights,
                                        **config["build"])
    t.append(time.perf_counter())
    graph = jax.block_until_ready(place.graph(graph))
    t.append(time.perf_counter())
    return graph, {"partition_s": t[1] - t[0], "build_s": t[2] - t[1],
                   "transfer_s": t[3] - t[2]}


def run_hybrid(graph, prog, vdata):
    """The timed path: one job to its fixed point, as a user runs it."""
    from repro.core import run_hybrid as _run_hybrid
    es, _ = _run_hybrid(graph, prog, vdata)
    return es


def _counters(es) -> dict:
    c = es.counters
    return {"iterations": c.iterations,
            "pseudo_supersteps": c.pseudo_supersteps,
            "net_messages": c.net_messages, "mem_messages": c.mem_messages}


def _host_counters(c: dict) -> dict:
    return {"iterations": int(c["iterations"]),
            "pseudo_supersteps": int(np.sum(np.asarray(
                c["pseudo_supersteps"], np.int64))),
            "net_messages": int(c["net_messages"]),
            "mem_messages": int(c["mem_messages"])}


def window(graph, kind, prog, seconds: float, traced: bool = False,
           runner=run_hybrid, place: Placement | None = None):
    """Jobs back to back until ``seconds`` have passed; the job in flight
    finishes; each job's inputs placed by ``place``.  -> (wall seconds,
    [(state, counters)] per job)."""
    import jax
    span = (jax.profiler.TraceAnnotation if traced
            else lambda _: contextlib.nullcontext())
    done = []
    t0 = time.perf_counter()
    while True:
        with span(JOB_SPAN):
            vdata = kind.vdata(len(done))
            if place is not None:
                vdata = place.replicated(vdata)
            es = runner(graph, prog, vdata)
            jax.block_until_ready(es.state)
        done.append((es.state[kind.state_key], _counters(es)))
        del es
        if time.perf_counter() - t0 >= seconds:
            return time.perf_counter() - t0, done


def _read_metric(path: Path, record: dict):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


def log(msg: str, **rec) -> None:
    print(f"[{msg}] " + json.dumps(rec), file=sys.stderr, flush=True)


@dataclasses.dataclass
class Prepared:
    """A cell's set-up: its graph on the chips and the inputs it came
    from."""
    wl: Workload
    seed: int
    devices: list
    place: Placement
    edges: np.ndarray
    weights: np.ndarray
    n: int
    kind: object
    prog: object
    graph: object
    split: dict


def prepare(wl: Workload, seed: int, devices, runner=run_hybrid) -> Prepared:
    """Generate, partition, build, transfer and warm up with one job."""
    import jax

    place = placement(wl, devices)
    split = {}
    t = time.perf_counter()
    edges, weights, n = generate(wl.config, seed)
    kind = JOBS[wl.traffic["job"]](wl.traffic, edges, weights, n, seed)
    weights = kind.weights(edges, weights, n)
    split["generate_s"] = time.perf_counter() - t
    graph, times = build(wl.config, edges, weights, n, seed, place)
    split.update(times)
    prog = kind.program()
    t = time.perf_counter()
    with jax.default_device(devices[0]):
        jax.block_until_ready(runner(graph, prog, place.replicated(
            kind.vdata(-1))).state)
    split["warmup_s"] = time.perf_counter() - t
    return Prepared(wl, seed, list(devices), place, edges, weights, n, kind,
                    prog, graph, split)


def check(prep: Prepared, done: list) -> dict:
    """Compare a sample of the window's jobs, drawn from the seed, with
    the plain reference -> {"failed", "worst": {number: max reading}}."""
    rng = np.random.default_rng([prep.seed, 2])
    picked = sorted(rng.choice(len(done), min(len(done),
                                              int(prep.wl.traffic["check"])),
                               replace=False).tolist())
    from repro.core.graph import unpack_vertex
    outputs = {j: unpack_vertex(prep.graph, done[j][0]) for j in picked}
    t = time.perf_counter()
    readings = prep.kind.check(outputs, prep.edges, prep.weights, prep.n)
    log("check", jobs=picked, check_s=time.perf_counter() - t)
    limits = prep.wl.limits
    return {"failed": sum(any(r[k] > lim for k, lim in limits.items())
                          for r in readings.values()),
            "worst": {k: max(r[k] for r in readings.values())
                      for k in limits}}


def run_cell(wl: Workload, seed: int, seconds: float, trace: bool,
             devices, runner=run_hybrid) -> dict:
    """One run of cell ``wl`` on ``devices``; returns the result line.
    ``runner`` is the timed path (tests and the control put another in
    its place)."""
    import jax
    from bench.hardware import peaks

    t_start = time.perf_counter()
    device = devices[0]
    with CompileCounter() as cc:
        prep = prepare(wl, seed, devices, runner)
        setup_s = time.perf_counter() - t_start
        log("setup", setup_s=setup_s, **prep.split, **cc.snapshot())

        before = cc.snapshot()
        trace_dir = CACHE / "trace"
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with jax.default_device(device):
            wall, done = window(prep.graph, prep.kind, prep.prog, seconds,
                                traced=trace, runner=runner,
                                place=prep.place)
        if trace:
            jax.profiler.stop_trace()
        after = cc.snapshot()
    log("window", jobs=len(done), wall_s=wall,
        compiles=after["compiles"] - before["compiles"],
        cache_hits=after["cache_hits"] - before["cache_hits"])

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    counters = [_host_counters(c) for _, c in done]
    mbytes = message_bytes(prep.prog, prep.graph)
    verdict = check(prep, done)
    del done, prep

    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": verdict["failed"] == 0, "attempted": len(counters),
              "failed": int(verdict["failed"])}
    if not trace:
        values = {"setup_s": setup_s, "job_s": wall / len(counters),
                  "peak_hbm_gib": peak / 2**30}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in wl.end_to_end}
    else:
        from bench.layers import load_events, reduce_layers
        from bench.trace import reduce_window
        events = load_events(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ids = [d.id for d in devices]
        reduced = reduce_window(events, JOB_SPAN, ids)
        layers = reduce_layers(events, JOB_SPAN, ids)
        del events
        if layers is not None:
            log("layers", **layers)
        record = {"jobs": counters, "trace": reduced, "layers": layers,
                  "chips": len(devices), "message_bytes": mbytes,
                  "peaks": peaks(device.device_kind)}
        metrics = {}
        for m in wl.per_layer:
            value = _read_metric(BENCH / "metrics" / f"{m['name']}.py",
                                 record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    result.update(metrics=metrics, device=dev)
    result["checks"] = {k: {"value": v, "limit": wl.limits[k]}
                        for k, v in verdict["worst"].items()}
    return result
