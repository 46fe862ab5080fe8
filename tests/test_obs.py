"""Observability subsystem: the injectable clock, the metrics registry,
span tracing through the executor, the executor's own spans and the phase
scopes in a profiler trace, the phased profiler's bit-parity with the
fused engines, Chrome trace-event export, and the zero-cost guarantee for
the disabled path."""

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core import (bfs_partition, build_partitioned_graph,
                        hash_partition, run_bsp, run_hybrid)
from repro.core.apps import SSSP, IncrementalPageRank
from repro.core.apps.pagerank import pagerank_edge_weights
from repro.data.graphs import grid_graph, rmat_graph
from repro.exec.policy import EnginePolicy, make_policy
from repro.exec.driver import run_engine
from repro.ft import FaultInjector, FaultPlan, run_hybrid_ft
from repro.obs import clock as obs_clock
from repro.obs import span
from repro.obs.export import chrome_trace, profile_blob, write_chrome_trace
from repro.obs.metrics import (MetricsRegistry, load_registry,
                               record_engine_counters, save_registry)
from repro.obs.trace import (TraceHook, Tracer, exchange_bytes, phased_run,
                             trace_hooks, wrap_hooks)


@pytest.fixture(scope="module")
def road():
    edges, w, n = grid_graph(6, 40, seed=3)
    part = bfs_partition(edges, n, 4, seed=1)
    return build_partitioned_graph(edges, n, part, weights=w)


@pytest.fixture(scope="module")
def web():
    edges, n = rmat_graph(200, avg_degree=5, seed=7)
    part = hash_partition(n, 4, seed=2)
    w = pagerank_edge_weights(edges, n)
    return build_partitioned_graph(edges, n, part, weights=w)


def assert_counters_equal(a, b):
    for f in ("iterations", "net_messages", "net_local_messages",
              "mem_messages"):
        assert int(getattr(a.counters, f)) == int(getattr(b.counters, f)), f
    np.testing.assert_array_equal(np.asarray(a.counters.pseudo_supersteps),
                                  np.asarray(b.counters.pseudo_supersteps))


# ---------------------------------------------------------------------------
# clock
# ---------------------------------------------------------------------------

def test_fake_clock_drives_heartbeat_without_explicit_param():
    """Satellite: ft/ reads the one installable clock — no monkeypatching,
    no clock= threading."""
    from repro.ft import HeartbeatMonitor

    with obs_clock.fake() as fc:
        mon = HeartbeatMonitor(3, suspect_after=5.0, fail_after=15.0)
        fc.advance(6.0)
        mon.beat(0)
        assert mon.sweep() == []          # suspect only, nobody failed
        fc.advance(10.0)
        assert sorted(mon.sweep()) == [1, 2]
    assert obs_clock._monotonic is not fc    # backend restored on exit


def test_fake_clock_drives_straggler_deadline():
    from repro.ft import StragglerMitigator

    with obs_clock.fake() as fc:
        mit = StragglerMitigator(min_deadline=1.0)
        mit.issue(7, replica=0)
        fc.advance(10.0)
        assert [w.work_id for w in mit.overdue()] == [7]
        assert mit.redispatches == 1


def test_fake_clock_drives_checkpoint_save_billing(road, tmp_path):
    from repro.checkpoint import AsyncCheckpointer
    from repro.exec.iteration import init_hybrid

    es = init_hybrid(road, SSSP(source=0), None)
    with obs_clock.fake() as fc:
        ck = AsyncCheckpointer(str(tmp_path / "c"), keep=2)
        real = obs_clock._perf_counter       # the fake backend
        assert real is fc
        ck.save(1, es)
        ck.wait()
        ck.close()
        # the fake clock never advanced, so the billed snapshot time is 0
        assert ck.save_seconds == 0.0


def test_clock_install_returns_previous():
    prev = obs_clock.install(lambda: 42.0)
    try:
        assert obs_clock.monotonic() == 42.0
    finally:
        obs_clock.install(*prev)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_round_trip(tmp_path):
    reg = MetricsRegistry()
    reg.inc("a.count", 3, unit="msgs")
    reg.set_gauge("a.vec", [1, 2, 3])
    reg.set_gauge("a.scalar", 2.5, unit="s")
    for v in (0.001, 0.5, 10.0, 2000.0):
        reg.observe("a.hist", v, unit="s")
    path = str(tmp_path / "m.json")
    save_registry(reg, path)
    back = load_registry(path)
    assert back.names() == reg.names()
    assert back.value("a.count") == 3.0
    assert back.value("a.vec") == [1.0, 2.0, 3.0]
    h = back.histogram("a.hist")
    assert h.count == 4 and h.min == 0.001 and h.max == 2000.0
    assert abs(h.mean - (0.001 + 0.5 + 10.0 + 2000.0) / 4) < 1e-9
    assert sum(h.counts) == 4


def test_registry_kind_collision_and_negative_inc():
    reg = MetricsRegistry()
    reg.inc("x")
    with pytest.raises(ValueError, match="counter"):
        reg.set_gauge("x", 1.0)
    with pytest.raises(ValueError, match="negative"):
        reg.inc("x", -1)


def test_record_engine_counters(road):
    es, _ = run_hybrid(road, SSSP(source=0), device_loop=False)
    reg = MetricsRegistry()
    record_engine_counters(reg, es.counters)
    assert reg.value("engine.iterations") == float(es.counters.iterations)
    vec = reg.value("engine.pseudo_supersteps")
    assert len(vec) == road.n_partitions
    np.testing.assert_array_equal(
        np.asarray(vec), np.asarray(es.counters.pseudo_supersteps, float))


# ---------------------------------------------------------------------------
# tracing through the executor
# ---------------------------------------------------------------------------

def test_trace_hook_counters_bit_identical(road):
    """The stepwise TraceHook observes; it must not perturb: final state
    and every paper counter match the untraced run bit-for-bit."""
    prog = SSSP(source=0)
    policy = make_policy("hybrid")
    ref = run_engine(road, prog, policy, None)

    tracer = Tracer()
    ctx = run_engine(road, prog, policy, None, hooks=trace_hooks(tracer))
    np.testing.assert_array_equal(np.asarray(ctx.es.state["dist"]),
                                  np.asarray(ref.es.state["dist"]))
    assert_counters_equal(ctx.es, ref.es)

    steps = [s for s in tracer.spans if s.cat == "superstep"]
    assert len(steps) == ctx.iteration
    assert all(s.dur >= 0 and s.args["exchange_bytes"] >= 0 for s in steps)
    assert sum(s.args["barriers"] for s in steps) == ctx.iteration


def _profiled(tmp_path, fn):
    """Run ``fn`` inside a ``jax.profiler`` session -> (its result, the
    trace's events as (name, start ns, end ns, stats))."""
    from jax.profiler import ProfileData

    out_dir = str(tmp_path / "profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host annotations, not every call
    with jax.profiler.trace(out_dir, profiler_options=opts):
        out = fn()
    [path] = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                       recursive=True)
    data = ProfileData.from_file(path)
    return out, [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                  dict(ev.stats))
                 for plane in data.planes for line in plane.lines
                 for ev in line.events]


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_device_loop_records_engine_spans(road, tmp_path):
    """A device-loop run carries no hooks; its own spans land in the
    profiler trace, nested engine.run > {dispatch > loop_trace, wait}, in
    that order.  Its init runs inside the jitted loop, so it opens no
    engine.init of its own."""
    assert trace_hooks(Tracer(), device_loop=True) == ()
    (es, iters), events = _profiled(
        tmp_path, lambda: run_hybrid(road, SSSP(source=0)))
    assert iters > 0
    [run] = _named(events, "engine.run")
    assert run[3]["engine"] == "hybrid"
    assert not _named(events, "engine.init")
    [dispatch] = _named(events, "engine.dispatch")
    [loop_trace] = _named(events, "engine.loop_trace")
    [wait] = _named(events, "engine.wait")
    assert all(_inside(s, run) for s in (dispatch, wait))
    assert _inside(loop_trace, dispatch)
    assert dispatch[2] <= wait[1]

    with pytest.raises(ValueError, match="device_loop"):
        run_engine(road, SSSP(source=0), make_policy("hybrid"), None,
                   hooks=(TraceHook(Tracer()),), device_loop=True)


def test_loop_trace_span_once_per_trace_of_the_loop(road, tmp_path):
    """engine.loop_trace opens inside the traced function: one span for
    each time JAX traces the device loop (counted by the step's own
    trace-time side effect), none for a run that does not trace it."""
    base = make_policy("hybrid")
    traced = []

    def step(g, prog, es, vdata):
        traced.append(1)
        return base.step(g, prog, es, vdata)

    policy = EnginePolicy(base.name, base.init, step)
    _, events = _profiled(tmp_path, lambda: [
        run_engine(road, SSSP(source=0), policy, None, device_loop=True)
        for _ in range(2)])
    assert len(_named(events, "engine.run")) == 2
    assert len(_named(events, "engine.loop_trace")) == len(traced) > 0


def test_host_loop_records_run_and_init_spans(road, tmp_path):
    """The host-driven loop has no dispatch or wait spans of its own, but
    its run and init spans land in the trace like the device loop's."""
    _, events = _profiled(tmp_path, lambda: run_hybrid(
        road, SSSP(source=0), device_loop=False))
    [run] = _named(events, "engine.run")
    [init] = _named(events, "engine.init")
    assert _inside(init, run)
    assert not _named(events, "engine.dispatch")


@pytest.mark.parametrize("engine,scopes", [
    ("hybrid", ("exchange", "remote_delivery", "global_phase",
                "local_phase", "message_accounting")),
    ("bsp", ("exchange", "bsp_delivery", "bsp_compute",
             "message_accounting"))])
def test_lowered_step_carries_phase_scopes(road, engine, scopes):
    """Every phase of the step is a named scope in the lowered program's
    op names, so a device trace can attribute each op to its phase."""
    prog = SSSP(source=0)
    policy = make_policy(engine)
    es = policy.init(road, prog, None)
    text = jax.jit(lambda g, e: policy.step(g, prog, e, None)).lower(
        road, es).as_text(debug_info=True)
    for scope in scopes:
        assert f"/{scope}/" in text, scope


def test_span_is_a_profiler_annotation(tmp_path):
    """repro.obs.span and Tracer.span land in a profiler trace with their
    args; outside a profiler session span is a plain context manager."""
    with span("obs.outside", k=1):
        pass
    tracer = Tracer()

    def record():
        with span("obs.direct", k=7):
            with tracer.span("obs.tracer", cat="test", n=3):
                pass

    _, events = _profiled(tmp_path, record)
    [direct] = _named(events, "obs.direct")
    [inner] = _named(events, "obs.tracer")
    assert direct[3]["k"] == 7 and inner[3]["n"] == 3
    assert _inside(inner, direct)
    assert not _named(events, "obs.outside")
    [kept] = tracer.spans
    assert kept.name == "obs.tracer" and kept.args == {"n": 3}


def test_disabled_tracer_contributes_nothing(road):
    assert trace_hooks(None) == ()
    assert trace_hooks(Tracer(enabled=False)) == ()
    t = Tracer(enabled=False)
    with t.span("x"):
        t.instant("y")
    assert t.spans == []
    # wrap_hooks is identity when tracing is off
    h = TraceHook(Tracer())
    assert wrap_hooks(None, (h,)) == (h,)


def test_hot_path_never_imports_tracing():
    """Zero-cost disabled path: importing the engines and the executor must
    not pull in the tracing/metrics modules."""
    code = (
        "import sys\n"
        "import repro.core.runtime, repro.core.distributed\n"
        "import repro.exec.driver, repro.exec.iteration\n"
        "import repro.ft.driver, repro.serve.engine\n"
        "bad = [m for m in sys.modules if m.startswith('repro.obs.')\n"
        "       and m != 'repro.obs.clock' and m != 'repro.obs.metrics']\n"
        "assert 'repro.obs.trace' not in sys.modules, 'trace imported'\n"
        "assert 'repro.obs.export' not in sys.modules, 'export imported'\n"
        "assert not [m for m in bad if m != 'repro.obs.metrics'], bad\n"
    )
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))),
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


def test_exchange_bytes_zero_when_nothing_to_send(road):
    """After quiescence no vertex is exporting: the accounted wire bytes
    for a further exchange are exactly zero."""
    es, _ = run_hybrid(road, SSSP(source=0), device_loop=False)
    assert exchange_bytes(road, es) == 0


# ---------------------------------------------------------------------------
# phased profiler
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["bsp", "hybrid"])
def test_phased_run_bit_identical(road, engine):
    """The phase decomposition is the step body: final state, iteration
    count, and every counter are bit-identical to the fused engines."""
    runner = {"bsp": run_bsp, "hybrid": run_hybrid}[engine]
    kwargs = {"device_loop": False} if engine == "hybrid" else {}
    es_ref, it_ref = runner(road, SSSP(source=0), **kwargs)

    res = phased_run(road, SSSP(source=0), engine, None)
    assert res.iterations == it_ref
    np.testing.assert_array_equal(np.asarray(res.es.state["dist"]),
                                  np.asarray(es_ref.state["dist"]))
    assert_counters_equal(res.es, es_ref)
    assert len(res.records) == it_ref
    assert all(0.0 <= r.local_compute_fraction <= 1.0 for r in res.records)


def test_phased_hybrid_fewer_barriers_than_bsp(web):
    """The paper's claim on one shared graph: hybrid converges in fewer
    global barriers (and fewer exchanged bytes) than BSP."""
    prog = IncrementalPageRank(tolerance=1e-4)
    b = phased_run(web, prog, "bsp", None)
    h = phased_run(web, prog, "hybrid", None)
    assert h.total_barriers < b.total_barriers
    assert h.total_exchange_bytes < b.total_exchange_bytes


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------

def _schema_check(doc):
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert evs, "no events"
    for e in evs:
        assert e["ph"] in ("X", "i")
        for field in ("name", "cat", "ts", "pid", "tid"):
            assert field in e, f"missing {field}"
        assert isinstance(e["ts"], (int, float))
        if e["ph"] == "X":
            assert e["dur"] >= 0
    # timestamps monotone within every (pid, tid) track
    by_track = {}
    for e in evs:
        by_track.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    for ts in by_track.values():
        assert ts == sorted(ts)
    return evs


def test_chrome_trace_schema(road, tmp_path):
    tracer = Tracer()
    tracer.name_track(0, "hybrid")
    run_engine(road, SSSP(source=0), make_policy("hybrid"), None,
               hooks=trace_hooks(tracer))
    path = str(tmp_path / "trace.json")
    write_chrome_trace(tracer, path)
    with open(path) as f:
        doc = json.load(f)
    evs = _schema_check(doc)
    assert any(e["cat"] == "superstep" for e in evs)
    names = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert names and names[0]["args"]["name"] == "hybrid"


def test_ft_recovery_span_in_trace(road, tmp_path):
    """A kill-and-recover FT run leaves the recovery annotated in the
    trace: a cat='ft' span with the rollback accounting, bracketed by
    superstep spans, all schema-valid."""
    tracer = Tracer()
    inj = FaultInjector(FaultPlan.kill_at(3, worker=1), n_workers=4)
    res = run_hybrid_ft(road, SSSP(source=0), ckpt_dir=str(tmp_path / "c"),
                        n_workers=4, injector=inj, tracer=tracer)
    assert len(res.recoveries) == 1

    [rec] = [s for s in tracer.spans if s.cat == "ft"]
    assert rec.name == "recovery"
    assert rec.args["failed_workers"] == [1]
    assert rec.args["iterations_lost"] >= 0
    assert rec.args["bytes_read"] > 0
    # the hooks' own work is visible too (checkpoint saves, fault sweeps)
    assert any(s.cat == "hook" and "CheckpointHook" in s.name
               for s in tracer.spans)
    assert any(s.cat == "superstep" for s in tracer.spans)
    _schema_check(chrome_trace(tracer))


def test_ft_registry_populated_and_flags_from_registry(road):
    """run_hybrid_ft fills the registry and derives straggler flags from
    its gauges; an absurdly low factor flags every partition."""
    reg = MetricsRegistry()
    res = run_hybrid_ft(road, SSSP(source=0), registry=reg,
                        straggler_factor=0.01)
    assert res.registry is reg
    assert reg.value("engine.iterations") == float(res.iterations)
    assert reg.value("ft.recoveries") == 0.0
    assert len(res.straggler_flags) > 0
    flagged = {f.partition for f in res.straggler_flags}
    counts = np.asarray(reg.value("engine.pseudo_supersteps"))
    med = max(float(np.median(counts)), 1.0)
    assert flagged == set(np.flatnonzero(counts > 0.01 * med).tolist())


def test_profile_blob_shape(road):
    tracer = Tracer()
    res = phased_run(road, SSSP(source=0), "hybrid", None, tracer=tracer)
    reg = MetricsRegistry()
    record_engine_counters(reg, res.es.counters)
    blob = profile_blob(tracer=tracer, registry=reg, runs=[res],
                        meta={"fixture": "road"})
    assert blob["schema"] == "repro.obs.profile/1"
    eng = blob["engines"]["hybrid"]
    assert eng["iterations"] == res.iterations
    assert len(eng["supersteps"]) == res.iterations
    assert eng["total_barriers"] == res.total_barriers
    json.dumps(blob)          # fully JSON-serializable
    _schema_check(blob["trace"])
