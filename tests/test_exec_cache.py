"""The executor's jit cache: one jitted device loop (or host step) per
program object, policy, ``max_iters`` and whether the loop runs the init,
reused by every later run with results bit-identical to a run that traces
afresh, and dropped with its program."""

import gc
import glob
import os
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bfs_partition, build_partitioned_graph, run_hybrid
from repro.core.apps import SSSP, MultiSourceMonotone
from repro.data.graphs import grid_graph
from repro.exec import driver
from repro.exec.driver import run_engine
from repro.exec.policy import EnginePolicy, hybrid_policy, make_policy


@pytest.fixture(scope="module")
def road():
    edges, w, n = grid_graph(6, 40, seed=3)
    part = bfs_partition(edges, n, 4, seed=1)
    return build_partitioned_graph(edges, n, part, weights=w)


def _profiled(tmp_path, fn):
    """Run ``fn`` inside a ``jax.profiler`` session -> (its result, the
    trace's events as (name, stats))."""
    from jax.profiler import ProfileData

    out_dir = str(tmp_path / "profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(out_dir, profiler_options=opts):
        out = fn()
    [path] = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                       recursive=True)
    data = ProfileData.from_file(path)
    return out, [(ev.name, dict(ev.stats)) for plane in data.planes
                 for line in plane.lines for ev in line.events]


def _named(events, name):
    return [stats for n, stats in events if n == name]


def _sources(root: int):
    return {"sources": jnp.asarray([root], jnp.int32)}


def _assert_same_run(a, b):
    """Final state and every counter bit-identical."""
    for x, y in zip(jax.tree.leaves((a.state, a.counters)),
                    jax.tree.leaves((b.state, b.counters))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_same_program_traces_the_loop_once(road, tmp_path):
    """Two run_hybrid calls on one program object: the loop is traced by
    the first, and the second's dispatch says it came from the cache."""
    prog = SSSP(source=0)
    (first, second), events = _profiled(
        tmp_path, lambda: [run_hybrid(road, prog) for _ in range(2)])
    assert len(_named(events, "engine.loop_trace")) == 1
    assert [bool(s["cached"]) for s in _named(events, "engine.dispatch")] \
        == [False, True]
    assert first[1] == second[1] > 0
    _assert_same_run(first[0], second[0])


def test_cached_runs_match_runs_from_an_empty_cache(road, tmp_path):
    """One program object run from several roots (traced vdata) hits the
    cache after its first run; each result equals that of a new program
    object, whose loop is traced afresh."""
    prog = MultiSourceMonotone(lanes=1, semiring="min_add")
    roots = (0, 17, 101, 239)

    def runs():
        return [(run_hybrid(road, prog, _sources(r))[0],
                 run_hybrid(road, MultiSourceMonotone(
                     lanes=1, semiring="min_add"), _sources(r))[0])
                for r in roots]

    pairs, events = _profiled(tmp_path, runs)
    cached = [bool(s["cached"]) for s in _named(events, "engine.dispatch")]
    assert cached == [False, False] + [True, False] * (len(roots) - 1)
    assert len(_named(events, "engine.loop_trace")) == len(roots) + 1
    for hit, fresh in pairs:
        _assert_same_run(hit, fresh)
    assert len({float(np.asarray(es.counters.net_messages))
                for es, _ in pairs}) > 1      # the roots differ in work


VARIANTS = {
    "wire_dtype": dict(policy=hybrid_policy(wire_dtype=jnp.bfloat16)),
    "collect_metrics": dict(policy=hybrid_policy(collect_metrics=False)),
    "use_ell": dict(policy=hybrid_policy(use_ell=False)),
    "program": dict(prog=True),
    "max_iters": dict(max_iters=2),
    "seeded_es": dict(seeded=True),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_each_key_part_gets_its_own_loop(road, tmp_path, variant):
    """After a run of the default policy on a program, a run that differs
    in one part of the key traces its own loop, and its result equals
    that of the same run on a new program object (an empty cache)."""
    base = SSSP(source=3)
    run_engine(road, base, hybrid_policy(), None, device_loop=True)
    v = VARIANTS[variant]
    policy = v.get("policy", hybrid_policy())
    max_iters = v.get("max_iters", 100_000)
    prog = SSSP(source=3) if v.get("prog") else base

    def es():        # a seeded run jits the loop alone, without the init
        return policy.init(road, prog, None) if v.get("seeded") else None

    ctx, events = _profiled(tmp_path, lambda: run_engine(
        road, prog, policy, None, max_iters=max_iters, es=es(),
        device_loop=True))
    assert len(_named(events, "engine.loop_trace")) == 1
    [dispatch] = _named(events, "engine.dispatch")
    assert not bool(dispatch["cached"])

    fresh = run_engine(road, SSSP(source=3), policy, None,
                       max_iters=max_iters, es=es(), device_loop=True)
    assert ctx.iteration == fresh.iteration
    _assert_same_run(ctx.es, fresh.es)
    if variant == "max_iters":
        assert ctx.iteration == 2


def test_host_loop_step_is_traced_once_per_program(road):
    """The host loop's default jitted step is cached like the device
    loop: a second run on the same program traces nothing."""
    base = make_policy("hybrid")
    traced = []

    def step(g, prog, es, vdata):
        traced.append(1)
        return base.step(g, prog, es, vdata)

    policy = EnginePolicy(base.name, base.init, step)
    prog = SSSP(source=0)
    a = run_engine(road, prog, policy, None)
    b = run_engine(road, prog, policy, None)
    assert len(traced) == 1 and a.iteration == b.iteration > 1
    _assert_same_run(a.es, b.es)
    run_engine(road, SSSP(source=0), policy, None)
    assert len(traced) == 2


@pytest.mark.parametrize("engine", ["bsp", "am", "hybrid"])
def test_equal_knobs_give_equal_policies(engine):
    a, b = make_policy(engine), make_policy(engine, use_ell=True)
    assert a == b and hash(a) == hash(b)
    assert a != make_policy(engine, collect_metrics=False)
    if engine == "hybrid":
        assert (hybrid_policy(wire_dtype=jnp.bfloat16)
                == hybrid_policy(wire_dtype=jnp.bfloat16))
        assert hybrid_policy(wire_dtype=jnp.bfloat16) != a


def test_dropped_program_is_collected_with_its_entry(road):
    """The cache holds a program weakly: once the caller drops it, the
    program and its jitted loop and step go."""
    prog = SSSP(source=0)
    run_hybrid(road, prog)
    run_hybrid(road, prog, device_loop=False)
    key = id(prog)
    assert len(driver._JITS[key]) == 2
    ref = weakref.ref(prog)
    del prog
    gc.collect()
    assert ref() is None
    assert key not in driver._JITS


def test_with_knobs_rebinds_only_the_knobs_a_step_has():
    """The mesh path rebinds a policy's ``gather_table``: the result
    equals a policy built with that knob, and a knob the step does not
    bind is refused."""
    base = hybrid_policy()
    gather = jnp.asarray
    assert base.step.with_knobs(gather_table=gather) == \
        hybrid_policy(gather_table=gather).step
    assert base.step.with_knobs(gather_table=None) == base.step
    with pytest.raises(TypeError, match="no_such_knob"):
        base.step.with_knobs(no_such_knob=1)
