"""Executor hook composition: multiple hooks on one run, method call
ordering, and the consumed-tick contract (every hook's ``before_step``
evaluates each tick even when an earlier one consumes it)."""

import numpy as np
import pytest

from repro.core import bfs_partition, build_partitioned_graph
from repro.core.apps import SSSP
from repro.data.graphs import grid_graph
from repro.exec.driver import ExecHook, run_engine
from repro.exec.policy import make_policy


@pytest.fixture(scope="module")
def road():
    edges, w, n = grid_graph(5, 30, seed=3)
    part = bfs_partition(edges, n, 4, seed=1)
    return build_partitioned_graph(edges, n, part, weights=w)


class Recorder(ExecHook):
    """Logs every method call into a shared list as (hook_name, method)."""

    def __init__(self, name, log):
        self.name = name
        self.log = log

    def on_start(self, ctx):
        self.log.append((self.name, "on_start"))

    def before_step(self, ctx):
        self.log.append((self.name, "before_step"))

    def after_step(self, ctx):
        self.log.append((self.name, "after_step"))

    def on_exit(self, ctx):
        self.log.append((self.name, "on_exit"))


class SkipOnce(Recorder):
    """Consumes exactly one tick (returns False from before_step once)."""

    def __init__(self, name, log, skip_tick):
        super().__init__(name, log)
        self.skip_tick = skip_tick

    def before_step(self, ctx):
        super().before_step(ctx)
        if ctx.tick == self.skip_tick:
            self.log.append((self.name, "CONSUMED"))
            return False


def test_hooks_called_in_order_every_phase(road):
    """Two hooks: list order is call order for every method, each step is
    bracketed before/after, start/exit fire exactly once per hook."""
    log = []
    a, b = Recorder("a", log), Recorder("b", log)
    ctx = run_engine(road, SSSP(source=0), make_policy("hybrid"), None,
                     hooks=(a, b))
    assert ctx.iteration > 1

    assert log[:2] == [("a", "on_start"), ("b", "on_start")]
    assert log[-2:] == [("a", "on_exit"), ("b", "on_exit")]
    per_step = [("a", "before_step"), ("b", "before_step"),
                ("a", "after_step"), ("b", "after_step")]
    assert log[2:-2] == per_step * ctx.iteration


def test_consumed_tick_still_evaluates_every_hook(road):
    """The all-hooks-evaluate contract: when hook a consumes tick 2, hook
    b's before_step still ran that tick (its failure-detection clock must
    advance), no after_step fires, and the run completes correctly."""
    ref = run_engine(road, SSSP(source=0), make_policy("hybrid"), None)

    log = []
    a = SkipOnce("a", log, skip_tick=2)
    b = Recorder("b", log)
    ctx = run_engine(road, SSSP(source=0), make_policy("hybrid"), None,
                     hooks=(a, b))

    # one extra tick: the consumed one did not step
    befores_b = [x for x in log if x == ("b", "before_step")]
    afters_b = [x for x in log if x == ("b", "after_step")]
    assert len(befores_b) == ctx.iteration + 1
    assert len(afters_b) == ctx.iteration
    # b's before_step DID run on the consumed tick: it directly follows
    # a's CONSUMED marker, with no after_step until the next tick's step
    i = log.index(("a", "CONSUMED"))
    assert log[i + 1] == ("b", "before_step")
    assert log[i + 2] == ("a", "before_step")      # next tick begins

    np.testing.assert_array_equal(np.asarray(ctx.es.state["dist"]),
                                  np.asarray(ref.es.state["dist"]))


def test_later_hook_false_does_not_shortcircuit(road):
    """`False in [h.before_step(ctx) for h in hooks]` evaluates the whole
    list: a False from the FIRST hook must not stop the second from being
    called (regression guard on replacing the list with any())."""
    log = []
    a = SkipOnce("a", log, skip_tick=1)
    b = SkipOnce("b", log, skip_tick=1)   # both consume the same tick
    ctx = run_engine(road, SSSP(source=0), make_policy("hybrid"), None,
                     hooks=(a, b))
    assert ctx.iteration > 0
    assert ("a", "CONSUMED") in log and ("b", "CONSUMED") in log


def test_device_loop_matches_host_loop_and_takes_no_step(road):
    """The one-jit device loop reaches the host loop's fixed point in the
    same iterations; it jits the policy's own step, so a caller's
    ``jit_step`` is refused rather than closed over."""
    policy = make_policy("hybrid")
    ref = run_engine(road, SSSP(source=0), policy, None)
    ctx = run_engine(road, SSSP(source=0), policy, None, device_loop=True)
    assert ctx.iteration == ref.iteration
    np.testing.assert_array_equal(np.asarray(ctx.es.state["dist"]),
                                  np.asarray(ref.es.state["dist"]))
    with pytest.raises(ValueError, match="jit_step"):
        run_engine(road, SSSP(source=0), policy, None, device_loop=True,
                   jit_step=lambda e: e)


def test_checkpoint_fault_and_trace_hooks_compose(road, tmp_path):
    """The production stack — fault detection + checkpointing + tracing on
    one run — leaves results identical to the bare run and a consistent
    trace."""
    from repro.ft import run_hybrid_ft
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.trace import Tracer

    ref = run_hybrid_ft(road, SSSP(source=0))

    tracer = Tracer()
    reg = MetricsRegistry()
    res = run_hybrid_ft(road, SSSP(source=0), ckpt_dir=str(tmp_path / "c"),
                        tracer=tracer, registry=reg)
    np.testing.assert_array_equal(np.asarray(res.es.state["dist"]),
                                  np.asarray(ref.es.state["dist"]))
    for f in ("iterations", "net_messages", "net_local_messages"):
        assert int(getattr(res.es.counters, f)) == \
            int(getattr(ref.es.counters, f))

    steps = [s for s in tracer.spans if s.cat == "superstep"]
    assert len(steps) == res.iterations
    # the wrapped hooks' work is attributed, and the superstep span that
    # brackets each step is recorded last (TraceHook sits last in the list)
    assert any(s.cat == "hook" and "CheckpointHook.after_step" in s.name
               for s in tracer.spans)
    assert any(s.cat == "hook" and "_FaultHook.before_step" in s.name
               for s in tracer.spans)
    assert reg.value("engine.iterations") == float(res.iterations)
    assert reg.value("checkpoint.bytes_written") > 0
