"""The per-layer reduction of what the program names in a trace: its
``engine.*`` spans, phase scopes and kernel names."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.layers import (Event, hlo_op_names, instruction, kernel_of,
                          reduce_layers)
from bench.trace import reduce_window

REPO = Path(__file__).resolve().parents[2]
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _scoped_trace():
    # one job: the program's spans on the jobs' thread, and on the device
    # the outer loop op holding an exchange gather, a local-phase loop
    # with the min_step kernel and its message counter, and a global op
    ms = 1_000_000
    loop = "jit(loop)/while/body"
    return [
        Event(HOST, "python", "bench.job", 0, 100 * ms),
        Event(HOST, "python", "engine.run", 1 * ms, 99 * ms),
        Event(HOST, "python", "engine.init", 1 * ms, 3 * ms),
        Event(HOST, "python", "engine.dispatch", 4 * ms, 20 * ms),
        Event(HOST, "python", "engine.loop_trace", 5 * ms, 9 * ms),
        Event(HOST, "python", "engine.wait", 20 * ms, 99 * ms),
        Event(HOST, "python", "engine.init", 150 * ms, 160 * ms),
        Event(DEV, "XLA Ops", "%while.1 = (...) while(...)", 20 * ms,
              90 * ms, "jit(loop)/while"),
        Event(DEV, "XLA Ops", "%gather.2 = f32[8] gather(...)", 20 * ms,
              30 * ms, f"{loop}/exchange/gather"),
        Event(DEV, "XLA Ops", "%while.3 = (...) while(...)", 30 * ms,
              70 * ms, f"{loop}/local_phase/while"),
        Event(DEV, "XLA Ops",
              "%min_step.1 = (f32[8,512]) custom-call(%a), "
              'custom_call_target="tpu_custom_call"', 30 * ms, 50 * ms,
              f"{loop}/local_phase/while/body/min_step/pallas_call"),
        Event(DEV, "XLA Ops", "%reduce.4 = s32[] reduce(...)", 50 * ms,
              55 * ms,
              f"{loop}/local_phase/while/body/message_accounting/reduce"),
        Event(DEV, "XLA Ops", "%fusion.5 = f32[8] fusion(...)", 75 * ms,
              85 * ms, f"{loop}/global_phase/max"),
        Event(DEV, "XLA Ops", "%copy.6 = f32[8] copy(...)", 95 * ms,
              98 * ms, ""),
    ]


def test_layers_count_the_engine_spans_in_the_window():
    spans = reduce_layers(_scoped_trace(), "bench.job")["spans"]
    # the init at 150 ms lies outside the window
    assert {k: v[0] for k, v in spans.items()} == {
        "engine.run": 1, "engine.init": 1, "engine.dispatch": 1,
        "engine.loop_trace": 1, "engine.wait": 1}
    assert spans["engine.dispatch"][1] == pytest.approx(0.016)
    assert spans["engine.init"][1] == pytest.approx(0.002)


def test_layers_give_each_op_to_its_innermost_phase_scope():
    ev = _scoped_trace()
    lay = reduce_layers(ev, "bench.job")
    assert lay["scopes"] == pytest.approx({
        "exchange": 0.010, "local_phase": 0.035,
        "message_accounting": 0.005, "global_phase": 0.010,
        # the outer loop's own 70 - 10 - 40 - 10 ms, and the copy
        "unscoped": 0.013})
    assert lay["kernels"] == pytest.approx({"min_step": 0.020})
    assert sum(lay["scopes"].values()) == pytest.approx(
        reduce_window(ev, "bench.job")["busy_s"])


def test_kernel_found_by_its_instruction_name():
    call = ('%min_step.7 = (f32[8,512]) custom-call(%a), '
            'custom_call_target="tpu_custom_call"')
    assert kernel_of(Event(DEV, "XLA Ops", call, 0, 1)) == "min_step"
    assert kernel_of(Event(DEV, "XLA Ops", "%min_step_like.1 = f32[8] "
                           "fusion()", 0, 1)) is None
    # a fusion named after a kernel's jit is no kernel call
    assert kernel_of(Event(DEV, "XLA Ops", "%ell_spmv.2 = f32[8] "
                           "fusion(%a)", 0, 1)) is None


def test_hlo_op_names_read_from_a_cpu_profile(tmp_path):
    """The trace's metadata plane holds each program's HLO: every
    instruction's op_name, scope included, read off the wire format."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("exchange"):
            y = jnp.sin(x)
        with jax.named_scope("local_phase"):
            return y * 2.0

    x = jnp.ones((16,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        f(x).block_until_ready()
    [path] = tmp_path.glob("**/*.xplane.pb")
    programs = hlo_op_names(path.read_bytes())
    [ops] = [v for k, v in programs.items() if k.startswith("jit_f(")]
    scopes = set(ops.values())
    assert any(n.endswith("/exchange/sin") for n in scopes), scopes
    assert any("/local_phase/" in n for n in scopes), scopes
    assert instruction("%fusion.263 = pred[8] fusion(%a)") == "fusion.263"


def test_layers_read_nothing_without_a_window():
    assert reduce_layers(_scoped_trace(), "no.such.span") is None


def _scoped_excerpt():
    path = Path(__file__).parent / "data" / "tpu_trace_scoped_excerpt.json"
    return [Event(*row) for row in json.loads(path.read_text())]


def test_recorded_tpu_trace_with_spans_scopes_and_kernels():
    """An excerpt of one traced job of ``g500-s16.sssp`` on one TPU v5e
    (``bench/tests/data/tpu_trace_scoped_excerpt.json``, read with
    ``load_events``): the job's span and the program's ``engine.*`` spans
    whole, and the device ops of one whole global iteration (the 16th of
    18, one ``min_step`` call), each with the op_name its program's HLO
    gave it; the outer loop's own op, which spans the job, is left out.
    Op names are cut to 80 characters, or just past ``custom-call(``."""
    ev = _scoped_excerpt()
    r = reduce_window(ev, "bench.job")
    lay = reduce_layers(ev, "bench.job")
    assert lay["jobs"] == 1
    assert {k: v[0] for k, v in lay["spans"].items()} == {
        "engine.run": 1, "engine.init": 1, "engine.dispatch": 1,
        "engine.loop_trace": 1, "engine.wait": 1}
    spans = {e.name: e for e in ev if e.name.startswith("engine.")}
    assert spans["engine.loop_trace"].start_ns >= \
        spans["engine.dispatch"].start_ns
    assert lay["kernels"]["min_step"] > 0
    assert {"exchange", "remote_delivery", "global_phase",
            "local_phase"} <= set(lay["scopes"])
    # the scopes' self times and the unscoped time add up to busy_s
    assert sum(lay["scopes"].values()) == pytest.approx(r["busy_s"],
                                                        rel=1e-9)


def test_cli_refuses_to_run_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, "bench/layers.py", "--workload", "g500-s16.sssp",
         "--seed", str(2**31 + 11), "--seconds", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "no chip" in r.stderr
