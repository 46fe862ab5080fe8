"""Each per-layer metric's reader, on fixed counters and trace readings."""

import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
JOBS = [{"iterations": 8, "pseudo_supersteps": 190, "net_messages": 1000,
         "mem_messages": 3000},
        {"iterations": 10, "pseudo_supersteps": 210, "net_messages": 3000,
         "mem_messages": 1000}]
TRACE = {"busy_s": 2.0, "window_s": 8.0}
PEAKS = {"hbm_bytes_per_s": 1e6}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(jobs=JOBS, trace=TRACE):
    return {"jobs": jobs, "trace": trace, "message_bytes": 12,
            "peaks": PEAKS}


@pytest.mark.parametrize("name,want", [
    ("global_iters", 9.0), ("pseudo_supersteps", 200.0),
    ("net_messages", 2000.0),
    # 8,000 messages * 12 B at 1 MB/s need 0.096 s of the 2 s busy
    ("relax_roofline", 100.0 * 0.096 / 2.0),
    ("device_idle_pct", 75.0)])
def test_reader(name, want):
    assert _reader(name)(_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["relax_roofline", "device_idle_pct"])
def test_trace_readers_read_nothing_without_a_trace(name):
    assert _reader(name)(_run(trace=None)) is None


@pytest.mark.parametrize("name", ["global_iters", "pseudo_supersteps",
                                  "net_messages", "relax_roofline"])
def test_readers_read_nothing_without_jobs(name):
    assert _reader(name)(_run(jobs=[])) is None


def test_relax_roofline_over_four_chips_is_a_quarter_of_one():
    one = _reader("relax_roofline")({**_run(), "chips": 1})
    four = _reader("relax_roofline")({**_run(), "chips": 4})
    assert four == pytest.approx(one / 4)
    # a record that names no chips is one chip's
    assert _reader("relax_roofline")(_run()) == one


#: each phase reading and its scope
PHASES = {"exchange_ms": "exchange", "remote_delivery_ms": "remote_delivery",
          "global_phase_ms": "global_phase", "local_phase_ms": "local_phase",
          "accounting_ms": "message_accounting"}


def _excerpt_run():
    """The record a traced run of ``g500-s16.sssp`` hands its readers, on
    the recorded excerpt of one job on one TPU v5e."""
    from bench.layers import Event, reduce_layers
    from bench.trace import reduce_window
    path = Path(__file__).parent / "data" / "tpu_trace_scoped_excerpt.json"
    ev = [Event(*row) for row in json.loads(path.read_text())]
    return {"jobs": JOBS[:1], "trace": reduce_window(ev, "bench.job", [0]),
            "layers": reduce_layers(ev, "bench.job", [0]), "chips": 1,
            "message_bytes": 12, "peaks": PEAKS}


def test_phase_readings_add_up_to_busy_time_per_job():
    run = _excerpt_run()
    got = {name: _reader(name)(run) for name in PHASES}
    assert all(v is not None and v > 0 for v in got.values()), got
    jobs = run["layers"]["jobs"]
    unscoped = 1e3 * run["layers"]["scopes"].get("unscoped", 0.0) / jobs
    assert sum(got.values()) + unscoped == pytest.approx(
        1e3 * run["trace"]["busy_s"] / jobs, rel=1e-9)
    for name, scope in PHASES.items():
        assert got[name] == 1e3 * run["layers"]["scopes"][scope] / jobs


@pytest.mark.parametrize("name", sorted(PHASES))
def test_phase_readers_read_nothing_without_a_trace_or_scope(name):
    assert _reader(name)({**_run(trace=None), "layers": None}) is None
    assert _reader(name)(_run(trace=None)) is None
    run = _excerpt_run()
    del run["layers"]["scopes"][PHASES[name]]
    assert _reader(name)(run) is None


def test_one_chip_readings_keep_the_parents_formula():
    """At one chip the idle share and the roofline are the formulas the
    parent computed, bit for bit, on the recorded excerpt."""
    run = _excerpt_run()
    busy, window = run["trace"]["busy_s"], run["trace"]["window_s"]
    msgs = sum(j["mem_messages"] + j["net_messages"] for j in run["jobs"])
    assert _reader("device_idle_pct")(run) == 100.0 * (1.0 - busy / window)
    assert _reader("relax_roofline")(run) == 100.0 * (
        msgs * 12 / PEAKS["hbm_bytes_per_s"]) / busy


def test_every_per_layer_metric_has_a_reader():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
