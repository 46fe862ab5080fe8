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


def test_every_per_layer_metric_has_a_reader():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]
