"""The harness finds every cell's files by name, and refuses to run
without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.jobs import JOBS

REPO = Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    wl = harness.load_workload(name)
    entry = next(w for w in SPEC["workloads"] if w["name"] == name)
    assert wl.config["name"] == entry["config"]
    assert wl.traffic["job"] in JOBS
    assert wl.limits and all(v >= 0 for v in wl.limits.values())
    assert {m["name"] for m in wl.end_to_end} >= {"setup_s", "job_s"}
    assert wl.per_layer
    for m in wl.per_layer:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_config_files_match_benchmark_json():
    for c in SPEC["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


def test_unknown_cell_raises():
    with pytest.raises(KeyError):
        harness.load_workload("no-such.cell")


def _run(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2**31 + 11), "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_to_run_without_a_tpu():
    r = _run(REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no chip" in r.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
