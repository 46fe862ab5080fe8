"""A run with the timed path broken underneath comes out not correct,
once for each fault an analytics job can have; a sound run comes out
correct."""

import dataclasses

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests.tiny import CELL, PAGERANK, ROAD, run_tiny

CELLS = [CELL, ROAD, PAGERANK]
SEED = 2**31 + 101


def _init(graph, prog, vdata):
    from repro.exec.policy import hybrid_policy
    return hybrid_policy().init(graph, prog, vdata)


def unchanged(graph, prog, vdata):
    """Every step returns its state unchanged: the job ends where it
    started."""
    return _init(graph, prog, vdata)


def half_left_out(graph, prog, vdata):
    """Half of the partitions' vertices are never computed."""
    es = harness.run_hybrid(graph, prog, vdata)
    start = _init(graph, prog, vdata)
    half = graph.n_partitions // 2
    state = jax.tree.map(lambda a, b: a.at[half:].set(b[half:]), es.state,
                         start.state)
    return dataclasses.replace(es, state=state)


def answer_altered(graph, prog, vdata):
    """One vertex's answer is off by one where it is produced."""
    es = harness.run_hybrid(graph, prog, vdata)

    def alter(a):
        host = np.array(a)
        flat = host.reshape(-1)
        ok = (np.isfinite(flat)
              & np.repeat(np.asarray(graph.vertex_gid).ravel() >= 0,
                          flat.size // graph.vertex_gid.size))
        flat[np.flatnonzero(ok)[0]] += 1.0
        return jax.numpy.asarray(host)

    return dataclasses.replace(es, state=jax.tree.map(alter, es.state))


def _exchange_left_out(monkeypatch):
    import repro.exec.iteration as iteration
    monkeypatch.setattr(iteration, "exchange",
                        lambda graph, es, *a, **k: es)
    return harness.run_hybrid


FAULTS = {"unchanged": lambda mp: unchanged,
          "half_left_out": lambda mp: half_left_out,
          "exchange_left_out": _exchange_left_out,
          "answer_altered": lambda mp: answer_altered}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(monkeypatch, cell):
    r = run_tiny(monkeypatch, cell, SEED)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", [CELL, PAGERANK])
def test_fault_is_not_correct(monkeypatch, cell, fault):
    runner = FAULTS[fault](monkeypatch)
    # the warm-up job runs the sound program; the window the broken one
    real_prepare = harness.prepare
    monkeypatch.setattr(harness, "prepare",
                        lambda wl, seed, devices, runner=None:
                        real_prepare(wl, seed, devices))
    r = run_tiny(monkeypatch, cell, SEED, runner=runner)
    assert not r["correct"] and r["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
