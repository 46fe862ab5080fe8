"""The trace reduction, on a small trace with known answers."""

import json
from pathlib import Path

import pytest

from bench.trace import Event, device_id, reduce_window, union

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _trace():
    # two jobs on the host's main thread; in the first the device runs a
    # loop op holding a gather and two folds, one nested in the other, in
    # the second one more fold
    ms = 1_000_000
    return [
        Event(HOST, "python", "bench.job", 0, 50 * ms),
        Event(HOST, "python", "bench.job", 50 * ms, 100 * ms),
        Event(HOST, "python", "PjitFunction(run)", 0, 10 * ms),
        Event(HOST, "python", "compile", 55 * ms, 80 * ms),
        Event(HOST, "other", "noise", 0, 100 * ms),
        Event(DEV, "XLA Ops", "while", 10 * ms, 40 * ms),
        Event(DEV, "XLA Ops", "gather", 10 * ms, 20 * ms),
        Event(DEV, "XLA Ops", "fold", 20 * ms, 40 * ms),
        Event(DEV, "XLA Ops", "fold", 25 * ms, 35 * ms),
        Event(DEV, "XLA Ops", "fold", 80 * ms, 90 * ms),
        Event(DEV, "XLA Modules", "jit_loop", 10 * ms, 90 * ms),
        Event(DEV, "XLA Ops", "outside", 200 * ms, 210 * ms),
    ]


def test_union_merges_overlaps_and_clips():
    assert union([(5, 8), (0, 2), (1, 3), (9, 20)], 0, 10) == [
        (0, 3), (5, 8), (9, 10)]
    assert union([(11, 12)], 0, 10) == []


def test_busy_union_and_window():
    r = reduce_window(_trace(), "bench.job")
    assert r["window_s"] == pytest.approx(0.1)
    # 10-40 ms and 80-90 ms: nested ops and the module line are not
    # counted twice, the op outside the window not at all
    assert r["busy_s"] == pytest.approx(0.04)
    assert r["jobs"] == 2 and r["devices"] == 1


def test_top_ops_by_self_time():
    ops = dict(reduce_window(_trace(), "bench.job")["device_ops"])
    assert ops["fold"] == pytest.approx(0.03)
    assert ops["gather"] == pytest.approx(0.01)
    assert ops["while"] == pytest.approx(0.0)
    assert sum(ops.values()) == pytest.approx(0.04)
    assert "outside" not in ops and "jit_loop" not in ops


def test_gaps_named_by_the_innermost_host_span_of_the_jobs_thread():
    gaps = reduce_window(_trace(), "bench.job")["idle_gaps"]
    # 40-80 ms (midpoint 60: inside "compile"), 0-10 ms (midpoint 5:
    # inside "PjitFunction(run)"), 90-100 ms (only the job span)
    assert [g[0] for g in gaps] == ["compile", "PjitFunction(run)",
                                    "bench.job"]
    assert [g[1] for g in gaps] == pytest.approx([0.04, 0.01, 0.01])


def test_no_window_or_no_device_op_reads_nothing():
    assert reduce_window(_trace(), "no.such.span") is None
    host_only = [e for e in _trace() if not e.plane.startswith("/device")]
    assert reduce_window(host_only, "bench.job") is None


def test_busy_is_averaged_over_devices():
    ms = 1_000_000
    ev = _trace() + [Event("/device:TPU:1", "XLA Ops", "fold", 0, 100 * ms)]
    assert reduce_window(ev, "bench.job")["busy_s"] == pytest.approx(0.07)


def _four_chips(idle: int):
    """One 100 ms job; chips 0-3 each busy all through, but chip ``idle``,
    which runs no op."""
    ms = 1_000_000
    ev = [Event(HOST, "python", "bench.job", 0, 100 * ms)]
    ev += [Event(f"/device:TPU:{d}", "XLA Ops", "fold", 0, 100 * ms)
           for d in range(4) if d != idle]
    return ev


@pytest.mark.parametrize("idle", [0, 3])
def test_chip_that_ran_no_op_counts_as_idle(idle):
    from bench.metrics.device_idle_pct import read
    r = reduce_window(_four_chips(idle), "bench.job", [0, 1, 2, 3])
    assert r["devices"] == 4
    assert r["busy_s"] == pytest.approx(0.075)
    assert read({"trace": r}) >= 25.0
    # without the cell's chips, only the planes that ran an op count
    assert reduce_window(_four_chips(idle), "bench.job")["devices"] == 3


def test_one_chip_cell_reads_only_its_own_plane():
    ms = 1_000_000
    ev = _four_chips(idle=3) + [
        Event("/device:TPU:3", "XLA Ops", "fold", 0, 20 * ms)]
    assert reduce_window(ev, "bench.job", [3])["busy_s"] == \
        pytest.approx(0.02)
    # a chip of the cell that ran nothing in the window reads nothing
    assert reduce_window(_four_chips(idle=3), "bench.job", [3]) is None


def test_device_id_of_a_plane():
    assert device_id("/device:TPU:3") == 3
    assert device_id("/device:TPU:12") == 12
    assert device_id("/host:CPU") is None
    assert device_id("/device:TPU:0 SparseCore") is None


def test_recorded_tpu_trace():
    """An excerpt of a traced run on one TPU v5e: its device ops lie on
    the TPU plane's ops line and every reading stays within the window."""
    path = Path(__file__).parent / "data" / "tpu_trace_excerpt.json"
    ev = [Event(*row) for row in json.loads(path.read_text())]
    r = reduce_window(ev, "bench.job")
    assert r is not None and r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(s for _, s in r["device_ops"]) >= r["busy_s"] * 0.999
    assert all(s <= r["window_s"] for _, s in r["idle_gaps"])


def test_recorded_tpu_trace_reads_the_same_for_its_one_chip():
    """Given the cell's one chip, the reduction is the one without: the
    same readings, bit for bit."""
    path = Path(__file__).parent / "data" / "tpu_trace_excerpt.json"
    ev = [Event(*row) for row in json.loads(path.read_text())]
    assert reduce_window(ev, "bench.job", [0]) == reduce_window(
        ev, "bench.job")
