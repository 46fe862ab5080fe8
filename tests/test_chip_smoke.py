"""The chip smoke's phases at a tiny size on the CPU.

``chip_smoke.py`` drives the main path once on a TPU at R-MAT scale 20;
here every phase runs on a scale-8 graph (interpret-mode kernels), so a
broken phase or reference check fails in tier-1 and not on the chip.  The
four-chip phase runs on four virtual CPU devices in a subprocess.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

SCALE, EDGE_FACTOR = 8, 8


@pytest.fixture(scope="module")
def tiny():
    graph, edges, w, _ = cs.build_graph(SCALE, EDGE_FACTOR, seed=0)
    source = int(cs.pick_sources(edges, graph.n_vertices, 1, 0)[0])
    return graph, edges, w, source


def test_phase_sssp_matches_dijkstra(tiny):
    graph, edges, w, source = tiny
    rec, got = cs.phase_sssp(graph, source)
    ref = cs.dijkstra(edges, w, graph.n_vertices, [source])[0]
    chk = cs.check_distances(got, ref, "sssp")
    assert chk["reached"] > 1 and rec["iterations"] >= 1
    assert chk["max_rel_err"] <= cs.SSSP_RTOL


def test_phase_serve_matches_dijkstra(tiny):
    graph, edges, w, _ = tiny
    sources = cs.pick_sources(edges, graph.n_vertices, 6, 0)
    rec, got = cs.phase_serve(graph, sources, lane_width=4)
    assert rec["queries"] == 6 and rec["batches"] == 2
    ref = cs.dijkstra(edges, w, graph.n_vertices, sources)
    assert cs.check_serve(got, ref, sources)["max_rel_err"] <= cs.SSSP_RTOL


def test_phase_kernel_path_names_fused_kernel(tiny):
    graph, _, _, source = tiny
    rec = cs.phase_kernel_path(graph, source)
    assert rec["fused_kernel"] == "min_step"
    assert rec["tpu_custom_calls"] == 0          # interpret mode on the CPU


def test_phase_pagerank_matches_power_iteration():
    graph, edges, _, _ = cs.build_graph(SCALE, EDGE_FACTOR, seed=0,
                                        pagerank=True)
    _, got = cs.phase_pagerank(graph)
    ref = cs.pagerank_reference(edges, graph.n_vertices)
    chk = cs.check_pagerank(got, ref, cs.PR_TOLERANCE)
    assert 0 <= chk["max_rel_err"] <= 200 * cs.PR_TOLERANCE


def test_serve_check_catches_a_wrong_answer(tiny):
    graph, edges, w, _ = tiny
    sources = cs.pick_sources(edges, graph.n_vertices, 2, 0)
    ref = cs.dijkstra(edges, w, graph.n_vertices, sources)
    bad = ref.copy()
    bad[1, np.isfinite(bad[1])] *= 1.01
    with pytest.raises(AssertionError):
        cs.check_serve(bad, ref, sources)


def test_check_pagerank_rejects_excess_mass():
    ref = np.array([1.0, 2.0, 0.15])
    with pytest.raises(AssertionError, match="exceeds"):
        cs.check_pagerank(ref * 1.01, ref, 1e-4)
    with pytest.raises(AssertionError, match="withholds"):
        cs.check_pagerank(ref * 0.9, ref, 1e-4)


def test_one_chip_flow_reaches_the_kernel_check(tiny, monkeypatch, capsys):
    """The whole one-chip flow, threads and all, at scale 8: every phase
    passes its reference check, and the last check refuses the CPU's
    interpret-mode step (no Mosaic kernel in it)."""
    monkeypatch.setattr(cs, "SCALE", SCALE)
    monkeypatch.setattr(cs, "EDGE_FACTOR", EDGE_FACTOR)
    monkeypatch.setattr(cs, "N_QUERIES", 6)
    monkeypatch.setattr(cs, "LANE_WIDTH", 4)
    graph, edges, w, source = tiny
    with pytest.raises(AssertionError, match="kernel path not taken"):
        cs._smoke_one_chip(graph, edges, w, source, seed=0)
    out = capsys.readouterr().out
    for phase in ("[sssp]", "[pagerank]", "[serve]", "[kernel_path]"):
        assert phase in out


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) == 2
    out = capsys.readouterr()
    assert "no TPU found" in out.err and out.out == ""


def test_four_chip_phase_on_virtual_devices():
    body = f"""
    import os, sys
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    sys.path.insert(0, {REPO!r})
    import chip_smoke as cs
    from repro.launch.mesh import make_host_mesh
    graph, edges, _, _ = cs.build_graph({SCALE}, {EDGE_FACTOR}, seed=0,
                                        edge_blocks=4)
    source = int(cs.pick_sources(edges, graph.n_vertices, 1, 0)[0])
    rec = cs.phase_four_chips(graph, source, make_host_mesh(2, 2))
    assert rec['devices'] == 4 and rec['iterations'] >= 1, rec
    print('FOUR OK', rec)
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "FOUR OK" in out.stdout
