"""Ahead-of-time compiles of the three Pallas kernels for a TPU v5e.

The TPU compiler is installed alongside jax and compiles for a described
(not attached) ``v5e:2x2`` topology, so these tests catch what interpret
mode cannot — layouts Mosaic refuses, in-kernel gathers, VMEM overruns,
programs larger than the chip's HBM — with no chip.  Each compiles one
kernel at the widths of a scale-20 graph (2**20 rows and frontier slots),
for one lane and for a 16-lane query batch — the fused monotone step for
every semiring it takes, since min_mul alone carries a per-lane sender gate
— and asserts that the kernel really lowered to Mosaic (``tpu_custom_call``),
under the name a device trace shows it by, rather than interpret mode:
tracing on the CPU backend would pick interpret mode, so the ``mosaic``
fixture makes ``default_interpret`` answer False.

The topology is described inside a fixture: only the worker that runs
these tests loads the TPU library, and every worker collects them.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.common import MONOTONE_SEMIRINGS

ROWS = 1 << 20          # P * Vp destination rows of a scale-20 graph
SLOTS = 16              # base-bin slot width
FRONTIER = 1 << 20
LANES = (None, 16)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    import repro.kernels.common as common
    monkeypatch.setattr(common, "default_interpret", lambda: False)


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _edges(one_chip):
    return (_shape(one_chip, (ROWS, SLOTS), jnp.int32),
            _shape(one_chip, (ROWS, SLOTS), jnp.float32),
            _shape(one_chip, (ROWS, SLOTS), jnp.bool_))


def _compile(fn, *args):
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def _assert_mosaic(compiled, kernel: str):
    """The kernel lowered to Mosaic, as a custom call named ``kernel``:
    the name a device trace shows it by."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{kernel}(\.\d+)? = [^\n]*custom-call\(", text), \
        kernel
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used


@pytest.mark.parametrize("lanes", LANES)
def test_ell_spmv_compiles_for_v5e(one_chip, mosaic, lanes):
    from repro.kernels.ell_spmv.ell_spmv import ell_spmv_pallas

    lane = (lanes,) if lanes else ()
    x = _shape(one_chip, (FRONTIER,) + lane, jnp.float32)
    compiled = _compile(
        lambda i, v, m, x: ell_spmv_pallas(i, v, m, x, semiring="min_add"),
        *_edges(one_chip), x)
    _assert_mosaic(compiled, "ell_spmv")


@pytest.mark.parametrize("semiring", sorted(MONOTONE_SEMIRINGS))
@pytest.mark.parametrize("lanes", LANES)
def test_fused_min_step_compiles_for_v5e(one_chip, mosaic, lanes, semiring):
    from repro.kernels.min_step.min_step import fused_min_step_pallas

    lane = (lanes,) if lanes else ()
    x = _shape(one_chip, (FRONTIER,) + lane, jnp.float32)
    send = _shape(one_chip, (FRONTIER,) + lane, jnp.bool_)
    row = _shape(one_chip, (ROWS,) + lane, jnp.float32)
    compiled = _compile(
        lambda i, v, m, x, s, xr, e: fused_min_step_pallas(
            i, v, m, x, s, xr, e, semiring=semiring),
        *_edges(one_chip), x, send, row, row)
    _assert_mosaic(compiled, "min_step")


@pytest.mark.parametrize("lanes", LANES)
def test_fused_pr_step_compiles_for_v5e(one_chip, mosaic, lanes):
    from repro.kernels.pr_step.pr_step import fused_pr_step_pallas

    lane = (lanes,) if lanes else ()
    delta = _shape(one_chip, (FRONTIER,) + lane, jnp.float32)
    send = _shape(one_chip, (FRONTIER,) + lane, jnp.bool_)
    row = _shape(one_chip, (ROWS,) + lane, jnp.float32)
    compiled = _compile(
        lambda i, v, m, d, s, r, e: fused_pr_step_pallas(
            i, v, m, d, s, r, e),
        *_edges(one_chip), delta, send, row, row)
    _assert_mosaic(compiled, "pr_step")


def test_run_hybrid_mesh_loop_compiles_for_v5e_2x2(topo, mosaic):
    """``run_hybrid``'s device loop on a graph placed over a 2x2 mesh: one
    shard_map over the mesh, so the chip's partitioner meets no Pallas
    call it would have to split (jit's automatic partitioning refuses
    Mosaic kernels).  The kernels lower to Mosaic on every block, and the
    export table crosses chips by all-gather."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core import build_partitioned_graph, hash_partition
    from repro.core.apps import MultiSourceMonotone
    from repro.core.distributed import shard0_specs
    from repro.data.graphs import rmat_graph
    from repro.exec import driver
    from repro.exec.policy import hybrid_policy

    edges, n = rmat_graph(1 << 10, avg_degree=16, seed=1)
    w = np.random.default_rng(1).random(len(edges), dtype=np.float32)
    graph = build_partitioned_graph(edges, n, hash_partition(n, 8, seed=1),
                                    weights=w, ell_base_slices=16,
                                    edge_blocks=4)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"))
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             shard0_specs(graph, mesh.axis_names))
    g = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                       sharding=s),
                     graph, shardings)
    v = {"sources": jax.ShapeDtypeStruct(
        (1,), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec()))}
    assert driver.graph_mesh(g) is mesh
    prog = MultiSourceMonotone(lanes=1, semiring="min_add")
    loop, _ = driver.device_loop_jit(prog, hybrid_policy(), 100_000, True,
                                     mesh)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = loop.lower(g, v, None).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    _assert_mosaic(compiled, "min_step")
    assert "all-gather" in compiled.as_text()
