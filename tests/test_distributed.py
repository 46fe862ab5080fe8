"""Distributed-mode tests.  Each runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so shard_map/GSPMD paths
execute on a real (fake-)multi-device mesh:

  * the distributed GraphHP engine produces the SAME fixed point and
    iteration count as the host engine (the shard_map lowering is faithful);
  * a smoke-sized LM train/prefill/decode cell lowers, compiles AND RUNS
    under the 2×4 mesh with the production sharding rules;
  * the hybrid-sync inner step + global sync run under a (2,2,2) pod mesh.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 900):
    src = "import os\n" \
          f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n" \
          + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + ":" + REPO
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_distributed_hybrid_engine_matches_host():
    run_sub("""
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import build_partitioned_graph, bfs_partition, run_hybrid
    from repro.core.apps import SSSP
    from repro.core.distributed import make_dist_hybrid_step, _es_specs, shard0_specs
    from repro.core.engine_hybrid import init_hybrid
    from repro.core.runtime import quiescent
    from repro.data.graphs import grid_graph

    edges, w, n = grid_graph(6, 40, seed=3)
    part = bfs_partition(edges, n, 8, seed=1)
    graph = build_partitioned_graph(edges, n, part, weights=w,
                                    edge_blocks=8)   # one block per device
    prog = SSSP(source=0)

    # host reference
    es_ref, iters_ref = run_hybrid(graph, prog)
    ref = np.asarray(es_ref.state['dist'])

    # distributed: one partition per device
    mesh = make_host_mesh(2, 4)   # Auto axes: eager code runs on sharded inputs
    axes = ('data', 'model')
    step = make_dist_hybrid_step(prog, mesh, axes=axes)
    es = init_hybrid(graph, prog, None)
    gs = jax.tree.map(lambda s: NamedSharding(mesh, s), shard0_specs(graph, axes))
    ess = jax.tree.map(lambda s: NamedSharding(mesh, s), _es_specs(es, axes))
    graph_d = jax.device_put(graph, gs)
    es_d = jax.device_put(es, ess)
    jitted = jax.jit(step, in_shardings=(gs, ess))
    iters = 0
    while not bool(quiescent(prog, es_d)) and iters < 500:
        es_d = jitted(graph_d, es_d)
        iters += 1
    got = np.asarray(jax.device_get(es_d.state['dist']))
    np.testing.assert_allclose(got, ref, rtol=1e-5)
    assert iters == iters_ref, (iters, iters_ref)
    # paper metric parity: the message counters agree with the host run
    assert int(es_d.counters.net_messages) == int(es_ref.counters.net_messages)
    print('DIST OK', iters, int(es_d.counters.net_messages))
    """)


def test_distributed_hybrid_kernel_path_matches_host():
    """The now-default use_ell=True under shard_map: the ELL kernels
    (including the fused min_step local phase and remote-ELL delivery over
    spill bins) run on block-local partition slices, exercising
    `slice_flat`'s re-offset branch (p != graph.n_partitions), with
    collect_metrics=True riding the tiles' per-slot group ids (no dense
    per-group fallback).  Fixed point, iteration count and every paper
    counter must match the host dense run bit-exactly."""
    run_sub("""
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import NamedSharding
    from repro.core import build_partitioned_graph, hash_partition, run_hybrid
    from repro.core.apps import SSSP
    from repro.core.distributed import make_dist_hybrid_step, _es_specs, shard0_specs
    from repro.core.engine_hybrid import init_hybrid
    from repro.core.runtime import quiescent

    # hub-skewed digraph so the sliced-ELL layout spills into extra bins
    rng = np.random.RandomState(13)
    n = 160
    edges = np.stack([rng.randint(0, n, size=1200),
                      rng.randint(0, 4, size=1200)], axis=1)
    edges = np.concatenate([edges, rng.randint(0, n, size=(600, 2))])
    edges = np.unique(edges, axis=0)
    edges = edges[edges[:, 0] != edges[:, 1]]
    part = hash_partition(n, 8, seed=2)
    w = rng.uniform(0.5, 3.0, size=len(edges)).astype(np.float32)
    graph = build_partitioned_graph(edges, n, part, weights=w,
                                    ell_base_slices=8, edge_blocks=8)
    assert len(graph.remote_ell) >= 2, 'skew should spill remote bins'
    prog = SSSP(source=0)

    es_ref, iters_ref = run_hybrid(graph, prog, use_ell=False)
    ref = np.asarray(es_ref.state['dist'])

    mesh = make_host_mesh(2, 4)   # Auto axes: eager code runs on sharded inputs
    axes = ('data', 'model')
    # kernel path + collect_metrics=True are the defaults now — no kwargs
    step = make_dist_hybrid_step(prog, mesh, axes=axes)
    es = init_hybrid(graph, prog, None)
    gs = jax.tree.map(lambda s: NamedSharding(mesh, s), shard0_specs(graph, axes))
    ess = jax.tree.map(lambda s: NamedSharding(mesh, s), _es_specs(es, axes))
    graph_d = jax.device_put(graph, gs)
    es_d = jax.device_put(es, ess)
    jitted = jax.jit(step, in_shardings=(gs, ess))
    iters = 0
    while not bool(quiescent(prog, es_d)) and iters < 500:
        es_d = jitted(graph_d, es_d)
        iters += 1
    got = np.asarray(jax.device_get(es_d.state['dist']))
    np.testing.assert_array_equal(got, ref)      # min semiring: bit-exact
    assert iters == iters_ref, (iters, iters_ref)
    for f in ('net_messages', 'net_local_messages', 'mem_messages'):
        assert int(getattr(es_d.counters, f)) == \\
            int(getattr(es_ref.counters, f)), f
    print('DIST ELL OK', iters, int(es_d.counters.net_messages))
    """)


def test_distributed_new_semiring_apps_match_host():
    """WidestPath (max_min) and RandomWalk (min_mul / max_add) through the
    default-kernel distributed step: fixed point and paper counters
    bit-exact against the host dense run for every app."""
    run_sub("""
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import NamedSharding
    from repro.core import build_partitioned_graph, hash_partition, run_hybrid
    from repro.core.apps import RandomWalk, WidestPath
    from repro.core.apps.random_walk import random_walk_edge_weights
    from repro.core.distributed import make_dist_hybrid_step, _es_specs, shard0_specs
    from repro.core.engine_hybrid import init_hybrid
    from repro.core.runtime import quiescent
    from repro.data.graphs import rmat_graph

    edges, n = rmat_graph(240, avg_degree=5, seed=9)
    part = hash_partition(n, 8, seed=1)
    rng = np.random.RandomState(7)
    w_cap = rng.uniform(0.5, 8.0, size=len(edges)).astype(np.float32)
    g_cap = build_partitioned_graph(edges, n, part, weights=w_cap,
                                    edge_blocks=8)
    g_rw = {m: build_partitioned_graph(
        edges, n, part, edge_blocks=8,
        weights=random_walk_edge_weights(edges, n, m))
        for m in ('odds', 'logprob')}

    mesh = make_host_mesh(2, 4)   # Auto axes: eager code runs on sharded inputs
    axes = ('data', 'model')
    cases = [('widest', g_cap, WidestPath(source=0), 'cap'),
             ('rw_odds', g_rw['odds'], RandomWalk(source=0, mode='odds'),
              'mass'),
             ('rw_logp', g_rw['logprob'],
              RandomWalk(source=0, mode='logprob'), 'mass')]
    for name, graph, prog, field in cases:
        es_ref, iters_ref = run_hybrid(graph, prog, use_ell=False)
        step = make_dist_hybrid_step(prog, mesh, axes=axes)
        es = init_hybrid(graph, prog, None)
        gs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          shard0_specs(graph, axes))
        ess = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           _es_specs(es, axes))
        graph_d = jax.device_put(graph, gs)
        es_d = jax.device_put(es, ess)
        jitted = jax.jit(step, in_shardings=(gs, ess))
        iters = 0
        while not bool(quiescent(prog, es_d)) and iters < 500:
            es_d = jitted(graph_d, es_d)
            iters += 1
        got = np.asarray(jax.device_get(es_d.state[field]))
        np.testing.assert_array_equal(got, np.asarray(es_ref.state[field]))
        assert iters == iters_ref, (name, iters, iters_ref)
        for f in ('net_messages', 'net_local_messages', 'mem_messages'):
            assert int(getattr(es_d.counters, f)) == \\
                int(getattr(es_ref.counters, f)), (name, f)
        print('DIST', name, 'OK', iters)
    """)


def test_distributed_lane_frontiers_match_host():
    """K-lane multi-source program through the block-sharded distributed
    step: the (P, Vp, L) state shards on dim 0 like everything else, and
    the fixed point, iteration count and message counters are bit-exact
    against the host K-lane run (which itself equals K single runs —
    tests/test_multi.py)."""
    run_sub("""
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import NamedSharding
    from repro.core import build_partitioned_graph, bfs_partition, run_hybrid
    from repro.core.apps import MultiSourceMonotone
    from repro.core.distributed import make_dist_hybrid_step, _es_specs, shard0_specs
    from repro.core.engine_hybrid import init_hybrid
    from repro.core.runtime import quiescent
    from repro.data.graphs import grid_graph

    edges, w, n = grid_graph(6, 40, seed=3)
    part = bfs_partition(edges, n, 8, seed=1)
    graph = build_partitioned_graph(edges, n, part, weights=w, edge_blocks=8)
    prog = MultiSourceMonotone([0, 7, n - 1, 120], semiring='min_add')

    es_ref, iters_ref = run_hybrid(graph, prog)
    ref = np.asarray(es_ref.state['val'])

    mesh = make_host_mesh(2, 4)   # Auto axes: eager code runs on sharded inputs
    axes = ('data', 'model')
    step = make_dist_hybrid_step(prog, mesh, axes=axes)
    es = init_hybrid(graph, prog, None)
    gs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                      shard0_specs(graph, axes))
    ess = jax.tree.map(lambda s: NamedSharding(mesh, s), _es_specs(es, axes))
    graph_d = jax.device_put(graph, gs)
    es_d = jax.device_put(es, ess)
    jitted = jax.jit(step, in_shardings=(gs, ess))
    iters = 0
    while not bool(quiescent(prog, es_d)) and iters < 500:
        es_d = jitted(graph_d, es_d)
        iters += 1
    got = np.asarray(jax.device_get(es_d.state['val']))
    assert got.shape == ref.shape and got.ndim == 3
    np.testing.assert_array_equal(got, ref)
    assert iters == iters_ref, (iters, iters_ref)
    assert int(es_d.counters.net_messages) == int(es_ref.counters.net_messages)
    print('DIST LANES OK', iters, got.shape)
    """)


def _dist_ft_body(app: str) -> str:
    """Kill-and-resume on the shard_map path: run the FT driver with the
    distributed step + NamedShardings, interrupt after 3 iterations,
    restart from the checkpoint — final state and every paper counter must
    be bit-identical to the uninterrupted distributed run."""
    return """
    import tempfile
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import NamedSharding
    from repro.core import bfs_partition, build_partitioned_graph, \\
        hash_partition
    from repro.core.apps import SSSP, IncrementalPageRank
    from repro.core.apps.pagerank import pagerank_edge_weights
    from repro.core.distributed import make_dist_hybrid_step, _es_specs, \\
        shard0_specs
    from repro.core.engine_hybrid import init_hybrid
    from repro.data.graphs import grid_graph, rmat_graph
    from repro.ft import run_hybrid_ft

    if %(sssp)s:
        edges, w, n = grid_graph(6, 40, seed=3)
        part = bfs_partition(edges, n, 8, seed=1)
        prog, field = SSSP(source=0), 'dist'
    else:
        edges, n = rmat_graph(240, avg_degree=6, seed=7)
        part = hash_partition(n, 8, seed=2)
        w = pagerank_edge_weights(edges, n)
        prog, field = IncrementalPageRank(tolerance=1e-4), 'rank'
    graph = build_partitioned_graph(edges, n, part, weights=w,
                                    edge_blocks=8)   # one block per device
    mesh = make_host_mesh(2, 4)   # Auto axes: eager code runs on sharded inputs
    axes = ('data', 'model')
    step = make_dist_hybrid_step(prog, mesh, axes=axes)
    es0 = init_hybrid(graph, prog, None)
    gs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                      shard0_specs(graph, axes))
    ess = jax.tree.map(lambda s: NamedSharding(mesh, s),
                       _es_specs(es0, axes))
    graph_d = jax.device_put(graph, gs)
    with tempfile.TemporaryDirectory() as d:
        ref = run_hybrid_ft(graph_d, prog, step_fn=step, es_shardings=ess)
        r1 = run_hybrid_ft(graph_d, prog, step_fn=step, es_shardings=ess,
                           ckpt_dir=d, max_iters=3)
        assert r1.iterations == 3 < ref.iterations
        r2 = run_hybrid_ft(graph_d, prog, step_fn=step, es_shardings=ess,
                           ckpt_dir=d)
        assert r2.resumed_from is not None and \\
            r2.resumed_from.endswith('step_00000003')
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(r2.es.state[field])),
        np.asarray(jax.device_get(ref.es.state[field])))
    for f in ('iterations', 'net_messages', 'net_local_messages',
              'mem_messages'):
        assert int(getattr(r2.es.counters, f)) == \\
            int(getattr(ref.es.counters, f)), f
    np.testing.assert_array_equal(
        np.asarray(r2.es.counters.pseudo_supersteps),
        np.asarray(ref.es.counters.pseudo_supersteps))
    print('DIST FT %(app)s OK', ref.iterations)
    """ % {"sssp": repr(app == "sssp"), "app": app}


def test_distributed_ft_kill_resume_sssp():
    run_sub(_dist_ft_body("sssp"))


def test_distributed_ft_kill_resume_pagerank():
    run_sub(_dist_ft_body("pagerank"))


def test_lm_cell_runs_on_mesh():
    run_sub("""
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import ArchConfig, LayerSpec
    from repro.models.registry import get_model, param_shapes
    from repro.sharding.rules import param_specs, batch_spec
    from repro.sharding.util import sanitize_specs, named
    from repro.train.trainer import make_train_step
    from repro.optim.adamw import adamw_init

    # tiny MoE stack (ad-hoc; the LM preset zoo was pruned)
    cfg = ArchConfig(
        name='moe-smoke', family='moe', n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=32, vocab=256,
        pattern=(LayerSpec(mixer='attn', attn='full', moe=True),),
        n_experts=8, top_k=2, d_expert=32, tie_embeddings=True)
    api = get_model(cfg)
    mesh = jax.make_mesh((2, 4), ('data', 'model'))
    params = api.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    pspecs = sanitize_specs(param_specs(params), params, mesh)
    rng = np.random.RandomState(0)
    batch = {'tokens': jnp.asarray(rng.randint(0, cfg.vocab, (8, 32), dtype=np.int32)),
             'labels': jnp.asarray(rng.randint(0, cfg.vocab, (8, 32), dtype=np.int32))}
    bspecs = sanitize_specs(batch_spec(batch), batch, mesh)
    opt = adamw_init(params)
    from repro.optim.adamw import AdamWState
    ospecs = AdamWState(mu=pspecs, nu=pspecs, step=P())
    step_fn = make_train_step(cfg, api, peak_lr=1e-3)
    with jax.set_mesh(mesh):
        params = jax.device_put(params, named(pspecs, mesh))
        opt = jax.device_put(opt, named(ospecs, mesh))
        batch = jax.device_put(batch, named(bspecs, mesh))
        jitted = jax.jit(step_fn)
        losses = []
        for s in range(3):
            params, opt, m = jitted(params, opt, batch, jnp.asarray(s))
            losses.append(float(m['loss']))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses   # same batch => must improve
    print('LM MESH OK', losses)
    """)


def test_decode_cell_seq_sharded_cache():
    run_sub("""
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.configs import ArchConfig, LayerSpec
    from repro.models.registry import get_model
    from repro.sharding.rules import cache_specs
    from repro.sharding.util import sanitize_specs, named

    # tiny dense GQA transformer (ad-hoc; the LM preset zoo was pruned)
    cfg = ArchConfig(
        name='dense-smoke', family='dense', n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
        pattern=(LayerSpec(mixer='attn', attn='full'),), tie_embeddings=True)
    api = get_model(cfg)
    mesh = jax.make_mesh((2, 4), ('data', 'model'))
    params = api.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab, (2, 16), dtype=np.int32))

    # unsharded reference
    cache = api.init_cache(cfg, 2, 32, jnp.float32)
    logits_ref, cache_ref = api.prefill(params, {'tokens': tokens}, cache, cfg)
    step_ref, _ = api.decode_step(params, tokens[:, :1], cache_ref, 16, cfg)

    # sequence-sharded cache on the mesh
    cache = api.init_cache(cfg, 2, 32, jnp.float32)
    cspecs = sanitize_specs(cache_specs(cache), cache, mesh)
    with jax.set_mesh(mesh):
        cache = jax.device_put(cache, named(cspecs, mesh))
        logits, cache = jax.jit(lambda p, b, c: api.prefill(p, b, c, cfg))(
            params, {'tokens': tokens}, cache)
        step, _ = jax.jit(lambda p, t, c: api.decode_step(p, t, c, 16, cfg))(
            params, tokens[:, :1], cache)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(step), np.asarray(step_ref),
                               rtol=2e-3, atol=2e-3)
    print('DECODE MESH OK')
    """)


def test_hybrid_sync_on_pod_mesh():
    run_sub("""
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import ArchConfig, LayerSpec
    from repro.core.hybrid_sync import (global_sync, inner_steps, outer_init,
                                        stack_pods)
    from repro.models.registry import get_model
    from repro.optim.adamw import adamw_init
    from repro.sharding.rules import param_specs, prepend_axis
    from repro.sharding.util import sanitize_specs, named
    from repro.train.trainer import make_train_step

    # tiny dense GQA transformer (ad-hoc; the LM preset zoo was pruned)
    cfg = ArchConfig(
        name='dense-smoke', family='dense', n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
        pattern=(LayerSpec(mixer='attn', attn='full'),), tie_embeddings=True)
    api = get_model(cfg)
    mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'))
    params = api.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    step_fn = make_train_step(cfg, api, peak_lr=1e-3)

    n_pods = 2
    pp = stack_pods(params, n_pods)
    oo = stack_pods(adamw_init(params), n_pods)
    pspecs = prepend_axis(sanitize_specs(param_specs(params), params, mesh), 'pod')
    pspecs = sanitize_specs(pspecs, pp, mesh)
    rng = np.random.RandomState(0)
    batch = {'tokens': jnp.asarray(rng.randint(0, cfg.vocab, (2, 4, 32), dtype=np.int32)),
             'labels': jnp.asarray(rng.randint(0, cfg.vocab, (2, 4, 32), dtype=np.int32))}
    outer = outer_init(params, n_pods)
    with jax.set_mesh(mesh):
        pp = jax.device_put(pp, named(pspecs, mesh))
        inner = jax.jit(lambda p, o, b, s: inner_steps(step_fn, p, o, b, s))
        for s in range(2):
            pp, oo, m = inner(pp, oo, batch, jnp.asarray(s))
        pp, outer = jax.jit(global_sync)(pp, outer)
    div = max(jax.tree.leaves(jax.tree.map(
        lambda p: float(jnp.max(jnp.abs(p[0] - p[1]))), pp)))
    assert div == 0.0, div
    print('HYBRID SYNC MESH OK')
    """)
