import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first initialization).

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the single-pod (16×16) and multi-pod (2×16×16) production meshes, record
memory_analysis / cost_analysis / parsed collective bytes per cell.

    PYTHONPATH=src python -m repro.launch.dryrun [--arch X] [--shape Y]
        [--mesh single|multi|both] [--out results/dryrun]

Each cell writes its JSON incrementally, so a long sweep is resumable
(--skip-done).  Failures (sharding mismatch, OOM at compile, unsupported
collective) are recorded — they are bugs in the system, per the brief.
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs.base import SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell, runnable

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", ".."))

# The LM preset zoo was pruned; LM cells now dry-run only via an explicit
# --arch against a registered config.  The default sweep is the paper's own
# graph workload (--graphhp / run_graphhp_cell).
ARCHS: list[str] = []

# the chip the production meshes are made of, as jax names its device_kind
DEVICE_KIND = "TPU v5 lite"


def _compile_once(cfg, shape, mesh, multi_pod, microbatches: int = 1):
    cell = build_cell(cfg, shape, mesh, multi_pod, microbatches=microbatches)
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings)
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    return compiled


def _probe_cfg(cfg, k_units: int):
    """Same arch with k repeating units (head/tail dropped for the probe)."""
    import dataclasses
    n = (cfg.first_k_dense or 0) + k_units * len(cfg.pattern)
    repl = {"n_layers": n}
    if cfg.family == "audio":
        repl["enc_layers"] = k_units
    return dataclasses.replace(cfg, **repl)


def _n_units(cfg) -> int:
    return (cfg.n_layers - (cfg.first_k_dense or 0)) // len(cfg.pattern)


def attn_correction_flops(cfg, shape, mesh) -> float:
    """Per-device analytic flops for full-attention layers whose flash block
    grid stays ROLLED at this sequence length (32k prefill): XLA's cost
    analysis sees one (cq × ckv) block of the scan, this adds the other
    nq·nkv−1 blocks.  Train/decode cells and window layers are unrolled or
    loop-free and need no correction (see models/attention.py)."""
    if shape.kind != "prefill":
        return 0.0
    s = shape.seq_len - (cfg.vis_tokens if cfg.family == "vlm" else 0)
    s_tot = s + (cfg.vis_tokens if cfg.family == "vlm" else 0)
    b = shape.global_batch
    cq, ckv = 512, 1024
    total = 0.0
    for spec in cfg.layers():
        if spec.mixer != "attn" or spec.attn in ("none", "window"):
            continue
        nq, nkv = s_tot // cq, s_tot // ckv
        if nq * nkv <= 64:
            continue
        hd = (cfg.qk_nope_dim + cfg.qk_rope_dim) if spec.attn == "mla" \
            else cfg.head_dim
        total += 4.0 * b * cfg.n_heads * hd * (s_tot * s_tot - cq * ckv)
    if cfg.family == "audio":                    # decoder cross-attention
        skv = cfg.enc_frames
        ckv2 = min(1024, skv)
        nq = s_tot // cq
        if nq * max(1, skv // ckv2) > 64:
            total += 4.0 * b * cfg.n_heads * cfg.head_dim * cfg.n_layers \
                * (s_tot * skv - cq * ckv2)
    # per-device: heads shard over model when divisible, batch over data
    tp = mesh.shape.get("model", 1)
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    div = dp * (tp if cfg.n_heads % max(tp, 1) == 0 else 1)
    return total / max(div, 1)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             verbose: bool = True, probes: bool = True,
             variant: str = "", microbatches: int = 1) -> dict:
    from benchmarks.roofline import collective_bytes, roofline_terms

    mesh_tag = ("multi" if multi_pod else "single") + \
        (f"-{variant}" if variant else "")
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "status": "unknown"}
    try:
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
    except KeyError as e:
        rec.update(status="fail", error=f"unknown arch/shape: {e}")
        return _write(rec, out_dir)

    ok, why = runnable(cfg, shape)
    if not ok:
        rec.update(status="skip", reason=why)
        return _write(rec, out_dir)

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mb = microbatches if shape.kind == "train" else 1
        compiled = _compile_once(cfg, shape, mesh, multi_pod, mb)
        t_full = time.time() - t0
        mem = compiled.memory_analysis()
        cost = dict(compiled.cost_analysis() or {})
        hlo = compiled.as_text()
        coll_full = collective_bytes(hlo)["total"]

        # --- scan-body extrapolation: cost_analysis counts a lax.scan body
        # once; probe with 1 and 2 units, add (n_units-1) * (B - A).
        extrap = {}
        if probes:
            # C0 = zero scanned units (embed/loss/optimizer base), C1 = one
            # unit; body = C1 - C0 is exactly one scan-body's cost whether or
            # not XLA unrolls the length-1 loop.
            c0_comp = _compile_once(_probe_cfg(cfg, 0), shape, mesh,
                                    multi_pod, mb)
            c1_comp = _compile_once(_probe_cfg(cfg, 1), shape, mesh,
                                    multi_pod, mb)
            c0 = dict(c0_comp.cost_analysis() or {})
            c1 = dict(c1_comp.cost_analysis() or {})
            coll_0 = collective_bytes(c0_comp.as_text())["total"]
            coll_1 = collective_bytes(c1_comp.as_text())["total"]
            n_u = _n_units(cfg)
            for key in ("flops", "bytes accessed"):
                body = max(0.0, float(c1.get(key, 0) or 0)
                           - float(c0.get(key, 0) or 0))
                cost[key] = float(cost.get(key, 0) or 0) + (n_u - 1) * body
            coll_full += (n_u - 1) * max(0.0, coll_1 - coll_0)
            extrap = {"n_units": n_u,
                      "unit_flops": max(0.0, float(c1.get("flops", 0) or 0)
                                        - float(c0.get("flops", 0) or 0)),
                      "unit_coll_bytes": max(0.0, coll_1 - coll_0)}

        attn_fix = attn_correction_flops(cfg, shape, mesh)
        cost["flops"] = float(cost.get("flops", 0) or 0) + attn_fix
        if mb > 1:
            # the grad-accumulation scan body is also counted once; one
            # microbatch's cost × M approximates the step (the optimizer
            # update outside the scan is over-scaled by M — negligible).
            cost["flops"] *= mb
            cost["bytes accessed"] = float(
                cost.get("bytes accessed", 0) or 0) * mb
            coll_full *= mb

        terms = roofline_terms(cost, hlo, DEVICE_KIND)
        terms["collective_bytes"] = coll_full
        from benchmarks.roofline import peaks
        terms["t_collective_s"] = (
            coll_full / peaks(DEVICE_KIND)["ici_bytes_per_s_per_link"])
        terms["dominant"] = max(
            (("compute", terms["t_compute_s"]),
             ("memory", terms["t_memory_s"]),
             ("collective", terms["t_collective_s"])),
            key=lambda kv: kv[1])[0]
        rec.update(
            status="ok",
            compile_s=round(t_full, 1),
            devices=int(mesh.size),
            memory=_mem_dict(mem),
            roofline=terms,
            extrapolation=extrap,
            attn_correction_flops=attn_fix,
            hlo_bytes=len(hlo),
        )
        if verbose:
            print(f"[ok] {arch} {shape_name} {mesh_tag}: "
                  f"mem/dev={rec['memory'].get('bytes_per_device', 0)/2**30:.2f}GiB "
                  f"flops={terms['flops']:.3e} "
                  f"coll={terms['collective_bytes']:.3e}B "
                  f"dom={terms['dominant']} ({time.time()-t0:.0f}s)",
                  flush=True)
    except Exception as e:
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        if verbose:
            print(f"[FAIL] {arch} {shape_name} {mesh_tag}: {e}", flush=True)
    return _write(rec, out_dir)


def run_graphhp_cell(multi_pod: bool, out_dir: str, smoke: bool = False,
                     wire_bf16: bool = False, variant: str = "") -> dict:
    """The paper's own workload: one distributed hybrid global iteration."""
    from benchmarks.roofline import roofline_terms
    from repro.configs.graphhp_paper import CONFIG, SMOKE
    from repro.core.apps.sssp import SSSP
    from repro.core.distributed import (block_graph_shapes,
                                        engine_state_shapes,
                                        make_dist_hybrid_step)

    import jax.numpy as jnp
    gcfg = SMOKE if smoke else CONFIG
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n_part = mesh.size          # one partition per device
    mesh_tag = ("multi" if multi_pod else "single") + \
        (f"-{variant}" if variant else "")
    rec = {"arch": gcfg.name, "shape": "hybrid_iteration", "mesh": mesh_tag,
           "status": "unknown"}
    t0 = time.time()
    try:
        graph = block_graph_shapes(
            n_part, gcfg.vertices_per_partition, gcfg.edges_per_partition,
            gcfg.exports_per_partition, gcfg.halo_per_partition)
        prog = SSSP(source=0)
        es = engine_state_shapes(prog, graph)
        step = make_dist_hybrid_step(
            prog, mesh, axes=axes, max_local_steps=10_000,
            wire_dtype=jnp.bfloat16 if wire_bf16 else None)
        from repro.core.distributed import _es_specs, shard0_specs
        from jax.sharding import NamedSharding
        gs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          shard0_specs(graph, axes))
        ess = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           _es_specs(es, axes))
        with jax.set_mesh(mesh):
            jitted = jax.jit(lambda g, e: step(g, e),
                             in_shardings=(gs, ess))
            lowered = jitted.lower(graph, es)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        rec.update(status="ok", devices=int(mesh.size),
                   memory=_mem_dict(mem),
                   roofline=roofline_terms(cost or {}, hlo, DEVICE_KIND),
                   elapsed_s=round(time.time() - t0, 1))
        print(f"[ok] graphhp {mesh_tag}: {rec['roofline']['dominant']}",
              flush=True)
    except Exception as e:
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[FAIL] graphhp {mesh_tag}: {e}", flush=True)
    return _write(rec, out_dir)


def run_sync_cell(arch: str, out_dir: str, compress: bool = True,
                  variant: str = "") -> dict:
    """Lower the hybrid-sync GLOBAL PHASE (cross-pod delta exchange with
    int8 error-feedback compression) on the multi-pod mesh — GraphHP's
    once-per-iteration exchange at training scale.  The int8 wire shows up
    directly in the parsed collective schedule."""
    import functools

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.roofline import roofline_terms
    from repro.core.hybrid_sync import OuterState, global_sync, outer_init
    from repro.models.registry import param_shapes
    from repro.optim.compression import ErrorFeedbackState
    from repro.sharding.rules import param_specs
    from repro.sharding.util import named, sanitize_specs

    tag = "multi" + (f"-{variant}" if variant else "")
    rec = {"arch": arch, "shape": "global_sync", "mesh": tag,
           "status": "unknown", "compress": compress}
    t0 = time.time()
    try:
        cfg = get_config(arch)
        mesh = make_production_mesh(multi_pod=True)
        n_pods = mesh.shape["pod"]
        pshapes = param_shapes(cfg, jnp.bfloat16)
        pspecs = sanitize_specs(param_specs(pshapes), pshapes, mesh)
        pp_shapes = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((n_pods,) + l.shape, l.dtype),
            pshapes)
        pp_specs = jax.tree.map(lambda s: P("pod", *tuple(s)), pspecs,
                                is_leaf=lambda x: isinstance(x, P))
        outer_shapes = jax.eval_shape(lambda p: outer_init(p, n_pods),
                                      pshapes)
        outer_specs = OuterState(
            anchor=pspecs, momentum=pspecs,
            ef=ErrorFeedbackState(residual=pp_specs))
        # pod-REPLICATED specs pin the cross-pod gather onto the quantized
        # tensors (wire bytes = int8, not dequantized f32)
        gspecs = jax.tree.map(lambda s: P(None, *tuple(s)), pspecs,
                              is_leaf=lambda x: isinstance(x, P))
        fn = functools.partial(global_sync, compress=compress,
                               gathered_specs=gspecs)
        with jax.set_mesh(mesh):
            compiled = jax.jit(fn, in_shardings=(
                named(pp_specs, mesh), named(outer_specs, mesh))
            ).lower(pp_shapes, outer_shapes).compile()
        cost = dict(compiled.cost_analysis() or {})
        hlo = compiled.as_text()
        terms = roofline_terms(cost, hlo, DEVICE_KIND)
        rec.update(status="ok", devices=int(mesh.size),
                   memory=_mem_dict(compiled.memory_analysis()),
                   roofline=terms,
                   elapsed_s=round(time.time() - t0, 1))
        print(f"[ok] {arch} global_sync {tag} compress={compress}: "
              f"coll={terms['collective_bytes']:.3e}B "
              f"dom={terms['dominant']}", flush=True)
    except Exception as e:
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[FAIL] {arch} global_sync: {e}", flush=True)
    return _write(rec, out_dir)


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    keys = ("temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    d = {k: int(getattr(mem, k, 0)) for k in keys if hasattr(mem, k)}
    d["bytes_per_device"] = (d.get("temp_size_in_bytes", 0)
                             + d.get("argument_size_in_bytes", 0))
    return d


def _write(rec: dict, out_dir: str) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    fn = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    with open(os.path.join(out_dir, fn), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--graphhp", action="store_true",
                    help="also dry-run the paper's graph engine")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="enable sequence-parallel residual streams "
                         "(the §Perf optimized variant)")
    ap.add_argument("--variant", default="",
                    help="tag appended to the mesh name in output JSONs")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="grad-accumulation microbatches for train cells "
                         "(§Perf memory optimization)")
    ap.add_argument("--graphhp-wire-bf16", action="store_true",
                    help="quantize graph-engine exchange payloads to bf16 "
                         "(§Perf collective optimization)")
    args = ap.parse_args()

    if args.seq_parallel:
        from repro.sharding.util import set_seq_parallel
        set_seq_parallel(True)
        if not args.variant:
            args.variant = "sp" 

    archs = [args.arch] if args.arch else ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_fail = 0
    for multi in meshes:
        tag = "multi" if multi else "single"
        for arch in archs:
            for shape in shapes:
                vtag = tag + (f"-{args.variant}" if args.variant else "")
                fn = os.path.join(args.out, f"{arch}__{shape}__{vtag}.json")
                if args.skip_done and os.path.exists(fn):
                    with open(fn) as f:
                        if json.load(f).get("status") in ("ok", "skip"):
                            continue
                rec = run_cell(arch, shape, multi, args.out,
                               variant=args.variant,
                               microbatches=args.microbatches)
                n_fail += rec["status"] == "fail"
        if args.graphhp:
            rec = run_graphhp_cell(multi, args.out,
                                   wire_bf16=args.seq_parallel is None and False
                                   or args.graphhp_wire_bf16,
                                   variant=args.variant)
            n_fail += rec["status"] == "fail"
    print(f"dry-run complete; failures: {n_fail}", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
