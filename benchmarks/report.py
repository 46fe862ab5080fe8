"""Generate the EXPERIMENTS.md §Dry-run / §Roofline tables from
results/dryrun/*.json.

    PYTHONPATH=src python -m benchmarks.report [--out results/roofline.md]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ARCH_ORDER = [
    "graphhp-paper",
]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k",
               "hybrid_iteration", "global_sync"]


def model_flops_per_device(rec) -> float | None:
    """6·N·D (train) / 2·N·D (inference fwd), active params for MoE,
    divided over the mesh."""
    from repro.configs.base import SHAPES, get_config
    from repro.models.registry import count_params
    if rec["arch"] == "graphhp-paper" or rec["shape"] not in SHAPES:
        return None
    try:
        cfg = get_config(rec["arch"])
    except KeyError:        # result row from a since-pruned LM preset
        return None
    shape = SHAPES[rec["shape"]]
    n = count_params(cfg, active_only=True)
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        mult = 6.0
    elif shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        mult = 2.0
    else:  # decode: one token per sequence
        d = shape.global_batch
        mult = 2.0
    return mult * n * d / rec.get("devices", 256)


def rows(out_dir: str, mesh: str):
    out = []
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            fn = os.path.join(out_dir, f"{arch}__{shape}__{mesh}.json")
            if not os.path.exists(fn):
                continue
            with open(fn) as f:
                rec = json.load(f)
            out.append(rec)
    return out


def fmt(x, unit=""):
    if x is None:
        return "—"
    if x == 0:
        return "0"
    for div, suf in ((1e15, "P"), (1e12, "T"), (1e9, "G"), (1e6, "M"),
                     (1e3, "k")):
        if abs(x) >= div:
            return f"{x/div:.2f}{suf}{unit}"
    return f"{x:.3g}{unit}"


def render(out_dir: str = "results/dryrun") -> str:
    lines = []
    for mesh, title in (("single", "single-pod (16×16 = 256 chips)"),
                        ("multi", "multi-pod (2×16×16 = 512 chips)")):
        recs = rows(out_dir, mesh)
        if not recs:
            continue
        lines.append(f"\n### Mesh: {title}\n")
        lines.append(
            "| arch | shape | status | mem/dev | t_compute | t_memory | "
            "t_collective | dominant | MODEL/HLO flops | note |")
        lines.append("|---|---|---|---|---|---|---|---|---|---|")
        for rec in recs:
            arch, shape = rec["arch"], rec["shape"]
            if rec["status"] == "skip":
                lines.append(f"| {arch} | {shape} | SKIP | — | — | — | — | — "
                             f"| — | {rec['reason'][:60]}… |")
                continue
            if rec["status"] != "ok":
                lines.append(f"| {arch} | {shape} | **FAIL** | — | — | — | — "
                             f"| — | — | {rec.get('error','')[:60]} |")
                continue
            t = rec["roofline"]
            mem = rec.get("memory", {}).get("bytes_per_device", 0) / 2**30
            mf = model_flops_per_device(rec)
            ratio = f"{mf / t['flops']:.2f}" if mf and t["flops"] else "—"
            note = ""
            if mem > 16:
                note = "exceeds v5e HBM → §Perf target"
            lines.append(
                f"| {arch} | {shape} | ok | {mem:.1f}GiB "
                f"| {t['t_compute_s']*1e3:.1f}ms | {t['t_memory_s']*1e3:.1f}ms "
                f"| {t['t_collective_s']*1e3:.1f}ms | {t['dominant']} "
                f"| {ratio} | {note} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    text = render(args.dir)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
