"""Roofline term derivation from compiled dry-run artifacts.

compute term    = HLO_FLOPs / peak_FLOPs          (per chip, cost_analysis)
memory term     = HLO_bytes / HBM_bw              (per chip, cost_analysis)
collective term = collective_bytes / ICI_bw       (per link, parsed from HLO)

with the peaks of the target ``device_kind`` from :data:`HARDWARE`.

collective_bytes is NOT in cost_analysis: we parse the compiled module text
and sum wire bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, with op-specific ring-cost factors.
"""

from __future__ import annotations

import re

#: Published peaks, keyed by ``jax.Device.device_kind`` — the one source of
#: hardware constants in the repo.  TPU v5e: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16 and 16 GB HBM at 819 GB/s per chip; 1,600
#: Gbit/s of chip-to-chip interconnect per chip).  The ring-cost factors of
#: :func:`collective_bytes` count the bytes one link carries, so the ICI
#: figure is per link: the per-chip 200 GB/s over the four links a chip has
#: in the v5e's 2-D torus.
HARDWARE = {
    "TPU v5 lite": {"peak_flops_bf16_per_chip": 197e12,
                    "hbm_bytes_per_s_per_chip": 819e9,
                    "ici_bytes_per_s_per_link": 1600e9 / 8 / 4},
}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device raises."""
    try:
        return HARDWARE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add it to HARDWARE with its "
                       f"source") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(text: str) -> int:
    """Sum sizes of every `dtype[dims]` group in a type string."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Wire bytes per collective kind from a compiled HLO module.

    Ring costs (n = group size, parsed from replica_groups when present):
      all-reduce      2·(n-1)/n · size
      all-gather      (n-1)/n · result_size
      reduce-scatter  (n-1)/n · operand_size
      all-to-all      (n-1)/n · size
      collective-permute  size
    """
    out: dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (.+?) (\w[\w\-]*)\(", ls)
        if not m:
            continue
        result_type, op = m.groups()
        kind = None
        for k in _COLLECTIVES:
            if op == k or op.startswith(k + "-start") or op == k + "-done":
                kind = k
                break
        if kind is None or op.endswith("-done"):
            continue
        size = _shape_bytes(result_type)
        if kind == "reduce-scatter":
            # operand = result * n; parse operands inside parens instead
            inner = ls[ls.index("(") + 1:]
            size = _shape_bytes(inner.split("),")[0])
        n = _group_size(ls)
        frac = (n - 1) / n if n > 1 else 0.0
        if kind == "all-reduce":
            size = 2 * size * frac
        elif kind == "collective-permute":
            size = size * (1.0 if n > 1 else 0.0)
        else:
            size = size * frac
        out[kind] += size
    out["total"] = sum(out.values())
    return out


def _group_size(line: str) -> int:
    # replica_groups={{0,1,2,...},{...}} or replica_groups=[8,32]<=[256]
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"source_target_pairs=", line)
    if m:
        return 2    # permute: pairwise
    return 1


def roofline_terms(cost: dict, hlo_text: str, device_kind: str) -> dict:
    hw = peaks(device_kind)
    flops = float(cost.get("flops", 0.0) or 0.0)
    bytes_hbm = float(cost.get("bytes accessed", 0.0) or 0.0)
    coll = collective_bytes(hlo_text)
    t_compute = flops / hw["peak_flops_bf16_per_chip"]
    t_memory = bytes_hbm / hw["hbm_bytes_per_s_per_chip"]
    t_coll = coll["total"] / hw["ici_bytes_per_s_per_link"]
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    return {
        "flops": flops, "hbm_bytes": bytes_hbm,
        "collective_bytes": coll["total"],
        "collectives": {k: v for k, v in coll.items() if k != "total"},
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
    }


def model_flops(n_params: int, n_tokens: int, kind: str = "train") -> float:
    """MODEL_FLOPS = 6·N·D for training, 2·N·D for inference forward."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_params * n_tokens
