"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e": 197 TFLOP/s
bf16, 394 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect.  A device kind that is not in the table is an
error, never a default.
"""

from __future__ import annotations

HARDWARE = {
    "TPU v5 lite": {"peak_flops_bf16": 197e12,
                    "hbm_bytes": 16e9,
                    "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 1600e9 / 8},
}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    try:
        return HARDWARE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add it to bench/hardware.py with "
                       f"its source") from None
