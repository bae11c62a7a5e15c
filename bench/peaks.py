"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind`` (lower case). A device that is not here is an error: a
share of a peak is never computed against a guessed or default peak.

TPU v5e: Google Cloud documentation, "TPU v5e"
(https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
"""
from __future__ import annotations

from typing import Dict

SOURCE = "Google Cloud documentation, TPU v5e (cloud.google.com/tpu/docs/v5e)"

_V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12, "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8}

PEAKS: Dict[str, Dict[str, float]] = {
    "tpu v5 lite": _V5E,
    "tpu v5e": _V5E,
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; raises KeyError for an unknown one."""
    key = device_kind.strip().lower()
    if key not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})")
    return dict(PEAKS[key])
