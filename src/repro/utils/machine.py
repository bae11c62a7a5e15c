"""Machine roofline profile: the published peaks of the device in use.

One ``machine_profile()`` feeds every consumer (``launch/dryrun.py``,
``benchmarks/roofline.py``, the ladder's kernel table). Peaks come from the
table below, keyed by the jax ``device_kind``. A device that is not in the
table is an error unless every peak is given explicitly — as arguments or
as the ``REPRO_PEAK_FLOPS`` / ``REPRO_HBM_BW`` / ``REPRO_LINK_BW``
environment variables, which also override single peaks of a known device.
No device is ever handed another device's peaks.
"""
from __future__ import annotations

import os
from dataclasses import asdict, dataclass
from typing import Optional


@dataclass(frozen=True)
class MachineProfile:
    name: str
    peak_flops: float       # peak matmul flops/s per chip (bf16)
    hbm_bw: float           # HBM bytes/s per chip
    link_bw: float          # chip-to-chip interconnect bytes/s per chip

    def to_dict(self) -> dict:
        return asdict(self)


# Published per-chip peaks (Google Cloud documentation, "TPU v5e"): bf16
# matmul, HBM bandwidth, and interchip interconnect — 1,600 Gbit/s, i.e.
# 200e9 B/s. The table holds only devices whose figures were checked
# against that source; any other device names its peaks explicitly.
V5E = MachineProfile("tpu-v5e", 197e12, 819e9, 200e9)

# jax device_kind (lower-cased, exact) -> published peaks
_KNOWN = {
    "tpu v5 lite": V5E,
    "tpu v5e": V5E,
}


def _env(name: str) -> Optional[float]:
    v = os.environ.get(name)
    return float(v) if v else None


def machine_profile(peak_flops: Optional[float] = None,
                    hbm_bw: Optional[float] = None,
                    link_bw: Optional[float] = None, *,
                    device_kind: Optional[str] = None) -> MachineProfile:
    """Resolve the roofline peaks of ``device_kind`` (default: the kind of
    ``jax.devices()[0]``). Explicit arguments win over the ``REPRO_*``
    environment variables, which win over the table. Raises ``ValueError``
    for a kind the table does not hold unless all three peaks are given."""
    peak_flops = peak_flops if peak_flops is not None else \
        _env("REPRO_PEAK_FLOPS")
    hbm_bw = hbm_bw if hbm_bw is not None else _env("REPRO_HBM_BW")
    link_bw = link_bw if link_bw is not None else _env("REPRO_LINK_BW")
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    base = _KNOWN.get(device_kind.lower())
    explicit = (peak_flops, hbm_bw, link_bw)
    if base is None:
        if any(v is None for v in explicit):
            raise ValueError(
                f"no published peaks for device_kind {device_kind!r} "
                f"(known: {', '.join(sorted(_KNOWN))}); give peak_flops, "
                "hbm_bw and link_bw explicitly or set REPRO_PEAK_FLOPS, "
                "REPRO_HBM_BW and REPRO_LINK_BW")
        return MachineProfile(device_kind, *explicit)
    name = base.name
    if any(v is not None for v in explicit):
        name += "+overrides"
    return MachineProfile(
        name=name,
        peak_flops=peak_flops if peak_flops is not None else base.peak_flops,
        hbm_bw=hbm_bw if hbm_bw is not None else base.hbm_bw,
        link_bw=link_bw if link_bw is not None else base.link_bw)
