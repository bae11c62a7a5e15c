"""Roofline table renderer: reads dry-run JSONs and prints the per-cell
three-term analysis (EXPERIMENTS.md §Roofline is generated from this).

Peaks come from ``repro.utils.machine.machine_profile`` — the published
peaks of the jax device kind, overridable with
``--peak-flops``/``--hbm-bw``/``--link-bw`` (or
``REPRO_PEAK_FLOPS``/``REPRO_HBM_BW``/``REPRO_LINK_BW``); a device without
published peaks must be given all three. A ladder ``BENCH_*.json`` (its
``kernels`` key) renders as the per-kernel achieved bytes/s table instead,
with peak shares only against the profile the ladder recorded on the chip
(the peak flags apply to the dry-run table alone).
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional

from repro.utils.machine import MachineProfile, machine_profile


def load(path: str):
    with open(path) as f:
        return json.load(f)


def render(results: List[dict], *, only_single_pod: bool = True,
           profile: Optional[MachineProfile] = None) -> str:
    # the dry run computes its terms against the v5e peaks (launch/dryrun.py)
    prof = profile or machine_profile(device_kind="TPU v5 lite")
    lines = [f"profile: {prof.name}  peak_flops={prof.peak_flops:.3g}  "
             f"hbm_bw={prof.hbm_bw:.3g}  link_bw={prof.link_bw:.3g}"]
    hdr = (f"{'arch:shape':44s} {'kind':8s} {'t_comp(s)':>10s} {'t_mem(s)':>10s}"
           f" {'t_coll(s)':>10s} {'bottleneck':>11s} {'useful':>7s} {'roofl':>6s}")
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for r in sorted(results, key=lambda r: (r["arch"], r["shape"])):
        if r.get("status") == "skipped":
            if not r.get("multi_pod", False):
                lines.append(f"{r['arch']+':'+r['shape']:44s} SKIP "
                             f"({r['skipped'][:70]})")
            continue
        if r.get("status") == "error":
            lines.append(f"{r['arch']+':'+r['shape']:44s} ERROR "
                         f"{r.get('error','')[:70]}")
            continue
        if only_single_pod and r.get("multi_pod"):
            continue
        lines.append(
            f"{r['arch']+':'+r['shape']:44s} {r['kind']:8s} "
            f"{r['t_compute']:10.4f} {r['t_memory']:10.4f} "
            f"{r['t_collective']:10.4f} {r['bottleneck']:>11s} "
            f"{r['hlo_useful_ratio']:7.3f} {r['roofline_fraction']:6.3f}")
    return "\n".join(lines)


def render_kernels(kernels: Dict[str, dict]) -> str:
    """The ladder BENCH json's ``kernels`` key as an achieved bytes/s table
    (one row per registered DBS kernel). Shares of the HBM peak are shown
    only when the json carries the profile of the chip the kernels ran on
    compiled; interpret-mode wall times get no peak column."""
    p = kernels.get("profile")
    prof = MachineProfile(**p) if isinstance(p, dict) else None
    lines = [f"profile: {prof.name}  hbm_bw={prof.hbm_bw:.3g} B/s" if prof
             else "profile: none (interpret mode; no peak shares)"]
    peak = f" {'vs peak':>8s}" if prof else ""
    hdr = (f"{'kernel':10s} {'write us':>9s} {'write B/s':>11s}{peak} "
           f"{'read us':>9s} {'read B/s':>11s}{peak} {'identical':>9s}")
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for name in sorted(kernels):
        row = kernels[name]
        if not isinstance(row, dict) or "write_us" not in row:
            continue
        w_bps, r_bps = row["write_bytes_per_s"], row["read_bytes_per_s"]
        w_peak = f" {w_bps / prof.hbm_bw:8.2e}" if prof else ""
        r_peak = f" {r_bps / prof.hbm_bw:8.2e}" if prof else ""
        lines.append(
            f"{name:10s} {row['write_us']:9.1f} {w_bps:11.3g}{w_peak} "
            f"{row['read_us']:9.1f} {r_bps:11.3g}{r_peak} "
            f"{str(row.get('identical', '-')):>9s}")
    return "\n".join(lines)


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="results/roofline_single.json")
    ap.add_argument("--all-meshes", action="store_true")
    ap.add_argument("--peak-flops", type=float, default=None,
                    help="override peak flops/s per chip")
    ap.add_argument("--hbm-bw", type=float, default=None,
                    help="override HBM bytes/s per chip")
    ap.add_argument("--link-bw", type=float, default=None,
                    help="override ICI bytes/s per link")
    args = ap.parse_args()
    doc = load(args.json)
    if isinstance(doc, dict):           # a ladder BENCH json or its kernels
        print(render_kernels(doc.get("kernels", doc)))
        return
    explicit = (args.peak_flops, args.hbm_bw, args.link_bw)
    prof = (machine_profile(*explicit)
            if any(v is not None for v in explicit) else None)
    print(render(doc, only_single_pod=not args.all_meshes, profile=prof))


if __name__ == "__main__":
    main()
