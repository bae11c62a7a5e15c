# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark entry point:

  table1 (IOPS ladder)      -> paper Table I analogue + snapshot degradation
  table2 (bandwidth ladder) -> paper Table II analogue
  kernels                   -> reference-path microbenches
  roofline                  -> rendered from results/*.json when present
"""
from __future__ import annotations

import os

from repro.utils.compile_cache import place_compile_cache


def main() -> None:
    place_compile_cache()
    print("name,us_per_call,derived")
    from benchmarks import table1_iops, table2_bandwidth, kernels_bench
    for r in table1_iops.run(n_requests=256):
        name = f"{r['bench']}/{r['column']}/{r['layer']}/{r['kind']}"
        derived = f"{r['ops_per_s']:.0f}ops/s"
        if "layers_per_read" in r:
            derived += f";{r['layers_per_read']:.1f}layers/read"
        print(f"{name},{r['us_per_call']:.1f},{derived}", flush=True)
    for r in table2_bandwidth.run(n_extents_io=24):
        name = f"{r['bench']}/{r['column']}/{r['layer']}/{r['kind']}"
        print(f"{name},{r['us_per_call']:.1f},{r['mb_per_s']:.1f}MB/s",
              flush=True)
    for r in kernels_bench.run():
        name = f"{r['bench']}/{r['column']}/{r['layer']}/{r['kind']}"
        print(f"{name},{r['us_per_call']:.1f},-", flush=True)
    path = "results/roofline_single.json"
    if os.path.exists(path):
        from benchmarks import roofline
        print("\n# roofline (single-pod, from dry-run artifacts)")
        print(roofline.render(roofline.load(path)))


if __name__ == "__main__":
    main()
