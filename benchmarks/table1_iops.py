"""Table I analogue: random single-block ops/s through the engine ladder."""
from __future__ import annotations

from benchmarks.ladder import ROWS, COLUMNS, run_ladder, snapshot_degradation
from repro.utils.compile_cache import place_compile_cache


def run(n_requests: int = 384) -> list:
    rows = []
    for kind in ("read", "write"):
        res = run_ladder(n_requests=n_requests, payload_elems=64, kind=kind)
        for row in ROWS:
            for col in COLUMNS:
                rows.append({
                    "bench": "table1_iops", "kind": kind, "layer": row,
                    "column": col, "ops_per_s": res[col][row],
                    "us_per_call": 1e6 / res[col][row],
                })
    deg = snapshot_degradation()
    for key, series in deg.items():
        for rec in series:
            rows.append({"bench": "snapshot_degradation", "kind": "read",
                         "layer": f"snapshots={rec['snapshots']}",
                         "column": key, "ops_per_s": rec["ops_per_s"],
                         "us_per_call": 1e6 / rec["ops_per_s"],
                         "layers_per_read": rec["layers_per_read"]})
    return rows


def main():
    place_compile_cache()
    for r in run():
        print(f"{r['bench']},{r['column']},{r['layer']},{r['kind']},"
              f"{r['us_per_call']:.1f},{r['ops_per_s']:.0f}")


if __name__ == "__main__":
    main()
