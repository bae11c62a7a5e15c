"""Run a cell with a fault or a control planted under the timed path,
for several seeds in one process, and print each run's checks.

    python3 bench/control.py --workload <cell> --fault <name> \
        --seeds 1,2,3 --seconds 3

On the chip, from the root of a checkout; the benchmark's own runs never
plant anything. A fault or control that the check catches prints
``correct: false`` for the seed; the last line of standard output is one
JSON object with every seed's checks.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    from bench import faults, harness, run
    from bench.loader import load_cell
    cell = load_cell(args.workload)
    run.require_chips(cell.chips)
    run.place_compile_cache()
    results = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        try:
            out = harness.run_cell(
                cell, seed=seed, seconds=args.seconds, trace=False,
                t_start=t0,
                after_setup=lambda mgr: faults.arm(mgr, args.fault))
            res = {"correct": out["correct"], "checks": out["checks"]}
        except Exception as e:  # noqa: BLE001 — a crash is a caught fault
            res = {"correct": False, "crashed": f"{type(e).__name__}: {e}"}
        finally:
            faults.disarm()
            gc.collect()
        results[seed] = res
        print(f"seed {seed}: {json.dumps(res)}", file=sys.stderr, flush=True)
    print(json.dumps({"workload": args.workload, "fault": args.fault,
                      "seeds": results}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
