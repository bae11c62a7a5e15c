"""Run one cell several times, one process after another, and summarise
the spread of each metric: the measurement the bounds are set from.

    python3 bench/series.py --workload <cell> --seeds 1,2,3,4,5,6 \
        --seconds 10 [--trace 0] [--out results.jsonl]

Each run is ``bench/run.py`` in a process of its own (the chip belongs to
one process at a time; this one never touches JAX). Every result line is
appended to ``--out``; the summary prints, per metric, the median and the
spread ((Q3 - Q1) / median, ``statistics.quantiles``) over the runs, and
for ``setup_s`` also without the first run, which may compile.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench.stats import spread  # noqa: E402


def run_once(workload: str, seed: int, seconds: float, trace: int):
    t = time.perf_counter()
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        workload, "--seed", str(seed), "--seconds",
                        str(seconds), "--trace", str(trace)], cwd=ROOT,
                       capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    try:
        out = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    except json.JSONDecodeError:
        out = None
    return {"seed": seed, "rc": p.returncode, "wall_s":
            time.perf_counter() - t, "result": out,
            "stderr_tail": p.stderr[-2000:] if out is None else ""}


def summarise(runs) -> dict:
    ok = [r["result"] for r in runs if r["result"] is not None]
    names = sorted({k for o in ok for k in o["metrics"]})
    summary = {"runs": len(runs), "correct": sum(bool(o["correct"])
                                                 for o in ok)}
    for name in names:
        vals = [o["metrics"][name]["value"] for o in ok
                if name in o["metrics"]]
        s = {"median": statistics.median(vals), "values": vals}
        if len(vals) >= 2:
            s["spread"] = spread(vals)
        if name == "setup_s" and len(vals) >= 3:
            s["spread_after_first"] = spread(vals[1:])
            s["median_after_first"] = statistics.median(vals[1:])
        summary[name] = s
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    runs = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = run_once(args.workload, seed, args.seconds, args.trace)
        runs.append(r)
        line = json.dumps({"workload": args.workload, **r})
        print(line[:4000], flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "trace": args.trace, "summary": summarise(runs)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
