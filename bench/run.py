"""Run one cell of ``BENCHMARK.json`` once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. It prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``; ``checks``, the
numbers compared with the reference beside their limits, comes last and is
also printed as the last lines of standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits 1 and prints no result.
JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when
that is set, else ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(chips: int) -> None:
    """Exit 1 unless JAX sees at least ``chips`` TPU devices."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"bench: no accelerator: {e}")
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU — JAX reports platform {devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees "
                 f"{len(devs)}")


def place_compile_cache() -> str:
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    # every program of a cell is cached, so only a checkout's first run
    # compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return placed


def main(argv=None) -> int:
    args = parse(argv)
    from bench.loader import load_cell
    cell = load_cell(args.workload)
    require_chips(cell.chips)
    place_compile_cache()
    import jax
    from bench import harness
    from bench.peaks import peaks
    chip_peaks = peaks(jax.devices()[0].device_kind)
    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_start=T_START,
                           peaks=chip_peaks)
    print(f"correct: {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        limit = (f">= {c['at_least']}" if "at_least" in c
                 else f"<= {c['limit']}")
        print(f"check {name}: {c['value']} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
