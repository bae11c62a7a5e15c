"""Shared helpers of the benchmark's tests: the repository root on the
import path, and cells cut to a tiny geometry that the CPU runs in a few
seconds (64-byte blocks, 8-block extents, 16 pages per volume). Test files
import this module before ``bench``."""
import dataclasses
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = dict(payload_elems=64, page_blocks=8, n_extents=96, max_pages=16,
            batch=16)
CELLS = ("randwrite4k-qd64.3r", "randread4k-qd64.1r", "randrw4k-qd1.3r",
         "seqwrite128k-qd16.3r")


def tiny_cell(name: str, bench_dir=None):
    """Cell ``name`` at the tiny geometry: blocks cut from 4096 to 64
    bytes with calls cut alike, and at most 16 calls in flight."""
    from bench.loader import BENCH_DIR, load_benchmark, load_cell
    bench_dir = BENCH_DIR if bench_dir is None else bench_dir
    cell = load_cell(name, load_benchmark(bench_dir.parent), bench_dir)
    g = cell.config["geometry"]
    scale = TINY["payload_elems"] / g["payload_elems"]
    mix = dataclasses.replace(cell.mix,
                              call_bytes=int(cell.mix.call_bytes * scale),
                              qd=min(cell.mix.qd, 16))
    cfg = dict(cell.config, geometry=dict(g, **TINY))
    return dataclasses.replace(cell, mix=mix, config=cfg)


def cpu_ops(plane, line, event):
    """Device operations of a CPU trace: the events XLA tags with its op."""
    return any(k == "hlo_op" for k, _ in event.stats)


def run_tiny(name, *, seed=7, seconds=0.3, trace=False, cell=None, **kw):
    """Run a tiny cell once on the CPU; returns the result line."""
    from bench import harness
    cell = tiny_cell(name) if cell is None else cell
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(),
                            peaks={"hbm_bytes_per_s": 819e9},
                            trace_ops=cpu_ops, **kw)
