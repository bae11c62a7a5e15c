"""Table II analogue: sequential whole-extent transfers (bandwidth ladder)."""
from __future__ import annotations

import time
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from benchmarks.ladder import COLUMNS, ROWS, make_engine
from repro.core import Request
from repro.utils.compile_cache import place_compile_cache

# one "1 MB extent" analogue: page_blocks x block payload
PAGE_BLOCKS = 32
BLOCK_ELEMS = 256          # 1 KiB fp32 per block -> 32 KiB per extent


def run(n_extents_io: int = 64, warmup: bool = True) -> List[dict]:
    """``warmup`` runs the whole workload once before the timed pass so every
    column is measured steady-state (jit compiles happen off the clock),
    mirroring benchmarks/ladder.py."""
    payload = jnp.ones((BLOCK_ELEMS,), jnp.float32)
    bytes_per_req = BLOCK_ELEMS * 4 * PAGE_BLOCKS
    rows = []
    for kind in ("read", "write"):
        for col in COLUMNS:
            for row in ROWS:
                eng = make_engine(col, row, payload_shape=(BLOCK_ELEMS,),
                                  page_blocks=PAGE_BLOCKS,
                                  max_pages=n_extents_io + 2,
                                  n_extents=4 * n_extents_io + 16)
                vol = eng.create_volume()
                # sequential: all blocks of extent e, then extent e+1, ...
                reqs = []
                rid = 0
                for e in range(n_extents_io):
                    for b in range(PAGE_BLOCKS):
                        reqs.append(Request(req_id=rid, kind=kind, volume=vol,
                                            page=e, block=b, payload=payload))
                        rid += 1
                if kind == "read" and row == "full_engine":
                    for r in reqs:    # populate before reading
                        eng.submit(Request(req_id=r.req_id, kind="write",
                                           volume=vol, page=r.page,
                                           block=r.block, payload=payload))
                    eng.drain()
                if warmup:            # compile pass, off the clock
                    for r in reqs:
                        eng.submit(r)
                    eng.drain()
                    eng.completed = 0
                for r in reqs:
                    eng.submit(r)
                t0 = time.perf_counter()
                done = eng.drain()
                dt = time.perf_counter() - t0
                mbps = done / PAGE_BLOCKS * bytes_per_req / dt / 1e6
                rows.append({"bench": "table2_bandwidth", "kind": kind,
                             "layer": row, "column": col, "mb_per_s": mbps,
                             "us_per_call": dt / max(done, 1) * 1e6})
    return rows


def main():
    place_compile_cache()
    for r in run():
        print(f"{r['bench']},{r['column']},{r['layer']},{r['kind']},"
              f"{r['us_per_call']:.1f},{r['mb_per_s']:.1f}")


if __name__ == "__main__":
    main()
