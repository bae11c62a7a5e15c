"""A run with the timed path broken underneath comes out not correct: the
faults a cell can have, each planted under the ring step after set-up, at
a tiny geometry on the CPU (the chip's check is skipped, the rest of the
run is the benchmark's own). The exchange between chips does not exist in
a one-chip cell; half of a batch cannot be left out where a batch holds
one lane (qd 1)."""
import bench_tiny
import pytest

from bench import faults

CASES = [(cell, f) for cell in bench_tiny.CELLS for f in faults.FAULTS
         if not (f == "half_batch" and cell == "randrw4k-qd1.3r")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_caught(cell, fault):
    try:
        out = bench_tiny.run_tiny(cell, seed=2 ** 31 + 3,
                                  after_setup=lambda m: faults.arm(m, fault))
    finally:
        faults.disarm()
    assert out["correct"] is False
    caught = [k for k, c in out["checks"].items()
              if "limit" in c and c["value"] > c["limit"]]
    assert caught, out["checks"]
