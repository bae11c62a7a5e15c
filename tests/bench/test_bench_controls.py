"""Each configuration's control breaks a guarantee that the configuration
states, and the check calls the run not correct: a write acknowledged
with one of 3 replicas written (``longhorn-3r``), a read answered from the
first block of its 8-block tile (``longhorn-1r-local``). Tiny geometry on
the CPU; the same controls run at the cells' own size on the chip through
``bench/control.py``. Unplanted, the same runs are correct."""
import bench_tiny
import pytest

from bench import faults

CONTROLS = [("randwrite4k-qd64.3r", "ack_one_replica"),
            ("randrw4k-qd1.3r", "ack_one_replica"),
            ("seqwrite128k-qd16.3r", "ack_one_replica"),
            ("randread4k-qd64.1r", "tile_row0")]


@pytest.mark.parametrize("cell,control", CONTROLS)
def test_control_is_not_correct(cell, control):
    try:
        out = bench_tiny.run_tiny(cell, seed=2 ** 31 + 21,
                                  after_setup=lambda m: faults.arm(m,
                                                                   control))
    finally:
        faults.disarm()
    assert out["correct"] is False
    if control == "ack_one_replica":
        assert out["checks"]["replica0_mismatch_bytes"]["value"] == 0
        assert out["checks"]["replica1_mismatch_bytes"]["value"] > 0
        assert out["checks"]["replica2_mismatch_bytes"]["value"] > 0
    else:
        assert out["checks"]["read_mismatch_bytes"]["value"] > 0


def test_unknown_fault_is_refused():
    with pytest.raises(ValueError, match="unknown fault"):
        faults.arm(None, "no_such_fault")
