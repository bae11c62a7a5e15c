"""The trace reduction: busy union, idle share and the naming of idle gaps,
on synthetic intervals and on a small trace recorded on the CPU
(``data/cpu_trace.xplane.pb``, made by ``data/record_cpu_trace.py``)."""
import re
from pathlib import Path

import jax
import pytest
from bench_tiny import cpu_ops

from bench import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"
MS = 1e-3


def test_union_merges_overlaps_and_sorts():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert tr.union([]) == []


def test_idle_gaps_cover_the_window_outside_busy():
    busy = [(1.0, 2.0), (4.0, 5.0)]
    assert tr.idle_gaps(busy, 0.0, 6.0) == [(0.0, 1.0), (2.0, 4.0),
                                            (5.0, 6.0)]
    assert tr.idle_gaps([(0.0, 6.0)], 0.0, 6.0) == []


def test_gap_takes_the_name_of_the_span_that_overlaps_it_most():
    spans = [(0.0, 1.0, "bench.submit"), (1.0, 4.0, "bench.pump"),
             (4.0, 5.0, "bench.harvest")]
    assert tr.name_gap((0.5, 2.5), spans) == "bench.pump"
    assert tr.name_gap((0.0, 0.9), spans) == "bench.submit"
    assert tr.name_gap((6.0, 7.0), spans) == "none"


class _Ev:
    def __init__(self, name, start_ms, dur_ms):
        self.name, self.start_ns, self.duration_ns = (name, start_ms * 1e6,
                                                      dur_ms * 1e6)
        self.stats = []


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_tpu_planes_are_averaged_and_ops_summed():
    """Two chips' ``XLA Ops`` lines: busy is averaged over the chips, op
    seconds summed; ops outside the window are clipped away."""
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("bench.window", 0, 10), _Ev("bench.pump", 0, 10)])])
    chip0 = _Plane("/device:TPU:0", [_Line("XLA Ops", [
        _Ev("_write_kernel", 1, 2), _Ev("fusion.1", 2, 2),
        _Ev("fusion.1", 12, 5)])])
    chip1 = _Plane("/device:TPU:1", [_Line("XLA Ops", [
        _Ev("_write_kernel", 0, 1)]), _Line("XLA Modules", [
            _Ev("jit_stepped", 0, 10)])])
    red = tr.reduce_profile(_Profile([host, chip0, chip1]))
    assert red.window_s == pytest.approx(10 * MS)
    assert red.busy_s == pytest.approx((3 + 1) / 2 * MS)
    assert red.op_seconds == pytest.approx({"_write_kernel": 3 * MS,
                                            "fusion.1": 2 * MS})
    assert red.seconds_of(re.compile("^_write")) == pytest.approx(3 * MS)
    assert red.seconds_of(re.compile(r"fusion\.")) == pytest.approx(2 * MS)
    assert red.seconds_of(re.compile("nothing")) == 0.0
    assert red.gaps[0] == ("bench.pump", pytest.approx(6 * MS))
    assert red.n_ops == 3


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_profile(_Profile([]))


def test_recorded_cpu_trace():
    """Three rounds of submit (20 ms asleep), pump (a matrix product) and
    harvest (5 ms asleep) in a 90.773002 ms window; the operations add up
    to 11.30458 ms (read off the trace's events)."""
    red = tr.reduce_profile(jax.profiler.ProfileData.from_file(str(TRACE)),
                            is_device_op=cpu_ops)
    assert red.window_s == pytest.approx(90.773002 * MS, abs=1e-9)
    assert red.busy_s == pytest.approx(11.30458 * MS, abs=1e-8)
    assert red.idle_share == pytest.approx(1 - 11.30458 / 90.773002,
                                           abs=1e-6)
    assert red.n_ops == 12
    names = [n for n, _ in red.gaps[:4]]
    assert names == ["bench.submit"] * 3 + ["bench.harvest"]
    assert [s / MS for _, s in red.gaps[:4]] == pytest.approx(
        [28.262984, 25.57463, 20.36371, 5.257135], abs=1e-5)
    assert max(red.op_seconds, key=red.op_seconds.get) == "dot_general.1"
    assert red.top_ops(2)[0][0] == "dot_general.1"
