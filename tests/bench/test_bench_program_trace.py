"""The program's own spans in a trace (``bench/program_trace.py``): idle
gaps named down to them, their totals, and the per-layer readers of the
ring pump's parts, the hazard fence and the write kernel's bytes. On a
small trace recorded on the CPU with the program's spans nested in the
benchmark's (``data/cpu_trace_program.xplane.pb``, made by
``data/record_cpu_trace_program.py``), and on the trace without them
(``data/cpu_trace.xplane.pb``), which must reduce exactly as before."""
from pathlib import Path

import jax
import pytest
from bench_tiny import cpu_ops

from bench import peaks, program_trace as pt, trace_reduce as tr
from bench.harness import Reading
from bench.loader import load_reader

DATA = Path(__file__).parent / "data"
MS = 1e-3


def _profile(name):
    return jax.profiler.ProfileData.from_file(str(DATA / name))


def test_chain_names_the_spans_covering_half_the_gap_outer_first():
    spans = [(0.0, 10.0, "vm.fence"), (1.0, 9.0, "ring.fetch"),
             (9.0, 10.0, "ring.deliver"), (0.0, 1.0, "ring.dispatch")]
    assert pt.chain("bench.submit", (1.0, 9.0), spans) == \
        "bench.submit>vm.fence>ring.fetch"
    # ring.fetch covers 4 of 10: less than half
    assert pt.chain("bench.pump", (5.0, 15.0), spans) == "bench.pump>vm.fence"
    assert pt.chain("bench.pump", (20.0, 21.0), spans) == "bench.pump"
    assert pt.chain("none", (2.0, 4.0), []) == "none"


def test_recorded_program_trace():
    """Three rounds; the second stalls 40 ms in ``py.gc`` inside
    ``ring.stage``, the third fences one step inside ``bench.submit``
    (numbers read off the trace's events)."""
    red = pt.reduce_profile(_profile("cpu_trace_program.xplane.pb"),
                            is_device_op=cpu_ops)
    base = tr.reduce_profile(_profile("cpu_trace_program.xplane.pb"),
                             is_device_op=cpu_ops)
    assert (red.window_s, red.busy_s, red.n_ops, red.op_seconds) == (
        base.window_s, base.busy_s, base.n_ops, base.op_seconds)
    assert red.window_s == pytest.approx(245.401416 * MS, abs=1e-9)
    names = [n for n, _ in red.gaps[:6]]
    assert names == ["bench.pump>ring.stage>py.gc",
                     "bench.submit>vm.fence>ring.fetch"] \
        + ["bench.pump>ring.fetch"] * 3 + ["bench.submit"]
    assert [s / MS for _, s in red.gaps[:3]] == pytest.approx(
        [71.066831, 33.901063, 25.500815], abs=1e-5)
    # the bench.* part of every name is trace_reduce's, and so is each gap
    assert [(n.split(">")[0], s) for n, s in red.gaps] == base.gaps
    counts = {k: c for k, (c, _) in red.spans.items()}
    assert counts == {"ring.admit": 3, "ring.stage": 3, "ring.upload": 3,
                      "ring.dispatch": 4, "ring.fetch": 4,
                      "ring.deliver": 3, "vm.fence": 1, "py.gc": 1}
    secs = {k: s / MS for k, (_, s) in red.spans.items()}
    assert secs["py.gc"] == pytest.approx(40.161708, abs=1e-5)
    assert secs["ring.fetch"] == pytest.approx(101.842618, abs=1e-5)
    assert secs["vm.fence"] == pytest.approx(29.497622, abs=1e-5)


def test_a_trace_without_program_spans_reduces_as_before():
    old = _profile("cpu_trace.xplane.pb")
    red = pt.reduce_profile(old, is_device_op=cpu_ops)
    base = tr.reduce_profile(old, is_device_op=cpu_ops)
    assert red.spans == {}
    assert (red.window_s, red.busy_s, red.n_ops, red.op_seconds,
            red.gaps) == (base.window_s, base.busy_s, base.n_ops,
                          base.op_seconds, base.gaps)
    assert [n for n, _ in red.gaps[:4]] == ["bench.submit"] * 3 \
        + ["bench.harvest"]


# ---------------------------------------------------------------- readers
def _trace(spans=None):
    red = tr.Reduction(window_s=2.0, busy_s=0.5)
    if spans is not None:
        red.spans = spans
    return red


def _reading(trace=None, counters=None):
    geometry = {"n_replicas": 3, "payload_elems": 4096, "page_blocks": 32,
                "batch": 64}
    return Reading(geometry, {}, counters or {}, trace,
                   peaks.peaks("TPU v5 lite"))


SPANS = {"ring.admit": (10, 0.001), "ring.stage": (10, 0.004),
         "ring.upload": (10, 0.005), "ring.dispatch": (10, 0.003),
         "ring.fetch": (10, 0.030), "ring.deliver": (10, 0.008),
         "vm.fence": (1, 0.002)}


@pytest.mark.parametrize("suffix", ["", ".bw"])
@pytest.mark.parametrize("metric,want", [
    ("pump_stage_ms", 1.0), ("pump_dispatch_ms", 0.3),
    ("pump_fetch_ms", 3.0), ("pump_deliver_ms", 0.8)])
def test_pump_part_readers(metric, want, suffix):
    read = load_reader(metric + suffix)
    assert read(_reading(_trace(SPANS))) == pytest.approx(want)
    # nothing to read: no trace, today's reduction, no program spans
    assert read(_reading()) is None
    assert read(_reading(_trace())) is None
    assert read(_reading(_trace({}))) is None


@pytest.mark.parametrize("suffix", ["", ".bw"])
def test_fence_reader(suffix):
    read = load_reader("fence_steps_per_kcall" + suffix)
    assert read(_reading(counters={"fence_steps": 12, "calls": 4000})) \
        == pytest.approx(3.0)
    assert read(_reading(counters={"fence_steps": 0, "calls": 10})) == 0.0
    assert read(_reading(counters={"calls": 10})) is None
    assert read(_reading(counters={"fence_steps": 1, "calls": 0})) is None


@pytest.mark.parametrize("suffix", ["", ".bw"])
def test_write_bytes_reader(suffix):
    read = load_reader("dbs_rw_write_bytes_per_user_byte" + suffix)
    # one step of 64 one-block writes on 3 replicas: 128 rows of 32 blocks
    # and one 64-block payload per replica, 4 bytes per lane
    c = {"write_rows": 3 * 128, "write_kernel_calls": 3, "write_blocks": 64}
    want = 4 * (3 * 128 * 32 + 3 * 64) / 64
    assert read(_reading(counters=c)) == pytest.approx(want)
    assert read(_reading(counters=dict(c, write_blocks=0))) is None
    assert read(_reading(counters={"write_blocks": 64})) is None
