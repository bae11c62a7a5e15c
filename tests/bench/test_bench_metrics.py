"""The benchmark's arithmetic: percentiles with their sample counts, rates
over the whole window, the spread bounds are set from, the user-bytes work
of the kernel rooflines, the chip's peaks, and the per-layer readers."""
import bench_tiny  # noqa: F401  (puts the repository on the path)
import pytest

from bench import kernels, peaks, stats, trace_reduce
from bench.harness import Reading
from bench.loader import load_reader


def test_nearest_rank_p99_and_the_samples_beyond_it():
    values = list(range(1, 1001))            # 1..1000
    assert stats.percentile(values, 99) == 990.0
    assert stats.beyond(1000, 99) == 10
    assert stats.percentile([3.0], 99) == 3.0
    assert stats.beyond(1, 99) == 0
    assert stats.percentile([5, 1, 4, 2, 3], 50) == 3.0
    assert stats.percentile(list(range(1, 101)), 99) == 99.0
    with pytest.raises(ValueError):
        stats.percentile([], 99)


def test_rates_are_over_the_whole_window():
    assert stats.rate(5000, 10.0) == 500.0
    assert stats.rate(0, 2.0) == 0.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_spread_is_the_interquartile_range_over_the_median():
    # statistics.quantiles (exclusive) of 1..6 is 1.75, 3.5, 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert stats.spread([10.0] * 6) == 0.0


def test_user_bytes_of_the_rooflines():
    g3 = {"n_replicas": 3, "payload_elems": 4096}
    g1 = {"n_replicas": 1, "payload_elems": 4096}
    assert kernels.write_user_bytes(64, g3) == 64 * 4 * 4096
    assert kernels.write_user_bytes(1, g1) == 2 * 4096
    assert kernels.read_user_bytes(64, g1) == 64 * 2 * 4096


def test_v5e_peaks_and_unknown_devices(monkeypatch):
    monkeypatch.setenv("REPRO_HBM_BYTES_PER_S", "1")     # not honoured
    for kind in ("TPU v5 lite", "TPU v5e", "tpu v5 lite"):
        assert peaks.peaks(kind)["hbm_bytes_per_s"] == 819e9
        assert peaks.peaks(kind)["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")


def _reading(spans=None, counters=None, trace=None, replicas=3):
    geometry = {"n_replicas": replicas, "payload_elems": 4096}
    return Reading(geometry, spans or {}, counters or {}, trace,
                   peaks.peaks("TPU v5 lite"))


def _trace(ops, busy=0.5, window=2.0):
    return trace_reduce.Reduction(window_s=window, busy_s=busy,
                                  op_seconds=dict(ops), n_ops=len(ops))


def test_host_span_readers():
    r = _reading(spans={"submit": (4, 0.002), "pump": (2, 0.010)})
    assert load_reader("api_us_per_call")(r) == pytest.approx(500.0)
    assert load_reader("pump_ms")(r) == pytest.approx(5.0)
    assert load_reader("api_us_per_call.bw")(r) == pytest.approx(500.0)
    assert load_reader("pump_ms")(_reading()) is None


def test_counter_and_trace_readers():
    r = _reading(counters={"completed": 640, "dispatches": 10},
                 trace=_trace({"x": 0.5}))
    assert load_reader("blocks_per_pump")(r) == 64.0
    assert load_reader("device_idle_pct")(r) == pytest.approx(75.0)
    assert load_reader("device_idle_pct.bw")(r) == pytest.approx(75.0)
    assert load_reader("blocks_per_pump")(_reading(
        counters={"completed": 0, "dispatches": 0})) is None
    assert load_reader("device_idle_pct")(_reading(
        trace=_trace({}, busy=0.0))) is None


# device operations as a v5e trace names them (longhorn-3r)
WRITE_OP = (
    "%stepped.8 = f32[4097,32,4096]{2,1,0:T(8,128)} custom-call(s32[64]{0:"
    "T(128)S(1)} %get-tuple-element.331, s32[64]{0:T(128)S(1)} %get-tuple-"
    "element.283, s32[2048]{0:T(1024)S(1)} %reshape.9, f32[4097,32,4096]{2,"
    "1,0:T(8,128)} %bitcast.70, f32[64,4096]{1,0:T(8,128)} %bitcast.385), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={s32'
    "[64]{0}, s32[64]{0}, s32[2048]{0}, f32[4097,32,4096]{2,1,0}, f32[64,40"
    "96]{1,0}}, output_to_operand_aliasing={{}: (3, {})}, frontend_attribut"
    "es={kernel_metadata={}}")
READ_OP = (
    "%stepped.9 = f32[64,1,4096]{2,1,0:T(1,128)S(1)} custom-call(s32[64]{0:"
    "T(128)S(1)} %fusion.22, s32[64]{0:T(128)S(1)} %clamp_bitcast_fusion, f"
    "32[4097,32,4096]{2,1,0:T(8,128)} %stepped.6), custom_call_target=\"tpu"
    '_custom_call", operand_layout_constraints={s32[64]{0}, s32[64]{0}, f32'
    "[4097,32,4096]{2,1,0}}, frontend_attributes={kernel_metadata={}}")
FUSION_OP = ("%fusion.53 = f32[256,4096]{1,0:T(8,128)} fusion(f32[256,4096]"
             "{1,0:T(8,128)} %bitcast.381), kind=kCustom, calls=%fc.334")


def test_kernel_events_are_told_apart():
    assert kernels.kernel_of(WRITE_OP) == "dbs_rw_write"
    assert kernels.kernel_of(READ_OP) == "dbs_rw_read"
    assert kernels.kernel_of(FUSION_OP) is None
    assert kernels.label(WRITE_OP) == ("dbs_rw_write %stepped.8 custom-call "
                                       "f32[4097,32,4096]")
    assert kernels.label(FUSION_OP) == "%fusion.53 fusion f32[256,4096]"


@pytest.mark.parametrize("metric,counter,op,per_block", [
    ("dbs_rw_write_roofline", "write_blocks", WRITE_OP, 4 * 4096),
    ("dbs_rw_write_roofline.bw", "write_blocks", WRITE_OP, 4 * 4096),
    ("dbs_rw_read_roofline", "read_blocks", READ_OP, 2 * 4096),
])
def test_roofline_readers(metric, counter, op, per_block):
    read = load_reader(metric)
    ops = {op: 1e-3, FUSION_OP: 5.0}
    r = _reading(counters={counter: 1000}, trace=_trace(ops))
    want = 100 * 1000 * per_block / (1e-3 * 819e9)
    assert read(r) == pytest.approx(want)
    assert 0 < read(r) < 100
    # no kernel event in the trace, or no block of that kind: nothing read,
    # never a share of 0
    assert read(_reading(counters={counter: 1000},
                         trace=_trace({FUSION_OP: 1.0}))) is None
    assert read(_reading(counters={counter: 0}, trace=_trace(ops))) is None
