"""The program's own observability: the host spans of the ring pump, the
hazard fence and Python's collector (recorded while a profiler trace
runs), the names of the ring step's programs, and the counters
``VolumeManager.stats()`` reports — the hazard fence's flushes and steps
the ``dbs_rw_write`` kernel's extent-row traffic, and the pump's packed
SQE uploads."""
import dataclasses
import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dbs
from repro.core.blockdev import VolumeManager
from repro.kernels.dbs import dbs_rw_write_rows

BB = 8          # block bytes (payload_elems)
PB = 8          # page blocks: one read tile per page


def _mgr(kernel="pallas", batch=8, n_replicas=2, **kw):
    return VolumeManager(backend="ring", n_replicas=n_replicas,
                         payload_elems=BB, page_blocks=PB, n_extents=32,
                         max_pages=8, batch=batch, kernel=kernel, **kw)


def _block(i: int) -> bytes:
    return bytes([i % 251 + 1]) * BB


def _counts(mgr):
    s = mgr.stats()
    return s["write_rows"], s["write_kernel_calls"]


# ------------------------------------------------------------ the fence
def test_hazard_fence_counts_its_flushes_and_steps():
    mgr = _mgr(batch=4)
    vol = mgr.create()
    for b in range(6):                       # 6 lanes in flight: 2 batches
        vol.pwrite(b * BB, _block(b))
    s = mgr.stats()
    assert (s["fence_flushes"], s["fence_steps"]) == (0, 0)
    vol.pwrite(0, _block(10))                # races block 0: one flush
    s = mgr.stats()
    assert (s["fence_flushes"], s["fence_steps"]) == (1, 2)
    vol.pwrite(BB, _block(11))               # block 1 is idle: no fence
    vol.pread(2 * BB, BB)
    assert mgr.stats()["fence_flushes"] == 1
    vol.pwrite(2 * BB, _block(12))           # races an in-flight read
    s = mgr.stats()
    assert (s["fence_flushes"], s["fence_steps"]) == (2, 3)
    mgr.flush()                              # a caller's flush is no fence
    assert vol.read(0, 3 * BB) == _block(10) + _block(11) + _block(12)
    s = mgr.stats()
    assert (s["fence_flushes"], s["fence_steps"]) == (2, 3)


# ------------------------------------------------- the write kernel's rows
def _ops(dst, cow, ok):
    i32 = lambda v: jnp.asarray(v, jnp.int32)            # noqa: E731
    return dbs.WriteOps(dst=i32(dst), cow_src=i32(cow),
                        ok=jnp.asarray(ok, bool))


@pytest.mark.parametrize("dst,cow,ok,rows", [
    # in place, duplicate pages: leaders 0 and 2, members on the dump row
    # between them -> src and dst each run 5 | d | 7 | d
    ([5, 5, 7, 7], [-1] * 4, [1] * 4, 4 + 4),
    # the same pages interleaved: adjacent leaders, trailing members
    ([5, 7, 5, 7], [-1] * 4, [1] * 4, 3 + 3),
    # copy-on-write: the sources are fetched, the new rows written back;
    # a member and a dead lane share one dump run
    ([5, 5, -1, 9], [2, -1, -1, 3], [1, 1, 0, 1], 3 + 3),
    # nothing live: the dump row once each way
    ([-1] * 4, [-1] * 4, [0] * 4, 1 + 1),
])
def test_write_rows_match_a_hand_count(dst, cow, ok, rows):
    pool = jnp.zeros((16, PB, BB), jnp.float32)          # dump row 15
    got = dbs_rw_write_rows(pool, _ops(dst, cow, ok),
                            jnp.zeros((4,), jnp.int32))
    assert int(got) == rows


def test_ring_step_accumulates_the_write_kernels_rows_per_replica():
    r = 2
    mgr = _mgr(n_replicas=r)
    vol = mgr.create()
    # one batch: page 0 blocks 0, 1; page 1 block 0; page 0 block 2 ->
    # leaders at lanes 0 and 2 among 8 lanes: runs p0 | d | p1 | d...
    for page, blk in ((0, 0), (0, 1), (1, 0), (0, 2)):
        vol.pwrite((page * PB + blk) * BB, _block(blk))
    mgr.pump()
    assert _counts(mgr) == (r * (4 + 4), r)
    # the snapshot's program runs the write kernel over dump lanes only
    mgr.snapshot(vol)
    assert _counts(mgr) == (r * (8 + 2), r * 2)
    # copy-on-write of both pages: adjacent leaders, sources then new rows
    vol.pwrite(3 * BB, _block(3))
    vol.pwrite((PB + 3) * BB, _block(4))
    mgr.pump()
    assert _counts(mgr) == (r * (10 + 3 + 3), r * 3)
    assert vol.read(0, 4 * BB) == (_block(0) + _block(1) + _block(2)
                                   + _block(3))


def test_other_kernels_move_no_dbs_rw_write_rows():
    mgr = _mgr(kernel="xla")
    vol = mgr.create()
    vol.pwrite(0, _block(1))
    mgr.flush()
    assert _counts(mgr) == (0, 0)


def test_write_counters_take_deltas_modulo_2_32():
    mgr = _mgr(n_replicas=1)
    vol = mgr.create()
    impl = mgr.engine.impl
    assert _counts(mgr) == (0, 0)
    near = jnp.asarray([[2 ** 31 - 2, 2 ** 31 - 1]], jnp.int32)
    impl.cq = dataclasses.replace(impl.cq, work=near)
    assert _counts(mgr) == (2 ** 31 - 2, 2 ** 31 - 1)
    vol.pwrite(0, _block(1))                 # 4 rows, 1 call: both wrap
    mgr.flush()
    assert np.asarray(impl.cq.work)[0].tolist() == [-(2 ** 31) + 2,
                                                    -(2 ** 31)]
    assert _counts(mgr) == (2 ** 31 + 2, 2 ** 31)


UPLOADS = {"upload_transfers", "upload_bytes", "upload_payload_skips"}
FETCHES = {"fetch_transfers", "fetch_bytes"}


@pytest.mark.parametrize("backend,keys", [
    ("ring", {"fence_flushes", "fence_steps", "write_rows",
              "write_kernel_calls"} | UPLOADS | FETCHES),
    # the fused step counts no steps and has no CQ
    ("fused", {"fence_flushes"}),
])
def test_stats_carry_the_counters(backend, keys):
    mgr = VolumeManager(backend=backend, n_replicas=2, payload_elems=BB,
                        page_blocks=PB, n_extents=32, max_pages=8, batch=8)
    vol = mgr.create()
    vol.pwrite(0, _block(1))
    vol.pwrite(0, _block(2))
    mgr.flush()
    s = mgr.stats()
    assert set(s) & ({"fence_flushes", "fence_steps", "write_rows",
                      "write_kernel_calls"} | UPLOADS | FETCHES) == keys
    assert s["fence_flushes"] == 1
    assert s.get("fence_steps", 1) == 1
    if UPLOADS <= keys:                      # one upload per step, all writes
        assert s["upload_transfers"] == mgr.engine.impl.dispatches
        assert s["upload_payload_skips"] == 0
        assert s["upload_bytes"] > 0
    if FETCHES <= keys:                      # one fetch per completed step
        assert s["fetch_transfers"] == mgr.engine.impl.dispatches
        assert s["fetch_bytes"] > 0


# ------------------------------------------------------------ the spans
def _host_spans(trace_dir):
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split(".")[0] in ("ring", "vm", "py", "caller"):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def _traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("caller"):
            fn()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(str(tmp_path))


def test_a_pump_records_each_ring_span_once_in_order(tmp_path):
    mgr = _mgr(kernel="xla")
    vol = mgr.create()
    vol.pwrite(0, _block(1))
    mgr.flush()                              # compiled before the trace
    vol.pwrite(BB, _block(2))
    spans = _traced(tmp_path, mgr.pump)
    (caller,) = [s for s in spans if s[0] == "caller"]
    ring = [s for s in spans if s[0].startswith("ring.")]
    assert [s[0] for s in ring] == ["ring.admit", "ring.stage",
                                    "ring.upload", "ring.dispatch",
                                    "ring.fetch", "ring.deliver"]
    for _, t0, t1, _ in ring:
        assert caller[1] <= t0 <= t1 <= caller[2]
    step = mgr.engine.impl.dispatches
    assert ring[3][3] == {"step": step} and ring[4][3] == {"step": step}


def test_a_fence_and_a_collection_record_their_spans(tmp_path):
    mgr = _mgr(kernel="xla")
    vol = mgr.create()
    vol.pwrite(0, _block(1))
    mgr.flush()

    def work():
        vol.pwrite(0, _block(2))
        vol.pwrite(0, _block(3))             # the fence drains one step
        gc.collect(1)

    spans = _traced(tmp_path, work)
    (fence,) = [s for s in spans if s[0] == "vm.fence"]
    inside = [s[0] for s in spans
              if fence[1] <= s[1] and s[2] <= fence[2]
              and s[0] != "vm.fence"]
    assert inside.count("ring.dispatch") == 1
    assert inside.count("ring.fetch") == 1
    gcs = [s for s in spans if s[0] == "py.gc"]
    assert {"generation": 1} in [s[3] for s in gcs]
    assert vol.read(0, BB) == _block(3)
