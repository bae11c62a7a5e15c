"""The SQ/CQ ring protocol (core/ring.py).

Contracts:

1. **data path** — ``comm="ring"`` reaches byte-identical volume contents
   vs ``comm="fused"`` on a mixed CoW workload, and delivers read results
   with status/latency from the CQ.
2. **in-band control** — a random interleaving of WRITE/SNAPSHOT/CLONE/
   UNMAP submitted through the ring is bit-identical to the host-side
   ``dbs.snapshot/clone/unmap`` sequential reference (full DBS metadata,
   revision counter excepted — its granularity is per-program by design)
   and content-identical to the ``ChainedStore`` reference walk.
3. **in-band FAIL/REBUILD** — mid-drain on the sharded pool, exact: data
   intact, the rebuilt replica serves missed writes, protocol violations
   surface as CQE statuses without mutating the health mask.
4. **dispatch accounting** — one traced program per (batch geometry, class
   signature), no extra host dispatch per control op, and exactly one
   ``device_get`` per pump even with control lanes aboard.
5. satellites: unified ``Request.result``/``status`` across every comm
   mode; ``ChainedReplicas`` volume-id agreement and null-storage rr fixes.
6. **packed upload** — a pump sends its SQE as one host-to-device transfer
   (header only for the read tier) and the step's results match the
   leaf-by-leaf SQE bit for bit, float payload bits included.
7. **packed fetch** — a pump fetches its CQE view as one device-to-host
   transfer of one int32 array, which unpacks to the step's five-leaf view
   bit for bit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Engine, EngineConfig, Request, UpstreamEngine, dbs
from repro.core.ring import (ST_ERR, ST_HEALTHY, ST_LAST, ST_OK,
                             RingEngine)

PAY = (8,)


def _cfg(**kw):
    base = dict(comm="ring", storage="dbs", payload_shape=PAY, n_extents=256,
                max_pages=64, batch=16, n_replicas=2, n_shards=1,
                max_volumes=16)
    base.update(kw)
    return EngineConfig(**base)


def _pay(v: float) -> jnp.ndarray:
    return jnp.full(PAY, float(v))


# ---------------------------------------------------------------------------
# host-side sequential reference: one DBSState+pool driven op by op
# ---------------------------------------------------------------------------
class HostRef:
    def __init__(self, n_extents=256, max_volumes=16, max_pages=64,
                 page_blocks=32):
        self.st = dbs.make_state(n_extents, max_volumes, max_pages)
        self.pool = jnp.zeros((n_extents + 1, page_blocks) + PAY, jnp.float32)

    def write(self, vol, page, block, payload):
        self.st, ops = dbs.write_pages(
            self.st, jnp.int32(vol), jnp.asarray([page], jnp.int32),
            jnp.asarray([1 << block], jnp.uint32), jnp.asarray([True]))
        self.pool = dbs.apply_write_ops(self.pool, ops, payload[None],
                                        jnp.asarray([block], jnp.int32))

    def snapshot(self, vol):
        self.st, sid = dbs.snapshot(self.st, jnp.int32(vol))
        return int(sid)

    def clone(self, vol):
        self.st, vid = dbs.clone(self.st, jnp.int32(vol))
        return int(vid)

    def unmap(self, vol, page):
        self.st = dbs.unmap(self.st, jnp.int32(vol),
                            jnp.asarray([page], jnp.int32))

    def delete(self, vol):
        self.st = dbs.delete_volume(self.st, jnp.int32(vol))

    def read(self, vol, page, block):
        ext = int(self.st.table[vol, page])
        if ext < 0:
            return np.zeros(PAY, np.float32)
        return np.asarray(self.pool[ext, block])


def _ring_state(eng, replica):
    """Shard 0's replica state/pool of a ring engine (S=1 tests)."""
    st = jax.tree.map(lambda x: x[0], eng.pool.backend.states[replica])
    return st, eng.pool.backend.pools[replica][0]


def _assert_states_equal(a: dbs.DBSState, b: dbs.DBSState, msg=""):
    """Bit-exact DBS metadata equality, revision excepted (the ring bumps it
    once per batched write_pages, the sequential reference once per op)."""
    for f in dataclasses.fields(dbs.DBSState):
        if f.name == "revision":
            continue
        for la, lb in zip(jax.tree.leaves(getattr(a, f.name)),
                          jax.tree.leaves(getattr(b, f.name))):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                          err_msg=f"{msg} field {f.name}")


def _masked_read(st: dbs.DBSState, pool, vol, page, block):
    ext = int(st.table[vol, page])
    if ext < 0:
        return np.zeros(PAY, np.float32)
    return np.asarray(pool[ext, block])


# ---------------------------------------------------------------------------
# 1. data path: ring == fused, results delivered from the CQ
# ---------------------------------------------------------------------------
def test_ring_matches_fused_volume_contents():
    engs = [Engine(_cfg(comm="fused")), Engine(_cfg())]
    vols = [e.create_volume() for e in engs]
    for i in range(60):
        for e, v in zip(engs, vols):
            e.submit(Request(req_id=i, kind="write", volume=v, page=i % 48,
                             block=i % 8, payload=_pay(i + 1)))
    for e in engs:
        assert e.drain() == 60
    for e, v in zip(engs, vols):
        e.snapshot(v)
    for i in range(30):                      # CoW overwrites + reads mixed in
        for e, v in zip(engs, vols):
            e.submit(Request(req_id=i, kind="write", volume=v, page=i % 24,
                             block=(i * 3) % 8, payload=_pay(1000 + i)))
            e.submit(Request(req_id=i + 500, kind="read", volume=v,
                             page=i % 24, block=0))
    assert [e.drain() for e in engs] == [60, 60]
    pages = jnp.arange(48, dtype=jnp.int32)
    for blk in range(8):
        offs = jnp.full((48,), blk, jnp.int32)
        a = engs[0].backend.read(vols[0], pages, offs)
        b = engs[1].pool.read_volume(vols[1], pages, offs)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   err_msg=f"block {blk}")
    assert engs[1].pool.backend.consistent()


def test_ring_read_results_status_latency():
    eng = Engine(_cfg())
    vol = eng.create_volume()
    w = Request(req_id=0, kind="write", volume=vol, page=3, block=2,
                payload=_pay(7))
    eng.submit(w)
    eng.drain()
    r = Request(req_id=1, kind="read", volume=vol, page=3, block=2)
    eng.submit(r)
    eng.drain()
    np.testing.assert_allclose(np.asarray(r.result), np.full(PAY, 7.0))
    assert w.status == ST_OK and r.status == ST_OK
    assert w.latency == 1 and r.latency == 1


def test_ring_latency_counts_queueing_ticks():
    """Under slot pressure the drain caps at the slot count, so later lanes
    ride a later pump — the CQE latency (pump ticks) records the wait."""
    eng = Engine(_cfg(n_slots=4, batch=8))
    vol = eng.create_volume()
    reqs = [Request(req_id=i, kind="write", volume=vol, page=i, block=0,
                    payload=_pay(i)) for i in range(8)]
    for r in reqs:
        eng.submit(r)
    assert eng.drain() == 8
    lats = sorted(r.latency for r in reqs)
    assert lats[0] == 1 and lats[-1] > 1    # 4 slots: half requeued at least


def test_requeue_preserves_queue_order():
    """Slot pressure must never reorder a queue: the drain caps at the slot
    count (a transact pump starts with every slot free, so a capped batch
    cannot starve), and any requeue path restores back-to-front."""
    eng = Engine(_cfg(n_queues=1, n_slots=4, batch=8))
    reqs = [Request(req_id=i, kind="noop") for i in range(8)]
    for r in reqs:
        eng.submit(r)
    assert eng.pool.pump() == 4             # capped at the 4 slots
    q = eng.pool.frontend.queues[0][0]
    assert [r.req_id for r in q] == [4, 5, 6, 7]
    assert eng.drain() == 4
    # requeue_all restores submission order even for an arbitrary batch
    eng.pool.frontend.requeue_all(reqs[:3])
    assert [r.req_id for r in q] == [0, 1, 2]


def test_overwrite_order_survives_slot_pressure():
    """Writes past the slot count land on later pumps — never behind a
    LATER submission (the pipelined drain launches N+1 before completing N,
    so a starved suffix of N re-entering the queues would execute after
    N+1, out of submission order; the drain cap makes that impossible)."""
    eng = Engine(_cfg(n_queues=1, n_slots=4, batch=8))
    vol = eng.create_volume()
    for i in range(8):
        eng.submit(Request(req_id=i, kind="write", volume=vol, page=i,
                           block=0, payload=_pay(100 + i)))
    for i in range(4):                      # overwrite pages 4..7
        eng.submit(Request(req_id=8 + i, kind="write", volume=vol,
                           page=4 + i, block=0, payload=_pay(200 + i)))
    assert eng.drain() == 12
    got = np.asarray(eng.pool.read_volume(
        vol, jnp.arange(8, dtype=jnp.int32), jnp.zeros(8, jnp.int32)))
    np.testing.assert_allclose(
        got[:, 0], [100, 101, 102, 103, 200, 201, 202, 203])


def test_ring_noop_barrier_completes():
    eng = Engine(_cfg())
    r = Request(req_id=0, kind="noop")
    eng.submit(r)
    assert eng.drain() == 1
    assert r.status == ST_OK


def test_ring_null_rows_complete():
    for kw in (dict(null_backend=True), dict(null_storage=True)):
        eng = Engine(_cfg(**kw))
        vol = eng.create_volume()
        for i in range(40):
            eng.submit(Request(req_id=i, kind="write" if i % 2 else "read",
                               volume=vol, page=i % 64, block=0,
                               payload=jnp.ones(PAY)))
        assert eng.drain() == 40, kw


# ---------------------------------------------------------------------------
# 2. in-band control == host-side sequence == chained-store walk
# ---------------------------------------------------------------------------
def _interleaving(seed, n_ops, n_base=3, pages=48):
    """Random op stream. Writes draw pages from a per-volume permutation so
    no (vol, page) pair repeats within an admission batch window (the
    documented write_pages batch precondition, same as the fused path)."""
    rng = np.random.default_rng(seed)
    perm = {v: rng.permutation(pages) for v in range(n_base)}
    counters = {v: 0 for v in range(n_base)}
    ops = []
    for _ in range(n_ops):
        r = rng.random()
        vol = int(rng.integers(0, n_base))
        if r < 0.72:
            page = int(perm[vol][counters[vol] % pages])
            counters[vol] += 1
            ops.append(("write", vol, page, int(rng.integers(0, 8))))
        elif r < 0.84:
            ops.append(("snapshot", vol))
        elif r < 0.92:
            ops.append(("clone", vol))
        else:
            ops.append(("unmap", vol, int(perm[vol][rng.integers(0, pages)])))
    return ops


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_inband_control_matches_host_sequence_and_chained_walk(seed):
    from repro.core.engine import ChainedStore
    n_base, pages = 3, 48
    ops = _interleaving(seed, 110, n_base, pages)

    # ring engine: everything (data AND control) through the one SQE path;
    # n_queues=1 keeps a single totally-ordered submission stream
    eng = Engine(_cfg(n_queues=1, n_slots=256, max_pages=pages,
                      page_blocks=32))
    ring_vols = [eng.create_volume() for _ in range(n_base)]
    assert ring_vols == list(range(n_base))
    ctl_reqs = []
    for i, op in enumerate(ops):
        if op[0] == "write":
            _, vol, page, block = op
            eng.submit(Request(req_id=i, kind="write", volume=vol, page=page,
                               block=block, payload=_pay(i + 1)))
        else:
            kind, vol = op[0], op[1]
            r = Request(req_id=i, kind=kind, volume=vol,
                        page=op[2] if kind == "unmap" else 0)
            ctl_reqs.append((op, r))
            eng.submit(r)
    assert eng.drain() == len(ops)

    # host-side sequential reference + chained-store reference walk
    ref = HostRef(max_pages=pages)
    chained = ChainedStore(PAY)
    ref_ids, clone_map = [], {}          # ring vol -> chained vol
    for v in range(n_base):
        ref.st, _ = dbs.create_volume(ref.st)
        clone_map[v] = chained.create_volume()
    for i, op in enumerate(ops):
        if op[0] == "write":
            _, vol, page, block = op
            ref.write(vol, page, block, _pay(i + 1))
            chained.write(clone_map[vol], page, block,
                          np.asarray(_pay(i + 1)))
        elif op[0] == "snapshot":
            ref_ids.append(("snapshot", ref.snapshot(op[1])))
            chained.snapshot(clone_map[op[1]])
        elif op[0] == "clone":
            vid = ref.clone(op[1])
            ref_ids.append(("clone", vid))
            if vid >= 0:
                clone_map[vid] = chained.clone(clone_map[op[1]])
        else:
            ref.unmap(op[1], op[2])
            chained.unmap(clone_map[op[1]], op[2])

    # control results returned through the CQ match the reference ids
    got_ids = [(op[0], r.result) for op, r in ctl_reqs
               if op[0] in ("snapshot", "clone")]
    assert got_ids == ref_ids

    # bit-exact DBS metadata (both mirrored replicas) vs the reference
    for rep in range(2):
        st, pool = _ring_state(eng, rep)
        _assert_states_equal(st, ref.st, msg=f"replica {rep} seed {seed}")
        np.testing.assert_array_equal(np.asarray(pool), np.asarray(ref.pool),
                                      err_msg=f"pool {rep} seed {seed}")

    # content-identical to the chained-store reference walk (holes = zeros)
    st0, pool0 = _ring_state(eng, 0)
    all_vols = [v for v in clone_map]
    for vol in all_vols:
        for page in range(pages):
            for block in range(0, 8, 3):
                got = _masked_read(st0, pool0, vol, page, block)
                want = chained.read(clone_map[vol], page, block)
                want = (np.zeros(PAY, np.float32) if want is None
                        else np.asarray(want))
                np.testing.assert_allclose(
                    got, want,
                    err_msg=f"vol {vol} page {page} block {block}")


def test_inband_delete_matches_host_sequence():
    eng = Engine(_cfg(n_queues=1))
    ref = HostRef()
    va = eng.create_volume()
    vb = eng.create_volume()
    for _ in range(2):
        ref.st, _ = dbs.create_volume(ref.st)
    for i in range(12):
        vol = va if i % 2 else vb
        eng.submit(Request(req_id=i, kind="write", volume=vol, page=i,
                           block=0, payload=_pay(i + 1)))
        ref.write(vol, i, 0, _pay(i + 1))
    eng.drain()
    eng.delete_volume(va)
    ref.delete(va)
    # deleting A freed its extents and left B intact — and the freed ids
    # recycle identically: create a new volume and write through it
    vc = eng.create_volume()
    ref.st, _ = dbs.create_volume(ref.st)
    assert vc == va                        # first free volume slot reused
    for i in range(6):
        eng.submit(Request(req_id=100 + i, kind="write", volume=vc, page=i,
                           block=1, payload=_pay(50 + i)))
        ref.write(vc, i, 1, _pay(50 + i))
    eng.drain()
    for rep in range(2):
        st, pool = _ring_state(eng, rep)
        _assert_states_equal(st, ref.st, msg=f"replica {rep}")
        np.testing.assert_array_equal(np.asarray(pool), np.asarray(ref.pool))


def test_inband_control_error_statuses():
    eng = Engine(_cfg())
    r = Request(req_id=0, kind="snapshot", volume=9)    # never created
    eng.submit(r)
    eng.drain()
    assert r.status == ST_ERR and r.result == -1


def test_control_failure_surface_matches_host_modes():
    """snapshot/clone of a dead volume report -1 on every comm mode — the
    ring's sync wrappers must not grow their own error surface."""
    ring = Engine(_cfg(n_shards=2))
    pool = Engine(_cfg(comm="sharded", n_shards=2))
    for eng in (ring, pool):
        eng.create_volume()
        assert eng.snapshot(9) == -1 or eng.snapshot(9) is None
        assert eng.clone(9) == -1


# ---------------------------------------------------------------------------
# 3. in-band FAIL/REBUILD on the sharded pool, mid-drain
# ---------------------------------------------------------------------------
def test_inband_fail_rebuild_mid_drain_sharded():
    eng = Engine(_cfg(n_shards=3))
    pool = eng.pool
    assert isinstance(pool, RingEngine)
    vols = [eng.create_volume() for _ in range(3)]
    for i in range(60):
        eng.submit(Request(req_id=i, kind="write", volume=vols[i % 3],
                           page=i % 20, block=0, payload=_pay(i + 1)))
    assert eng.drain() == 60
    baseline = {v: np.asarray(pool.read_volume(
        v, jnp.arange(20, dtype=jnp.int32), jnp.zeros(20, jnp.int32)))
        for v in vols}

    sick = vols[1] % 3
    fail_req = Request(req_id=99, kind="fail", shard=sick, block=0)
    reqs = []
    for i in range(30):                     # traffic everywhere, fail inline
        if i == 11:
            reqs.append(fail_req)
        reqs.append(Request(req_id=100 + i, kind="write", volume=vols[i % 3],
                            page=20 + (i % 10), block=0,
                            payload=_pay(200 + i)))
        reqs.append(Request(req_id=500 + i, kind="read", volume=vols[i % 3],
                            page=i % 20, block=0))
    for r in reqs:
        eng.submit(r)
    assert eng.drain() == 61
    assert fail_req.status == ST_OK
    assert not pool.backend.healthy[sick, 0]
    for s in range(3):
        if s != sick:
            assert pool.backend.consistent(s)
    for v in vols:                          # old data intact everywhere
        got = np.asarray(pool.read_volume(
            v, jnp.arange(20, dtype=jnp.int32), jnp.zeros(20, jnp.int32)))
        np.testing.assert_allclose(got, baseline[v])

    # in-band rebuild, then force reads from the rebuilt replica: it must
    # serve the writes it missed while failed
    reb = Request(req_id=600, kind="rebuild", shard=sick, block=0)
    eng.submit(reb)
    assert eng.drain() == 1
    assert reb.status == ST_OK and pool.backend.consistent()
    pool.fail(sick, 1)
    got = np.asarray(pool.read_volume(
        vols[1], jnp.asarray([25], jnp.int32), jnp.zeros(1, jnp.int32)))
    assert got[0][0] >= 200.0
    pool.rebuild(sick, 1)
    assert pool.backend.healthy.all()


def test_inband_fail_rebuild_protocol_errors():
    eng = Engine(_cfg(n_shards=2))
    pool = eng.pool
    eng.create_volume()
    # rebuild of a healthy replica: CQE status, mask untouched
    r = Request(req_id=0, kind="rebuild", shard=0, block=0)
    eng.submit(r)
    eng.drain()
    assert r.status == ST_HEALTHY and pool.backend.healthy.all()
    # failing down to the last healthy replica: rejected in-band
    pool.fail(0, 0)
    r2 = Request(req_id=1, kind="fail", shard=0, block=1)
    eng.submit(r2)
    eng.drain()
    assert r2.status == ST_LAST
    assert pool.backend.healthy[0, 1]       # mask unchanged
    # the sync wrappers raise like the host-side controller
    with pytest.raises(RuntimeError):
        pool.fail(0, 1)
    with pytest.raises(ValueError):
        pool.rebuild(0, 1)
    with pytest.raises(IndexError):
        pool.fail(9, 0)
    pool.rebuild(0, 0)
    assert pool.backend.healthy.all()


# ---------------------------------------------------------------------------
# 4. dispatch accounting: in-band means IN the program
# ---------------------------------------------------------------------------
def test_one_program_per_class_signature_no_control_retrace():
    eng = Engine(_cfg(n_shards=2))
    pool = eng.pool
    vols = [eng.create_volume() for _ in range(4)]

    def traffic(base):
        for i in range(40):
            v = vols[i % 4]
            if i % 3 == 0:
                eng.submit(Request(req_id=base + i, kind="read", volume=v,
                                   page=i % 32, block=0))
            else:
                eng.submit(Request(req_id=base + i, kind="write", volume=v,
                                   page=i % 32, block=i % 8,
                                   payload=_pay(i)))
        eng.submit(Request(req_id=base + 90, kind="snapshot", volume=vols[0]))
        eng.submit(Request(req_id=base + 91, kind="unmap", volume=vols[1],
                           page=2))
    traffic(0)
    assert eng.drain() == 42
    assert all(v == 1 for v in pool.trace_counts.values()), pool.trace_counts
    before = dict(pool.trace_counts)
    d0 = pool.dispatches
    # more traffic with MORE control ops: no new programs, one dispatch per
    # pump — control ops cost zero extra host dispatches
    traffic(1000)
    assert eng.drain() == 42
    assert pool.trace_counts == before
    assert pool.dispatches > d0


def test_ring_pump_is_single_host_hop_with_control_aboard(monkeypatch):
    eng = Engine(_cfg(n_queues=1))   # one queue: the whole stream + its
                                     # control tail fit one ordered batch
    vol = eng.create_volume()
    # warm every program this traffic shape needs
    eng.submit(Request(req_id=0, kind="write", volume=vol, page=0, block=0,
                       payload=_pay(1)))
    eng.submit(Request(req_id=1, kind="snapshot", volume=vol))
    eng.drain()
    for i in range(6):
        eng.submit(Request(req_id=10 + i, kind="write", volume=vol,
                           page=1 + i, block=0, payload=_pay(i)))
    eng.submit(Request(req_id=20, kind="snapshot", volume=vol))
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (calls.append(1), real(x))[1])
    done = eng.pool.pump()
    assert done == 7
    assert len(calls) == 1, f"expected 1 completion fetch, saw {len(calls)}"


# ---------------------------------------------------------------------------
# 5. satellite: unified result/status across every comm mode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("comm,storage,shards", [
    ("loop", "chained", 1), ("loop", "dbs", 1),
    ("slots", "chained", 1), ("slots", "dbs", 1),
    ("fused", "dbs", 1), ("sharded", "dbs", 2), ("ring", "dbs", 2),
])
def test_result_status_unified_across_comms(comm, storage, shards):
    eng = Engine(EngineConfig(comm=comm, storage=storage, payload_shape=PAY,
                              n_extents=256, max_pages=64, batch=16,
                              n_replicas=2, n_shards=shards, max_volumes=16))
    vol = eng.create_volume()
    w = Request(req_id=0, kind="write", volume=vol, page=1, block=2,
                payload=_pay(7))
    eng.submit(w)
    assert eng.drain() == 1
    r = Request(req_id=1, kind="read", volume=vol, page=1, block=2)
    eng.submit(r)
    assert eng.drain() == 1
    assert w.status == 0 and r.status == 0
    np.testing.assert_allclose(np.asarray(r.result), np.full(PAY, 7.0))


@pytest.mark.parametrize("comm,storage,shards", [
    ("loop", "chained", 1), ("loop", "dbs", 1),
    ("slots", "chained", 1), ("slots", "dbs", 1),
    ("fused", "dbs", 1), ("sharded", "dbs", 2), ("ring", "dbs", 2),
    ("upstream", "dbs", 1), ("host", "dbs", 1),
])
def test_latency_unified_across_comms(comm, storage, shards):
    """Satellite (ISSUE 4): ``Request.latency`` is populated — in pump
    ticks — on EVERY comm mode, not just the ring's CQE path. A lone
    request completes at latency 1; under slot pressure (or the upstream/
    host one-op-per-tick loop) later requests ride later ticks."""
    eng = Engine(EngineConfig(comm=comm, storage=storage, payload_shape=PAY,
                              n_extents=256, max_pages=64, batch=8,
                              n_slots=4, n_replicas=2, n_shards=shards,
                              max_volumes=16))
    vol = eng.create_volume()
    w = Request(req_id=0, kind="write", volume=vol, page=1, block=2,
                payload=np.full(PAY, 7.0, np.float32))
    eng.submit(w)
    assert eng.drain() == 1
    assert w.latency == 1, (comm, w.latency)
    reqs = [Request(req_id=i, kind="write", volume=vol, page=2 + i, block=0,
                    payload=np.full(PAY, float(i), np.float32))
            for i in range(8)]
    for r in reqs:
        eng.submit(r)
    assert eng.drain() == 8
    lats = sorted(r.latency for r in reqs)
    assert all(l is not None and l >= 1 for l in lats), (comm, lats)
    assert lats[-1] > lats[0], (comm, lats)   # 4 slots / 1-op ticks: queueing
    rd = Request(req_id=100, kind="read", volume=vol, page=1, block=2)
    eng.submit(rd)
    assert eng.drain() == 1
    assert rd.latency is not None and rd.latency >= 1


def test_result_status_upstream_engine():
    eng = UpstreamEngine(EngineConfig(payload_shape=PAY))
    vol = eng.create_volume()
    w = Request(req_id=0, kind="write", volume=vol, page=1, block=2,
                payload=np.full(PAY, 7.0))
    eng.submit(w)
    eng.drain()
    r = Request(req_id=1, kind="read", volume=vol, page=1, block=2)
    eng.submit(r)
    eng.drain()
    assert w.status == 0 and r.status == 0
    np.testing.assert_allclose(np.asarray(r.result), np.full(PAY, 7.0))


def test_control_kinds_rejected_off_ring():
    eng = Engine(_cfg(comm="fused"))
    eng.create_volume()
    with pytest.raises(ValueError):
        eng.submit(Request(req_id=0, kind="snapshot", volume=0))
    # rejection happens at SUBMIT, not mid-drain: a drain-time failure
    # would already have popped (and then lost) innocent data requests
    pool = Engine(_cfg(comm="sharded", n_shards=2))
    vol = pool.create_volume()
    pool.frontend.submit(Request(req_id=1, kind="write", volume=vol,
                                 page=0, payload=_pay(1)))
    with pytest.raises(ValueError):
        pool.frontend.submit(Request(req_id=2, kind="snapshot", volume=vol))
    assert pool.frontend.depth() == 1       # the data request is intact
    assert pool.drain() == 1


def test_chained_store_control_ops_noop_on_miss():
    """The chained reference baseline must not diverge into KeyErrors where
    the DBS path completes harmlessly (delete-then-anything sequences)."""
    from repro.core.engine import ChainedStore
    cs = ChainedStore(PAY)
    v = cs.create_volume()
    cs.write(v, 0, 0, np.ones(PAY))
    cs.delete_volume(v)
    cs.delete_volume(v)                     # second delete: no-op
    cs.snapshot(v)
    cs.unmap(v, 0)
    assert cs.clone(v) == -1
    assert cs.read(v, 0, 0) is None


# ---------------------------------------------------------------------------
# 6. satellite: ChainedReplicas volume-id agreement + null-storage rr
# ---------------------------------------------------------------------------
def test_chained_replicas_detects_divergent_volume_ids():
    eng = Engine(EngineConfig(storage="chained", comm="slots",
                              payload_shape=PAY))
    eng.create_volume()                     # in agreement: fine
    eng.backend.stores[1].create_volume()   # one store drifts ahead
    with pytest.raises(RuntimeError):
        eng.create_volume()
    with pytest.raises(RuntimeError):
        eng.backend.clone(0)                # clone ids guarded too


def test_chained_null_storage_read_leaves_rr_alone():
    eng = Engine(EngineConfig(storage="chained", comm="slots",
                              null_storage=True, payload_shape=PAY))
    vol = eng.create_volume()
    b = eng.backend
    before = b._rr
    assert b.read(vol, [0, 1], [0, 0]) is None
    assert b.read(vol, [2], [0]) is None
    assert b._rr == before, "null-storage reads must not burn the rr cursor"
    # with real storage the cursor advances as before
    eng2 = Engine(EngineConfig(storage="chained", comm="slots",
                               payload_shape=PAY))
    v2 = eng2.create_volume()
    r0 = eng2.backend._rr
    eng2.backend.read(v2, [0], [0])
    assert eng2.backend._rr == r0 + 1


# ---------------------------------------------------------------------------
# ladder integration
# ---------------------------------------------------------------------------
def test_ladder_has_ring_column():
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.ladder import COLUMNS, make_engine
    assert "+ring" in COLUMNS
    eng = make_engine("+ring", "full_engine", payload_shape=PAY,
                      max_pages=64, n_extents=256, n_shards=2)
    assert eng.cfg.comm == "ring"
    vols = [eng.create_volume() for _ in range(2)]
    for i in range(24):
        eng.submit(Request(req_id=i, kind="write" if i % 2 else "read",
                           volume=vols[i % 2], page=i % 32, block=i % 8,
                           payload=jnp.ones(PAY)))
    assert eng.drain() == 24


# ---------------------------------------------------------------------------
# 6. the packed SQE upload: one host-to-device transfer per pump
# ---------------------------------------------------------------------------
# float32 lanes that are no byte values: negatives, fractions, a subnormal,
# -0.0, an infinity and NaNs that carry payload bits (quiet, signalling,
# negative)
_ODD_BITS = np.array([0xBFC00000, 0x3E800000, 0x00000001, 0x80000000,
                      0xFF800000, 0x7FC00123, 0x7F800001, 0xFFC0BEEF],
                     np.uint32)


def _odd(i: int) -> np.ndarray:
    lanes = _ODD_BITS.view(np.float32).copy()
    lanes[:2] -= np.float32(i)               # -1.5 - i, 0.25 - i
    return lanes


def _tier_traffic(tier, vols, base, odd=_odd):
    """Requests whose batch runs ``tier``'s program: reads of pages the
    warm-up wrote, plus the writes (payloads ``odd(i)``) and control/compute
    ops the tier adds."""
    reqs = [Request(req_id=base + i, kind="read", volume=vols[i % 2],
                    page=i % 4, block=i % 8) for i in range(4)]
    if tier != "read":
        reqs += [Request(req_id=base + 10 + i, kind="write",
                         volume=vols[i % 2], page=4 + i, block=i % 8,
                         payload=odd(base + i)) for i in range(4)]
    if tier == "compute":
        reqs += [Request(req_id=base + 20, kind="compute", fn="checksum",
                         volume=vols[0], page=0, block=2),
                 Request(req_id=base + 21, kind="compute",
                         fn="verify_on_read", volume=vols[1], page=1,
                         block=3, arg=7)]
    elif tier == "vol":
        reqs += [Request(req_id=base + 20, kind="snapshot", volume=vols[0]),
                 Request(req_id=base + 21, kind="unmap", volume=vols[1],
                         page=2)]
    elif tier == "repl":
        reqs += [Request(req_id=base + 20, kind="fail", shard=0, block=1)]
    return reqs


def _same_bits(got, want, what):
    assert jax.tree.structure(got) == jax.tree.structure(want), what
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert (g.dtype, g.shape) == (w.dtype, w.shape), what
        assert g.tobytes() == w.tobytes(), what


def _pump_against_the_leaf_sqe(eng, traffic, monkeypatch):
    """Submit ``traffic`` and run one pump through the packed upload; also
    run the step on the pre-pump state with the SQE uploaded leaf by leaf
    from ``_stage``'s named views. Returns the pump's handle, its program
    key, what the pump left (table, CQ, states, pools, watermarks,
    health) and the reference step's outputs."""
    from functools import partial

    from repro.core import ring
    pool, fe = eng.pool, eng.pool.frontend
    for r in traffic:
        eng.submit(r)
    staged = []
    stage = fe._stage
    monkeypatch.setattr(fe, "_stage",
                        lambda *a: staged.append(stage(*a)) or staged[-1])
    backend = pool.backend
    states, pools, healthy = backend.device_state()
    before = jax.tree.map(jnp.copy, (fe.table, pool.cq, states, pools,
                                     backend.device_page_revs(),
                                     backend._rr, healthy))
    p = pool.pump_async()
    key = pool._canon(staged[0][2])
    st = staged[0][1]
    leaves = ring.SQE(**{k: jnp.asarray(st[k])
                         for k in ring.SQE_LANES + ("payload", "step")})
    assert st["want"].dtype == bool and leaves.payload.dtype == jnp.float32
    core = partial(ring.ring_step_core, classes=key, kernel=pool._kernel,
                   compute_tail=pool._compute_tail)
    table0, cq0, states0, pools0, prs0, rr0, healthy0 = before
    ref = jax.jit(ring.vmap_shards(core, pool.n_shards))(
        table0, cq0, states0, pools0, prs0, leaves, rr0, healthy0)
    states1, pools1, healthy1 = backend.device_state()
    got = (fe.table, pool.cq, states1, pools1, backend.device_page_revs(),
           healthy1)
    return p, key, got, ref


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("tier", ["read", "write", "compute", "vol", "repl"])
def test_packed_upload_matches_the_eleven_leaf_sqe(tier, shards,
                                                   monkeypatch):
    """One pump through the packed upload leaves the same CQE view, CQ,
    slot table, replica states, pools, watermarks and health, bit for bit,
    as the step run on the pre-pump state with the SQE uploaded leaf by leaf
    from ``_stage``'s named views."""
    from repro.core import ring
    eng = Engine(_cfg(n_shards=shards, n_queues=1))
    vols = [eng.create_volume() for _ in range(2)]
    for i in range(8):
        eng.submit(Request(req_id=100 + i, kind="write", volume=vols[i % 2],
                           page=i % 4, block=i % 8, payload=_odd(i)))
    assert eng.drain() == 8
    p, key, got, ref = _pump_against_the_leaf_sqe(
        eng, _tier_traffic(tier, vols, 1000), monkeypatch)
    assert tier in key
    view = ring.unpack_cqe(np.asarray(jax.device_get(p.cqe)), PAY)
    for name, g, w in zip(("table", "cq", "states", "pools", "page_revs",
                           "healthy", "view"), got + (view,), ref):
        _same_bits(g, w, name)
    # the comparison is not vacuous: the reads returned the warm-up's odd
    # float lanes, NaN payload bits included
    reads = np.asarray(view.reads).view(np.uint32)
    assert (reads == 0x7FC00123).any() and (reads == 0xFFC0BEEF).any()


# ---------------------------------------------------------------------------
# 7. the packed CQE fetch: one device-to-host transfer per pump
# ---------------------------------------------------------------------------
_QUIET_NANS = (0x7FC00123, 0xFFC0BEEF)      # NaNs that carry payload bits


@pytest.mark.parametrize("payload", [(), (4096,)], ids=["scalar", "4k"])
@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("tier", ["read", "write", "vol"])
def test_packed_cqe_matches_the_five_leaf_view(tier, shards, payload,
                                               monkeypatch):
    """``unpack_cqe`` of the pump's one packed fetch equals, bit for bit,
    the ``CQEView`` that ``ring_step_core`` returns for the same step: lanes
    not admitted, negative statuses and values, NaN payload bits."""
    from repro.core import ring

    def odd(i):
        lanes = _odd(i)
        return np.resize(lanes, payload) if payload else lanes[5 + i % 3]

    eng = Engine(_cfg(n_shards=shards, n_queues=1, payload_shape=payload,
                      n_extents=32, max_pages=8, page_blocks=8))
    vols = [eng.create_volume() for _ in range(2)]
    for i in range(8):
        eng.submit(Request(req_id=100 + i, kind="write", volume=vols[i % 2],
                           page=i % 4, block=i % 8, payload=odd(i)))
    assert eng.drain() == 8
    traffic = _tier_traffic(tier, vols, 1000, odd)
    if tier == "vol":                        # a volume never created
        traffic.append(Request(req_id=1100, kind="snapshot",
                               volume=4 * shards))
    p, key, got, ref = _pump_against_the_leaf_sqe(eng, traffic, monkeypatch)
    assert tier in key
    assert p.cqe.dtype == jnp.int32
    assert p.cqe.shape == (shards, 16, len(ring.CQE_LANES)
                           + int(np.prod(payload)))
    buf = np.asarray(jax.device_get(p.cqe))
    view = ring.unpack_cqe(buf, payload)
    _same_bits(view, ref[-1], "view")
    bits = np.asarray(view.reads).view(np.uint32)
    assert np.isin(bits, _QUIET_NANS).any()
    assert (~view.ok).any() and view.ok.any()
    assert (view.value < 0).any()
    assert (view.status < 0).any() == (tier == "vol")
    # the unpacked fields are views of the fetched buffer, not copies
    for f in ("status", "value", "latency", "reads"):
        assert np.shares_memory(getattr(view, f), buf), f


class _Counting:
    """A module stand-in that records calls of some of its functions, and
    their positional arguments in ``args``."""

    def __init__(self, module, names, calls):
        self._module, self._names, self._calls = module, names, calls
        self.args = []

    def __getattr__(self, name):
        fn = getattr(self._module, name)
        if name not in self._names:
            return fn

        def counted(*a, **kw):
            self._calls.append(name)
            self.args.append(a)
            return fn(*a, **kw)
        return counted


@pytest.mark.parametrize("shards", [1, 2])
def test_a_pump_makes_one_host_to_device_transfer(shards, monkeypatch):
    from repro.core import ring
    eng = Engine(_cfg(n_shards=shards))
    pool = eng.pool
    vols = [eng.create_volume() for _ in range(2)]

    def submit(kind, base):
        for i in range(6):
            eng.submit(Request(req_id=base + i, kind=kind,
                               volume=vols[i % 2], page=i, block=i % 8,
                               payload=_odd(i) if kind == "write" else None))

    submit("write", 0)
    assert eng.drain() == 6
    submit("read", 10)
    assert eng.drain() == 6                  # both programs compiled
    calls = []
    monkeypatch.setattr(ring, "jax", _Counting(jax, {"device_put"}, calls))
    monkeypatch.setattr(ring, "jnp", _Counting(jnp, {"asarray"}, calls))
    h = ring.header_words(16)
    whole = shards * (h + 16 * PAY[0]) * 4
    for kind, nbytes, skips in (("write", whole, 0),
                                ("read", shards * h * 4, 1)):
        c0 = pool.upload_counters()
        d0 = pool.dispatches
        submit(kind, 100)
        calls.clear()
        assert pool.pump() == 6
        assert calls == ["device_put"], kind
        c1 = pool.upload_counters()
        assert pool.dispatches - d0 == 1
        assert c1["upload_transfers"] - c0["upload_transfers"] == 1
        assert c1["upload_bytes"] - c0["upload_bytes"] == nbytes, kind
        assert (c1["upload_payload_skips"]
                - c0["upload_payload_skips"]) == skips, kind
    assert pool.trace_counts == {("read", "write"): 1, ("read",): 1}
    assert pool.upload_transfers == pool.dispatches


@pytest.mark.parametrize("shards", [1, 2])
def test_a_pump_makes_one_device_to_host_transfer(shards, monkeypatch):
    """``_complete`` fetches the step's CQE view with one ``device_get`` of
    one array, in the read tier and in the write tier."""
    from repro.core import ring
    eng = Engine(_cfg(n_shards=shards))
    pool = eng.pool
    vols = [eng.create_volume() for _ in range(2)]

    def submit(kind, base):
        for i in range(6):
            eng.submit(Request(req_id=base + i, kind=kind,
                               volume=vols[i % 2], page=i, block=i % 8,
                               payload=_odd(i) if kind == "write" else None))

    submit("write", 0)
    assert eng.drain() == 6
    submit("read", 10)
    assert eng.drain() == 6                  # both programs compiled
    calls = []
    spy = _Counting(jax, {"device_get"}, calls)
    monkeypatch.setattr(ring, "jax", spy)
    nbytes = shards * 16 * (len(ring.CQE_LANES) + PAY[0]) * 4
    for kind in ("write", "read"):
        c0 = pool.fetch_counters()
        d0 = pool.dispatches
        submit(kind, 100)
        calls.clear()
        spy.args.clear()
        assert pool.pump() == 6
        assert calls == ["device_get"], kind
        (fetched,), = spy.args
        assert isinstance(fetched, jax.Array), kind
        c1 = pool.fetch_counters()
        assert pool.dispatches - d0 == 1
        assert c1["fetch_transfers"] - c0["fetch_transfers"] == 1
        assert c1["fetch_bytes"] - c0["fetch_bytes"] == nbytes, kind
    assert pool.fetch_transfers == pool.dispatches
