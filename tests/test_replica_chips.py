"""The three-node layout: ``VolumeManager(backend="ring", n_replicas=3,
replica_chips=3)`` puts replica r on device r and lowers the ring step over
a mesh of the replica chips. Checked here on four virtual CPU devices
(``XLA_FLAGS`` must be set before jax is imported, so the scenario runs in
a child python, once for the module): the byte API and every replica's
pool against the bytearray oracle and against the same seeded run with
all replicas on one device, the placement, the one upload and the one
fetch per pump, the fan-out counters, the acknowledgement's dependence on every replica's
write, and the layouts and ops the ring refuses.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SMOKE = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")

CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import importlib.util
    import json
    import sys
    import jax
    import numpy as np
    from repro.core import dbs
    from repro.core.blockdev import VolumeManager
    from repro.harness.oracle import ByteOracle

    G = dict(backend="ring", n_replicas=3, payload_elems=64, page_blocks=8,
             n_extents=96, max_pages=16, batch=16, kernel="pallas")
    BB = 64

    def images(mgr, vids):
        # every replica's bytes of each volume, read through its own
        # extent map (holes read as zeros), and where its pool lives
        states, pools, _ = mgr.engine.backend.device_state()
        out, where = [], []
        for st, pool in zip(states, pools):
            table, rows = np.asarray(st.table)[0], np.asarray(pool)[0]
            img = {}
            for vid in vids:
                ext = table[vid]
                got = np.where((ext >= 0)[:, None, None],
                               rows[np.maximum(ext, 0)], 0)
                img[vid] = got.astype(np.uint8).tobytes()
            out.append(img)
            where.append(sorted(d.id for d in pool.devices()))
        raw = [(np.asarray(p).tobytes(), np.asarray(s.table).tobytes())
               for s, p in zip(states, pools)]
        return out, where, raw

    def scenario(chips, seed):
        mgr = VolumeManager(replica_chips=chips, **G)
        cap = mgr.capacity
        orc = ByteOracle(cap)
        rng = np.random.default_rng(seed)
        vols = [mgr.create() for _ in range(3)]
        for v in vols:
            orc.add_volume(v.vid)
        reads = []

        def write(v, off, data):
            fut = v.pwrite(off, data)
            orc.write(v.vid, off, data)
            return fut

        def read(v, off, n):
            reads.append((v.pread(off, n), orc.expected(v.vid, off, n)))

        s0 = mgr.stats()
        futs, blocks = [], 0
        for i in range(120):                    # aligned, some multi-block
            v = vols[int(rng.integers(3))]
            nb = int(rng.integers(1, 4))
            off = int(rng.integers(cap // BB - nb)) * BB
            futs.append(write(v, off, rng.integers(
                0, 256, nb * BB, dtype=np.uint8).tobytes()))
            blocks += nb
            if i % 7 == 0:
                read(v, int(rng.integers(cap // BB - 2)) * BB, 2 * BB)
        mgr.flush()
        s1 = mgr.stats()
        for off, n in ((BB // 3, 5 * BB + 17), (9 * BB + 5, 40)):
            futs.append(write(vols[1], off, rng.integers(
                0, 256, n, dtype=np.uint8).tobytes()))
        read(vols[1], 3, 7 * BB)
        mgr.flush()
        vols[0].snapshot()
        clone = vols[0].clone()
        orc.clone(vols[0].vid, clone.vid)
        for v in (vols[0], clone):
            for _ in range(10):
                off = int(rng.integers(cap // BB)) * BB
                futs.append(write(v, off, rng.integers(
                    0, 256, BB, dtype=np.uint8).tobytes()))
        d_off, d_len = 2 * 8 * BB + 11, 3 * 8 * BB + 100
        futs.append(vols[2].discard(d_off, d_len))
        orc.discard(vols[2].vid, d_off, d_len)
        mgr.flush()
        for f in futs:
            f.result()
        bad_reads = sum(f.result() != want for f, want in reads)
        vids = sorted(orc.shadow)
        bad_api = sum(mgr.open(vid).read(0, cap) != bytes(orc.shadow[vid])
                      for vid in vids)
        imgs, where, raw = images(mgr, vids)
        bad_replica = [sum(img[vid] != bytes(orc.shadow[vid]) for vid in vids)
                       for img in imgs]
        s = mgr.stats()
        impl = mgr.engine.impl
        return dict(
            bad_reads=bad_reads, reads=len(reads), bad_api=bad_api,
            bad_replica=bad_replica, where=where, raw=raw,
            dispatches=impl.dispatches, uploads=s["upload_transfers"],
            fetches=s["fetch_transfers"], fetch_bytes=s["fetch_bytes"],
            fanout_phase=s1["fanout_bytes"] - s0["fanout_bytes"],
            blocks_phase=blocks, fanout=s["fanout_bytes"],
            remote_reads=s["remote_read_lanes"], slots=s["slots_active"],
            write_rows=s["write_rows"], mgr=mgr)

    def refused(make):
        try:
            make()
        except ValueError as e:
            return str(e)
        return None

    def ancestors(jaxpr, var):
        prod = {v: e for e in jaxpr.eqns for v in e.outvars}
        seen, stack, out = set(), [var], []
        while stack:
            v = stack.pop()
            if not hasattr(v, "aval") or type(v).__name__ == "Literal":
                continue
            if id(v) in seen:
                continue
            seen.add(id(v))
            e = prod.get(v)
            if e is not None:
                out.append(e)
                stack.extend(e.invars)
        return out

    def subjaxprs(params):
        for p in params.values():
            for q in (p if isinstance(p, (list, tuple)) else (p,)):
                if hasattr(q, "eqns"):
                    yield q
                elif hasattr(q, "jaxpr") and hasattr(q.jaxpr, "eqns"):
                    yield q.jaxpr

    def contains(eqn, pred):
        return pred(eqn) or any(contains(e, pred) for j in subjaxprs(
            eqn.params) for e in j.eqns)

    def find(jaxpr, name):
        for e in jaxpr.eqns:
            if e.primitive.name == name:
                return e
            for j in subjaxprs(e.params):
                got = find(j, name)
                if got is not None:
                    return got
        return None

    out = {}
    one, three = scenario(1, 5), scenario(3, 5)
    for k, r in (("one", one), ("three", three)):
        out[k] = {n: v for n, v in r.items() if n not in ("raw", "mgr")}
    out["same_as_one_chip"] = [a == b for a, b in zip(one["raw"],
                                                      three["raw"])]

    # the acknowledgement: node 0's CQE status depends, through a psum
    # over the replica nodes, on the pool the write kernel returned
    mgr = three["mgr"]
    impl = mgr.engine.impl
    get_step, seen = impl._get_step, {}

    def spy(classes):
        fn, key = get_step(classes)
        def record(*args):
            seen[key] = (fn, args)
            return fn(*args)
        return record, key
    impl._get_step = spy
    mgr.open(0).write(0, bytes(BB))
    impl._get_step = get_step
    fn, args = seen[("read", "write")]
    body = find(jax.make_jaxpr(fn)(*args).jaxpr, "shard_map").params["jaxpr"]
    # the packed CQE (ring.pack_cqe) is the body's last output, one
    # concatenate of the lanes ok, status, value, latency and the reads
    pack = next(e for e in body.eqns if body.outvars[-1] in e.outvars)
    assert pack.primitive.name == "concatenate" and len(pack.invars) == 5
    status = pack.invars[1]
    up = ancestors(body, status)
    out["ack_psum"] = any(e.primitive.name == "psum" for e in up)
    out["ack_barrier"] = any(e.primitive.name == "optimization_barrier"
                             for e in up)
    out["ack_kernel"] = any(contains(e, lambda q: q.primitive.name ==
                                     "pallas_call" and "dbs_rw_write" in
                                     str(q.params)) for e in up)

    # a node that leaves the write out fails the write everywhere
    orig = dbs.write_pages
    def skip_node2(st, vol, pages, bits, mask=None):
        return orig(st, vol, pages, bits,
                    mask & (jax.lax.axis_index("node") != 2))
    dbs.write_pages = skip_node2
    try:
        m2 = VolumeManager(replica_chips=3, **G)
        v = m2.create()
        try:
            v.write(0, bytes(range(BB)))
            out["skipped_write"] = "acknowledged"
        except OSError as e:
            out["skipped_write"] = str(e)
    finally:
        dbs.write_pages = orig

    ring = lambda **kw: VolumeManager(**dict(G, **kw))
    out["refused_layouts"] = [refused(lambda kw=kw: ring(**kw)) for kw in (
        dict(replica_chips=2),
        dict(backend="fused", replica_chips=3),
        dict(n_replicas=2, n_shards=2, replica_chips=2),
        dict(n_replicas=5, replica_chips=5),
        dict(null_storage=True, replica_chips=3))]
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  sys.argv[1])
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    geometry = dict(smoke.GEOMETRY, payload_elems=64, page_blocks=8,
                    n_extents=256, max_pages=32, kernel="pallas",
                    replica_chips=3)
    got = smoke.smoke(geometry, n_volumes=4, write_bytes=64 * 200,
                      diverge_writes=16, seed=0, log=lambda *_: None)
    out["smoke"] = {k: got[k] for k in ("api_mismatches",
                                         "replica_mismatches",
                                         "pool_devices", "volumes")}

    vol = mgr.open(0)
    out["refused_ops"] = [
        refused(lambda: impl.fail(0, 1)),
        refused(lambda: impl.rebuild(0, 1)),
        refused(lambda: mgr.engine.control("fail", shard=0, replica=2)),
        refused(lambda: vol.compute("checksum", 0, BB))]
    out["after_refusals"] = vol.read(0, BB) == bytes(BB)
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def res():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", CHILD, SMOKE], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("layout", ["one", "three"])
def test_byte_api_matches_the_oracle(res, layout):
    r = res[layout]
    assert r["reads"] > 10 and r["bad_reads"] == 0
    assert r["bad_api"] == 0


@pytest.mark.parametrize("layout", ["one", "three"])
def test_every_replica_matches_the_oracle(res, layout):
    assert res[layout]["bad_replica"] == [0, 0, 0]


def test_every_replica_equals_the_one_chip_run(res):
    """Pools and extent maps bit for bit, replica by replica."""
    assert res["same_as_one_chip"] == [True, True, True]
    assert res["three"]["write_rows"] == res["one"]["write_rows"]


def test_replica_r_lives_on_device_r(res):
    assert res["three"]["where"] == [[0], [1], [2]]
    assert res["one"]["where"] == [[0], [0], [0]]


def test_one_upload_per_pump(res):
    for layout in ("one", "three"):
        r = res[layout]
        assert r["uploads"] == r["dispatches"] > 0
    assert res["three"]["dispatches"] == res["one"]["dispatches"]
    assert res["three"]["slots"] == res["one"]["slots"] == 0


def test_one_fetch_per_pump(res):
    """Node 0's packed CQE view is fetched once per step: the same count
    and bytes as with every replica on one chip."""
    for layout in ("one", "three"):
        r = res[layout]
        assert r["fetches"] == r["dispatches"] > 0
    assert res["three"]["fetch_bytes"] == res["one"]["fetch_bytes"] > 0


def test_fanout_counts_the_written_blocks_twice(res):
    r = res["three"]
    assert r["fanout_phase"] == r["blocks_phase"] * 2 * 64
    assert r["fanout"] > r["fanout_phase"]
    assert r["remote_reads"] > 0
    assert res["one"]["fanout"] == res["one"]["remote_reads"] == 0


def test_the_acknowledgement_waits_for_every_replica(res):
    """The status the host fetches from node 0 is computed from a psum over
    the replica nodes of what each wrote, tied by a barrier to the pool its
    write kernel returned; a node that leaves a write out fails it."""
    assert res["ack_psum"] and res["ack_barrier"] and res["ack_kernel"]
    assert res["skipped_write"].startswith("write failed")


def test_layouts_the_ring_cannot_lower_are_refused(res):
    msgs = res["refused_layouts"]
    assert all(msgs), msgs
    assert "n_replicas=3" in msgs[0]
    assert "comm='ring'" in msgs[1]
    assert "n_shards=1" in msgs[2]
    assert "JAX sees 4" in msgs[3]
    assert "null_storage" in msgs[4]


def test_replica_control_and_compute_are_refused(res):
    msgs = res["refused_ops"]
    assert all(msgs), msgs
    assert all("separate chips" in m for m in msgs)
    assert res["after_refusals"]


def test_chip_smoke_scenario_on_three_chips(res):
    """chip_smoke.py's scenario with ``replica_chips=3``: API read-back,
    snapshot, clone and discard against the oracle, every replica's pool
    checked on its own device."""
    r = res["smoke"]
    assert r["volumes"] == 5
    assert r["api_mismatches"] == 0
    assert r["replica_mismatches"] == [0, 0, 0]
    assert r["pool_devices"] == [[0], [1], [2]]
