"""Smoke run of the storage engine's main path on one TPU chip.

Drives the public block-device API, ``VolumeManager(backend="ring")``, at a
deployment geometry: 4 KiB blocks (the paper's Table I fio block size),
32-block (128 KiB) extents, 4096 extents per replica and three replicas
(Longhorn's default replica count) — each replica pool is 4097 x 32 x 4096
float32, 2 GiB on the device, about 6 GiB in all. On it the run:

- creates 4 volumes of 64 MiB and writes 64 MiB of seeded random data in
  4 KiB random writes, plus one unaligned span;
- snapshots a volume, clones it, diverges both, and discards an unaligned
  range of another;
- reads every byte of every volume back through the API, and reads every
  byte of every volume out of each replica's pool through its own extent
  map, comparing both with a host bytearray oracle.

It prints the device, the resolved kernel, pool and peak device bytes,
compile and run seconds, op counts and mismatch counts, then, as its last
line, ``{"ok": true, "device": {...}}``. Without a TPU it exits non-zero
and prints no result. Run from the repository root:

    python chip_smoke.py [--replica-chips 3]

``--replica-chips 3`` runs the same scenario with each replica on its own
chip (``VolumeManager(replica_chips=3)``: replica r on chip r, writes
mirrored from chip 0 inside the ring step); every replica's pool is then
checked on its own chip, and the peak bytes are printed per chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.blockdev import VolumeManager  # noqa: E402
from repro.kernels.platform import default_interpret  # noqa: E402
from repro.utils.compile_cache import place_compile_cache  # noqa: E402

GEOMETRY = dict(backend="ring", n_shards=1, n_replicas=3,
                payload_elems=4096, page_blocks=32, n_extents=4096,
                max_volumes=8, max_pages=512, batch=64)
N_VOLUMES = 4
WRITE_BYTES = 64 << 20
DIVERGE_WRITES = 256          # post-clone CoW writes on each side
SEED = 0


@jax.jit
def _replica_mismatches(table, pool, vid, expected):
    """Bytes of one volume that differ between a replica and the oracle:
    the volume's pages gathered from the replica's (1, E+1, page, D) pool
    through its own (1, V, P) extent map (holes read as zeros), against
    ``expected`` (uint8). The shard axis is dropped inside the program: an
    eager ``pool[0]`` would copy the whole pool."""
    ext = table[0, vid]
    rows = pool[0, jnp.maximum(ext, 0)]
    got = jnp.where((ext >= 0)[:, None, None], rows, 0)
    return jnp.sum(got.reshape(-1) != expected.astype(pool.dtype))


def smoke(geometry: dict, *, n_volumes: int, write_bytes: int,
          diverge_writes: int, seed: int, log=print) -> dict:
    """Run the scenario on a fresh ``VolumeManager(**geometry)``; returns
    the counts, timings and mismatches."""
    t0 = time.perf_counter()
    mgr = VolumeManager(**geometry)
    bb, pby, cap = mgr.block_bytes, mgr.page_bytes, mgr.capacity
    storage = mgr.engine.backend
    _, pools, _ = storage.device_state()
    jax.block_until_ready(pools)
    out = {"kernel": mgr.engine._kernel,
           "pool_bytes": sum(int(p.nbytes) for p in pools),
           "n_pools": len(pools), "setup_s": time.perf_counter() - t0,
           "pool_devices": [sorted(d.id for d in p.devices())
                            for p in pools]}
    log(f"manager: {mgr!r}, {out['n_pools']} replica pools, "
        f"{out['pool_bytes']} pool bytes")

    rng = np.random.default_rng(seed)
    vols = [mgr.create() for _ in range(n_volumes)]
    oracle = {v.vid: bytearray(cap) for v in vols}
    futs, ops = [], {"pwrite": 0, "discard": 0, "snapshot": 0, "clone": 0,
                     "read": 0}

    def pwrite(vol, off, data):
        futs.append(vol.pwrite(off, data))
        oracle[vol.vid][off:off + len(data)] = data
        ops["pwrite"] += 1

    def random_writes(targets, n):
        pick = rng.integers(len(targets), size=n)
        blocks = rng.integers(cap // bb, size=n)
        data = rng.integers(0, 256, size=(n, bb), dtype=np.uint8)
        for i in range(n):
            pwrite(targets[pick[i]], int(blocks[i]) * bb, data[i].tobytes())

    t0 = time.perf_counter()
    random_writes(vols, write_bytes // bb)
    span = rng.integers(0, 256, size=5 * pby + 777, dtype=np.uint8)
    pwrite(vols[1 % n_volumes], 3 * pby + bb // 3, span.tobytes())
    mgr.flush()
    vols[0].snapshot()
    clone = vols[0].clone()
    if clone is None:
        raise RuntimeError("clone failed")
    ops["snapshot"] += 1
    ops["clone"] += 1
    oracle[clone.vid] = bytearray(oracle[vols[0].vid])
    random_writes([vols[0], clone], 2 * diverge_writes)
    d_off, d_len = 2 * pby + bb + 100, 4 * pby + 5000
    futs.append(vols[2 % n_volumes].discard(d_off, d_len))
    oracle[vols[2 % n_volumes].vid][d_off:d_off + d_len] = bytes(d_len)
    ops["discard"] += 1
    mgr.flush()
    for f in futs:
        f.result()              # raises OSError on an unacknowledged op
    out["write_s"] = time.perf_counter() - t0
    out["written_bytes"] = (write_bytes // bb + 2 * diverge_writes) * bb \
        + len(span)

    t0 = time.perf_counter()
    api_mismatch = 0
    for vid in oracle:
        got = np.frombuffer(mgr.open(vid).read(0, cap), np.uint8)
        api_mismatch += int(np.sum(got != np.frombuffer(oracle[vid],
                                                        np.uint8)))
        ops["read"] += 1
    out["read_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    states, pools, healthy = storage.device_state()
    replica_mismatch = [0] * len(pools)
    for vid, want in oracle.items():
        expected = jnp.asarray(np.frombuffer(want, np.uint8))
        for r, (st, pool) in enumerate(zip(states, pools)):
            replica_mismatch[r] += int(_replica_mismatches(
                st.table, pool, vid, expected))
    out["verify_s"] = time.perf_counter() - t0
    out.update(ops=ops, requests=mgr.engine.completed,
               healthy=np.asarray(healthy).tolist(),
               volumes=len(oracle), read_bytes=len(oracle) * cap,
               api_mismatches=api_mismatch,
               replica_mismatches=replica_mismatch)
    mgr.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--replica-chips", type=int, default=1, choices=(1, 3),
                    help="1: every replica on chip 0; 3: replica r on chip r")
    args = ap.parse_args(argv)
    geometry = dict(GEOMETRY, replica_chips=args.replica_chips)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX reports platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.replica_chips:
        print(f"chip_smoke: --replica-chips {args.replica_chips} needs as "
              f"many chips, JAX sees {len(jax.devices())}", file=sys.stderr)
        return 1
    cache = place_compile_cache()
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_s.append(secs)
        if event.startswith("/jax/core/compile/") else None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}")
    print(f"compile cache: {cache}")
    print(f"geometry: {json.dumps(geometry)}, volumes={N_VOLUMES}, "
          f"write_bytes={WRITE_BYTES}, seed={SEED}")
    t0 = time.perf_counter()
    res = smoke(geometry, n_volumes=N_VOLUMES, write_bytes=WRITE_BYTES,
                diverge_writes=DIVERGE_WRITES, seed=SEED)
    wall = time.perf_counter() - t0
    interpret = default_interpret()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:args.replica_chips]]
    print(f"kernel: {res['kernel']} interpret={interpret}")
    print(f"pool_bytes: {res['pool_bytes']} ({res['n_pools']} replicas, "
          f"on devices {res['pool_devices']})")
    print(f"peak_bytes_in_use: {peaks[0] if len(peaks) == 1 else peaks}")
    print(f"seconds: wall={wall} compile={sum(compile_s)} "
          f"run={wall - sum(compile_s)} setup={res['setup_s']} "
          f"write={res['write_s']} read={res['read_s']} "
          f"verify={res['verify_s']}")
    print(f"ops: {json.dumps(res['ops'])} requests={res['requests']} "
          f"written_bytes={res['written_bytes']} "
          f"read_bytes={res['read_bytes']} volumes={res['volumes']}")
    print(f"mismatches: api={res['api_mismatches']} "
          f"replicas={res['replica_mismatches']} healthy={res['healthy']}")
    problems = []
    if res["kernel"] != "pallas" or interpret:
        problems.append(f"kernel {res['kernel']} interpret={interpret}, "
                        "want pallas compiled")
    if res["api_mismatches"] or any(res["replica_mismatches"]):
        problems.append("bytes differ from the oracle")
    if res["n_pools"] != GEOMETRY["n_replicas"]:
        problems.append(f"{res['n_pools']} replica pools, want "
                        f"{GEOMETRY['n_replicas']}")
    want = [[jax.devices()[r if args.replica_chips > 1 else 0].id]
            for r in range(res["n_pools"])]
    if res["pool_devices"] != want:
        problems.append(f"replica pools on devices {res['pool_devices']}, "
                        f"want {want}")
    if problems:
        print("chip_smoke: FAILED: " + "; ".join(problems), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device,
                      "replica_chips": args.replica_chips}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
