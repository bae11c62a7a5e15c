"""The paper's §IV-A top-down methodology as a benchmark harness.

Columns (cumulative, mirroring Tables I/II — see docs/ARCHITECTURE.md):
  upstream      TGT-style single-loop frontend + dict map + chained store
  +frontend     multi-queue batched admission (ublk analogue), loop comm
  +comm         slot-array (Messages Array) batched comm, chained store
  +dbs          DBS replicas (the full modified engine)
  +fused        single-program engine step (core/fused.py): admission, CoW,
                mirrored stores, reads and retirement in ONE compiled
                program per batch — no host hop between admission and
                completion
  +sharded      EnginePool (core/sharded.py): S engine shards served by ONE
                vmapped fused step per pump, volumes hashed across shards,
                pipelined (double-buffered) completion
  +ring         SQ/CQ ring protocol (core/ring.py): opcode-tagged SQE path
                carrying data AND control ops through the same sharded
                step, CQ completion records on device

Rows (layer cuts): frontend-only (null backend) / without-storage (null
storage) / full engine.

``run_mixed_control`` measures the workload the ring exists for: a data
stream with ~5% snapshot/unmap control ops. ``+ring`` executes them
in-band; the ``fence`` baseline is the pre-ring engine (``+fused``), which
must drain the pipeline and dispatch each control op host-side.

``run_blockdev`` drives the public byte-addressed API
(``blockdev.VolumeManager``) — block-aligned spans plus a mixed-size
workload with ~10% unaligned writes (in-API read-modify-write) — and pins
aligned-span throughput to >= 0.9x the raw request-level ``+ring`` stream.

``run_replication`` is the replica-transport/policy matrix (ISSUE 5): the
slots engine over LocalTransport (gated >= 0.9x the identical ``+dbs``
column — the transport boundary must be free) and over a simulated network
with a straggler link, comparing write policies all/quorum/async and the
latency-weighted read policy — the quorum-vs-all tradeoff the paper
measures over a real network.

``run_serve`` is the serving pair (ISSUE 8): zero-copy KV-on-volumes
serving (``serving/engine.py`` with ``kv_backend="fused"`` — the extent
pool IS the KV cache) against the copy-based host baseline
(``kv_backend="host"``), reporting sessions/s, per-token wall P99 and the
engine step clock, plus the fork probe timing ``ServeEngine.fork`` at a
short vs a long context (``check_serve_gate`` pins zero-copy >= 1.0x
copy-based and the fork cost flat — O(1) in context length).

Also a CLI (the CI bench-smoke job, installed as ``repro-bench``):
``repro-bench --smoke --out BENCH.json --check`` runs a tiny-geometry
ladder + the mixed data+control workload + the VolumeManager blockdev
workload, writes the JSON artifact, and exits non-zero if
``+fused``/``+sharded``/``+ring`` fall below the device-resident ``+dbs``
baseline on any row, if ``+ring`` falls below ``+fused`` on the pure-data
rows, if in-band control loses to the fence-per-control-op baseline, or if
the byte API falls below 0.9x raw ``+ring`` on aligned spans
(see ``check_no_regression`` for why upstream is not the CPU-smoke floor).
``--only serve`` (or any comma-named section subset) runs just those
sections and their gates — the CI ``serve-smoke`` step.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Engine, EngineConfig, Request, UpstreamEngine
from repro.core.blockdev import VolumeManager
from repro.utils.compile_cache import place_compile_cache

COLUMNS = ("upstream", "+frontend", "+comm", "+dbs", "+fused", "+sharded",
           "+ring")
ROWS = ("frontend_only", "without_storage", "full_engine")


def make_engine(column: str, row: str, *, payload_shape=(64,),
                n_replicas: int = 2, page_blocks: int = 32,
                n_extents: int = 4096, max_pages: int = 1024,
                n_shards: int = 4, kernel: str = "auto"):
    null_backend = row == "frontend_only"
    null_storage = row == "without_storage"
    kw = dict(payload_shape=payload_shape, n_replicas=n_replicas,
              page_blocks=page_blocks, n_extents=n_extents,
              max_pages=max_pages, null_backend=null_backend,
              null_storage=null_storage, kernel=kernel)
    if column == "upstream":
        return UpstreamEngine(EngineConfig(**kw))
    if column == "+frontend":
        return Engine(EngineConfig(storage="chained", comm="loop", **kw))
    if column == "+comm":
        return Engine(EngineConfig(storage="chained", comm="slots", **kw))
    if column == "+dbs":
        return Engine(EngineConfig(storage="dbs", comm="slots", **kw))
    if column == "+fused":
        return Engine(EngineConfig(storage="dbs", comm="fused", **kw))
    if column == "+sharded":
        return Engine(EngineConfig(storage="dbs", comm="sharded",
                                   n_shards=n_shards, **kw))
    if column == "+ring":
        return Engine(EngineConfig(storage="dbs", comm="ring",
                                   n_shards=n_shards, **kw))
    raise ValueError(column)


def measure_engine(eng, *, n_requests: int, kind: str, pages: int,
                   n_volumes: int, payload: jnp.ndarray,
                   warmup: bool = True) -> float:
    """One timed steady-state drain -> ops/s. The single measurement
    protocol shared by the ladder columns and table3's shard sweep.

    ``warmup`` drains one full write batch and one read batch before the
    timed run so every batch-geometry program (including the read-only
    step variant) compiles outside the clock — the paper's fio numbers are
    steady-state too. The workload spreads requests round-robin over
    ``n_volumes`` volumes (a multi-tenant stream; on a sharded engine the
    volumes additionally hash across shards)."""
    vols = [eng.create_volume() for _ in range(n_volumes)]
    rng = np.random.default_rng(0)
    page_seq = rng.integers(0, pages, size=n_requests)
    if warmup:
        cap = getattr(eng.cfg, "batch", 64)
        for i in range(cap):
            eng.submit(Request(req_id=i, kind="write",
                               volume=vols[i % n_volumes],
                               page=i % pages, block=i % 8, payload=payload))
        for i in range(cap):
            eng.submit(Request(req_id=cap + i, kind="read",
                               volume=vols[i % n_volumes],
                               page=i % pages, block=i % 8))
        eng.drain()
        # an interleaved batch too: the ring engine compiles one program per
        # opcode-class signature, and a mixed read+write batch is its own
        for i in range(cap):
            eng.submit(Request(req_id=2 * cap + i,
                               kind="write" if i % 2 else "read",
                               volume=vols[i % n_volumes],
                               page=i % pages, block=i % 8, payload=payload))
        eng.drain()
        eng.completed = 0
    for i in range(n_requests):
        k = ("write" if (kind == "write" or (kind == "mixed" and i % 2))
             else "read")
        eng.submit(Request(req_id=i, kind=k, volume=vols[i % n_volumes],
                           page=int(page_seq[i]), block=i % 8,
                           payload=payload))
    t0 = time.perf_counter()
    done = eng.drain()
    dt = time.perf_counter() - t0
    assert done == n_requests, (done, n_requests)
    return n_requests / dt


def run_ladder(*, n_requests: int = 512, payload_elems: int = 64,
               kind: str = "mixed", pages: int = 256,
               repeats: int = 1, warmup: bool = True,
               n_volumes: int = 4, n_shards: int = 4
               ) -> Dict[str, Dict[str, float]]:
    """Returns best-of-``repeats`` ops/sec for every (column, row) cell
    (see ``measure_engine`` for the per-cell protocol)."""
    payload = jnp.ones((payload_elems,), jnp.float32)
    out: Dict[str, Dict[str, float]] = {}
    for col in COLUMNS:
        out[col] = {}
        for row in ROWS:
            out[col][row] = max(
                measure_engine(
                    make_engine(col, row, payload_shape=(payload_elems,),
                                max_pages=pages, n_shards=n_shards),
                    n_requests=n_requests, kind=kind, pages=pages,
                    n_volumes=n_volumes, payload=payload, warmup=warmup)
                for _ in range(repeats))
    return out


def _control_stream(n_requests: int, ctrl_every: int, pages: int,
                    n_volumes: int):
    """Deterministic mixed data+control op stream (~1/ctrl_every control
    ops, alternating snapshot/unmap — the paper's snapshot-heavy tenant)."""
    ops = []
    snap = True
    for i in range(n_requests):
        v = i % n_volumes
        if ctrl_every and i % ctrl_every == ctrl_every - 1:
            ops.append(("snapshot" if snap else "unmap", v, (i * 7) % pages))
            snap = not snap
        elif i % 2:
            ops.append(("write", v, i % pages))
        else:
            ops.append(("read", v, (i // 2) % pages))
    return ops


def run_mixed_control(*, n_requests: int = 512, ctrl_every: int = 20,
                      payload_elems: int = 64, pages: int = 256,
                      n_volumes: int = 4, repeats: int = 1,
                      **_ignored) -> Dict[str, float]:
    """The workload the ring protocol exists for: ~5% in-band control ops.

    ``+ring`` submits snapshot/unmap as opcode-tagged requests into the
    same stream as the data ops — they execute inside the jitted step,
    interleaved with foreground traffic. ``fence`` is the pre-ring
    behaviour: the ``+fused`` engine must drain (fence) the pipeline at
    every control op and dispatch it host-side. Both run one engine shard
    (the fused fence baseline has no shard axis) so the comparison isolates
    the protocol change. Returns best-of-``repeats`` ops/s per mode
    (control ops count as ops — both modes complete the identical op
    sequence)."""
    payload = jnp.ones((payload_elems,), jnp.float32)
    ops = _control_stream(n_requests, ctrl_every, pages, n_volumes)

    def measure(mode: str) -> float:
        eng = make_engine("+ring" if mode == "+ring" else "+fused",
                          "full_engine", payload_shape=(payload_elems,),
                          max_pages=pages, n_shards=1)
        vols = [eng.create_volume() for _ in range(n_volumes)]
        cap = getattr(eng.cfg, "batch", 64)
        for i in range(cap):                  # warm every program variant
            eng.submit(Request(req_id=i, kind="write" if i % 2 else "read",
                               volume=vols[i % n_volumes], page=i % pages,
                               block=i % 8, payload=payload))
        if mode == "+ring":
            eng.submit(Request(req_id=cap, kind="snapshot", volume=vols[0]))
            eng.submit(Request(req_id=cap + 1, kind="unmap",
                               volume=vols[0], page=0))
        else:
            eng.snapshot(vols[0])
            eng.unmap(vols[0], [0])
        eng.drain()
        eng.completed = 0
        t0 = time.perf_counter()
        if mode == "+ring":                   # in-band: one stream, one drain
            for i, (kind, v, page) in enumerate(ops):
                eng.submit(Request(
                    req_id=i, kind=kind, volume=vols[v], page=page,
                    block=i % 8, payload=payload if kind == "write" else None))
            done = eng.drain()
        else:                                 # fence per control op
            done = 0
            for i, (kind, v, page) in enumerate(ops):
                if kind in ("snapshot", "unmap"):
                    done += eng.drain()       # flush everything in flight
                    if kind == "snapshot":
                        eng.snapshot(vols[v])
                    else:
                        eng.unmap(vols[v], [page])
                    done += 1
                else:
                    eng.submit(Request(req_id=i, kind=kind, volume=vols[v],
                                       page=page, block=i % 8,
                                       payload=(payload if kind == "write"
                                                else None)))
            done += eng.drain()
        dt = time.perf_counter() - t0
        assert done == n_requests, (mode, done, n_requests)
        return n_requests / dt

    return {mode: max(measure(mode) for _ in range(repeats))
            for mode in ("+ring", "fence")}


def run_blockdev(*, n_requests: int = 512, payload_elems: int = 64,
                 pages: int = 256, n_volumes: int = 4, n_shards: int = 4,
                 repeats: int = 1, unaligned_every: int = 10,
                 **_ignored) -> Dict[str, float]:
    """The public-API workload: byte-addressed mixed-size I/O through
    ``VolumeManager`` (core/blockdev.py) on the ring backend.

    Three numbers, best-of-``repeats`` each, in BLOCK ops/s (one block = one
    SQE, so the aligned/raw numbers are the same unit as the ladder's):

    - ``aligned``  — page-aligned page-sized byte spans through the API
      ("aligned spans map straight onto batched page ops"): ONE
      ``pwrite``/``pread`` fans out to ``page_blocks`` SQEs that ride the
      engine's normal admission batches and complete on the pump's single
      CQ fetch,
    - ``mixed``    — mixed sizes (1 block / 4 blocks / 1 page) with
      ~1/``unaligned_every`` *unaligned* writes exercising the in-API
      read-modify-write path (user ops/s — an op may fan out to many SQEs),
    - ``raw_ring`` — the SAME SQE stream hand-rolled on request-level
      ``Engine`` submission, with equivalent end-to-end byte handling
      (payload encode on writes, payload decode on reads). This is the raw
      ``+ring`` reference the CI gate compares against: the API must keep
      aligned-span throughput >= 0.9x of it (``check_blockdev_gate``).
    """
    bb = payload_elems
    page_blocks = 32
    # enough page-span calls that one measurement outlasts shared-runner
    # scheduling spikes (each call is page_blocks SQEs)
    n_pages_ops = max(48, n_requests // page_blocks)  # API calls (page spans)
    n_blocks = n_pages_ops * page_blocks              # SQEs either way
    seq = [(i % n_volumes, (i // n_volumes) % (pages - 1))
           for i in range(n_pages_ops)]

    def aligned_round(api: bool):
        """Build a warmed manager and return one timed round as a thunk, so
        the api/raw rounds can be INTERLEAVED — a shared-runner scheduling
        spike then degrades both sides, not just one."""
        mgr = VolumeManager(backend="ring", n_shards=n_shards,
                            payload_elems=payload_elems, max_pages=pages,
                            n_extents=4096, max_volumes=16)
        vols = [mgr.create() for _ in range(n_volumes)]
        eng = mgr.engine
        page_bytes = mgr.page_bytes
        data = (bytes(range(256)) * ((page_bytes + 255) // 256))[:page_bytes]
        # warmup: compile every program this traffic shape needs
        for v in vols:
            v.write((pages - 1) * page_bytes, data)
            v.read((pages - 1) * page_bytes, page_bytes)
        mgr.flush()

        def one_round() -> float:
            eng.completed = 0
            t0 = time.perf_counter()
            if api:
                futs = []
                for i, (vi, p) in enumerate(seq):
                    if i % 2:
                        futs.append(vols[vi].pwrite(p * page_bytes, data))
                    else:
                        futs.append(vols[vi].pread(p * page_bytes,
                                                   page_bytes))
                mgr.flush()
                for f in futs:
                    f.result()                  # decode read payloads too
            else:
                reqs = []
                rid = 0
                for i, (vi, p) in enumerate(seq):
                    for blk in range(page_blocks):
                        kind = "write" if i % 2 else "read"
                        payload = (np.frombuffer(
                            data[blk * bb:(blk + 1) * bb], np.uint8)
                            .astype(np.float32) if i % 2 else None)
                        r = Request(req_id=rid, kind=kind,
                                    volume=vols[vi].vid, page=p, block=blk,
                                    payload=payload)
                        rid += 1
                        eng.submit(r)
                        reqs.append(r)
                eng.drain()
                for r in reqs:                  # equivalent byte decode
                    if r.kind == "read" and r.result is not None:
                        np.asarray(r.result).astype(np.uint8).tobytes()
            dt = time.perf_counter() - t0
            assert eng.completed >= n_blocks
            return n_blocks / dt
        return one_round

    def measure_mixed() -> float:
        mgr = VolumeManager(backend="ring", n_shards=n_shards,
                            payload_elems=payload_elems, max_pages=pages,
                            n_extents=4096, max_volumes=16)
        vols = [mgr.create() for _ in range(n_volumes)]
        page_bytes = mgr.page_bytes
        sizes = (bb, 4 * bb, page_bytes)
        for v in vols:                          # warm all program shapes
            v.write(0, b"w" * page_bytes)
            v.read(0, page_bytes)
            v.write(1, b"u" * bb)               # unaligned RMW shape
        mgr.flush()
        mgr.engine.completed = 0
        t0 = time.perf_counter()
        futs = []
        for i in range(n_requests):
            v = vols[i % n_volumes]
            size = sizes[i % len(sizes)]
            off = ((i // n_volumes) * page_bytes) % (mgr.capacity - 2 * size)
            if unaligned_every and i % unaligned_every == unaligned_every - 1:
                futs.append(v.pwrite(off + 3, b"u" * bb))   # unaligned RMW
            elif i % 2:
                futs.append(v.pwrite(off, b"m" * size))
            else:
                futs.append(v.pread(off, size))
        mgr.flush()
        for f in futs:
            f.result()
        dt = time.perf_counter() - t0
        return n_requests / dt

    api_round, raw_round = aligned_round(True), aligned_round(False)
    aligned = raw = 0.0
    for _ in range(max(repeats, 5)):            # interleaved best-of
        aligned = max(aligned, api_round())
        raw = max(raw, raw_round())
    return {"aligned": aligned, "raw_ring": raw,
            "mixed": max(measure_mixed() for _ in range(repeats))}


def run_replication(*, n_requests: int = 512, payload_elems: int = 64,
                    pages: int = 256, n_volumes: int = 4, repeats: int = 1,
                    straggler: int = 6, kind: str = "mixed", **_ignored
                    ) -> Dict[str, Dict[str, float]]:
    """The replica-transport/policy matrix (ISSUE 5): the host-dispatch
    (+dbs, ``comm="slots"``) engine over each controller<->replica
    transport and write/read policy (core/transport.py,
    core/replication.py). Best-of-``repeats`` ops/s per cell.

    - ``local/all`` — the redesigned default: LocalTransport,
      write-to-all. Measured on BOTH pure-data rows with the ladder's
      default 2 replicas so it is the exact configuration of the ``+dbs``
      column — the CI gate pins it to >= 0.9x that column
      (``check_replication_gate``): the transport boundary is allowed a
      message object, not a slow path.
    - ``simnet/*`` — the policy matrix the paper measures over a real
      network, on a simulated one: 3 replicas, one ``straggler``x-slower
      link (``latency=[1, 1, straggler]``). ``all`` waits for the
      straggler every batch; ``quorum`` acks on the two fast links (the
      straggler catches up via per-link FIFO, bounded by the in-flight
      window); ``async`` is write-behind; ``quorum+latreads`` adds the
      latency-weighted read policy so reads also avoid the slow link —
      the quorum-vs-all tradeoff, benchmarkable.
    """
    payload = jnp.ones((payload_elems,), jnp.float32)
    simnet = dict(transport="simnet",
                  transport_opts=dict(latency=[1, 1, straggler], window=8))
    scenarios = {
        "local/all": dict(n_replicas=2),
        "simnet/all": dict(n_replicas=3, write_policy="all", **simnet),
        "simnet/quorum": dict(n_replicas=3, write_policy="quorum", **simnet),
        "simnet/async": dict(n_replicas=3, write_policy="async", **simnet),
        "simnet/quorum+latreads": dict(n_replicas=3, write_policy="quorum",
                                       read_policy="latency", **simnet),
    }
    out: Dict[str, Dict[str, float]] = {}
    for name, kw in scenarios.items():
        rows = (("full_engine", "without_storage") if name == "local/all"
                else ("full_engine",))
        out[name] = {}

        def make(row: str):
            # geometry mirrors make_engine's, so the local/all cells are
            # the exact configuration of the +dbs column the gate compares
            # against (and the same --kind workload drives both)
            return Engine(EngineConfig(
                storage="dbs", comm="slots", n_extents=4096,
                payload_shape=(payload_elems,), max_pages=pages,
                null_storage=row == "without_storage", **kw))

        for row in rows:
            out[name][row] = max(
                measure_engine(make(row), n_requests=n_requests, kind=kind,
                               pages=pages, n_volumes=n_volumes,
                               payload=payload)
                for _ in range(repeats))
        # the metric the policies actually trade: controller-observed wait
        # time in SIMULATED ticks per op (deterministic — no repeats).
        # Wall ops/s barely separates the policies because ticking a
        # simulated link costs the host ~nothing; a real network charges
        # the latency the tick count stands in for.
        eng = make("full_engine")
        measure_engine(eng, n_requests=n_requests, kind=kind, pages=pages,
                       n_volumes=n_volumes, payload=payload, warmup=False)
        out[name]["wait_ticks_per_op"] = (eng.backend.wait_ticks
                                          / n_requests)
    return out


def run_trace(*, smoke: bool = False, trace_seed: int = 0,
              chaos_seed: int = 0, **_ignored) -> Dict[str, Any]:
    """The chaos-harness scenario matrix (ISSUE 6): trace-driven load with
    byte-oracle checking over the named ``repro.harness.SCENARIOS`` catalog,
    plus the replay-determinism double run. Returns the BENCH ``trace``
    document; ``check_trace_gates`` (re-exported from the harness) gates
    it under ``--check``."""
    from repro.harness import run_matrix
    return run_matrix(smoke=smoke, trace_seed=trace_seed,
                      chaos_seed=chaos_seed)


def check_trace_gates(trace: Dict[str, Any]) -> List[str]:
    from repro.harness import check_trace_gates as _gates
    return _gates(trace)


def run_kernels(*, repeats: int = 3, **_ignored) -> Dict[str, Any]:
    """The per-DBS-kernel micro benchmark (ISSUE 7): for every REGISTERED
    kernel (kernels/dbs registry), wall time + nominal achieved bytes/s for
    the write and read data planes of one engine-shaped batch (CoW lanes,
    a duplicate-dst write group, failed lanes, read holes), a bit-identity
    check against the ``xla`` reference, and — on compiled backends only —
    the ``+fused`` full_engine row rerun with ``kernel="pallas"`` vs
    ``kernel="xla"`` (the perf half of ``check_kernel_gate``;
    interpret-mode Pallas wall times measure the interpreter, not the
    kernel, so that ratio is only taken where the kernel compiles).
    Lands in BENCH json under ``kernels``; ``benchmarks/roofline.py``
    renders achieved-vs-peak bytes/s from it. Shares of a device peak are
    only recorded where the kernels run compiled: a CPU wall time is not a
    device number."""
    from repro.core import dbs
    from repro.kernels.dbs import (dbs_read_bytes, dbs_write_bytes,
                                   make_kernel)
    from repro.kernels.dbs.registry import available_kernels
    from repro.kernels.platform import default_interpret
    from repro.utils.machine import machine_profile

    on_chip = not default_interpret()
    prof = machine_profile() if on_chip else None
    e, page, d, b = 129, 8, 32, 32          # +1 reserved scratch row
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    pool = jax.random.normal(ks[0], (e, page, d))
    payload = jax.random.normal(ks[1], (b, d))
    lane = jnp.arange(b, dtype=jnp.int32)
    blocks = (lane * 3) % page
    # duplicate-dst groups: lane 8k+5 joins lane 8k+4's extent (the leader,
    # which also CoWs — cow_src sits on the group's first live lane, the
    # write_pages convention the kernels' routing assumes)
    dst = jnp.where(lane % 8 == 5, lane - 1, lane) * 3 % (e - 1)
    cow_src = jnp.where(lane % 8 == 4, (dst + 61) % (e - 1), -1)
    cow_src = cow_src.astype(jnp.int32)
    ok = lane % 11 != 10
    ext = jnp.where(lane % 5 == 0, -1, dst).astype(jnp.int32)  # read holes
    itemsize = pool.dtype.itemsize
    wbytes = dbs_write_bytes(int(ok.sum()),
                             int(((cow_src >= 0) & ok).sum()),
                             page, d, itemsize)
    rbytes = dbs_read_bytes(b, d, itemsize)

    def _time(fn, *args):
        fn(*args).block_until_ready()       # compile outside the clock
        t0 = time.perf_counter()
        for _ in range(repeats):
            fn(*args).block_until_ready()
        return (time.perf_counter() - t0) / repeats * 1e6

    xla = make_kernel("xla")
    ref_w = xla.write(pool, dbs.WriteOps(dst=dst, cow_src=cow_src, ok=ok),
                      payload, blocks)
    ref_r = xla.read(pool, ext, blocks)
    out: Dict[str, Any] = {"profile": prof.to_dict() if prof else None}
    for name in available_kernels():
        kern = make_kernel(name)
        wf = jax.jit(lambda p, pay, dd, cc, oo, bl, k=kern: k.write(
            p, dbs.WriteOps(dst=dd, cow_src=cc, ok=oo), pay, bl))
        rf = jax.jit(lambda p, ee, bl, k=kern: k.read(p, ee, bl))
        got_w = wf(pool, payload, dst, cow_src, ok, blocks)
        got_r = rf(pool, ext, blocks)
        identical = bool(
            np.array_equal(np.asarray(got_w[:e - 1]),      # excl. dump row
                           np.asarray(ref_w[:e - 1]))
            and np.array_equal(np.asarray(got_r), np.asarray(ref_r)))
        w_us = _time(wf, pool, payload, dst, cow_src, ok, blocks)
        r_us = _time(rf, pool, ext, blocks)
        out[name] = {
            "write_us": w_us, "read_us": r_us,
            "write_bytes_per_s": wbytes / (w_us * 1e-6),
            "read_bytes_per_s": rbytes / (r_us * 1e-6),
            "write_vs_peak": (wbytes / (w_us * 1e-6) / prof.hbm_bw
                              if prof else None),
            "read_vs_peak": (rbytes / (r_us * 1e-6) / prof.hbm_bw
                             if prof else None),
            "identical": identical,
        }
    if on_chip:                             # the compiled-only perf ratio
        pay = jnp.ones((16,), jnp.float32)
        for kname in ("pallas", "xla"):
            eng = make_engine("+fused", "full_engine", payload_shape=(16,),
                              max_pages=128, n_extents=512, kernel=kname)
            out[f"fused_{kname}_ops_s"] = measure_engine(
                eng, n_requests=512, kind="mixed", pages=64, n_volumes=4,
                payload=pay)
    return out


def check_kernel_gate(kernels: Dict[str, Any],
                      floor: float = 0.9) -> List[str]:
    """The all-Pallas-hot-path gate (ISSUE 7 acceptance): every registered
    DBS kernel must be bit-identical to the ``xla`` reference on the
    crafted engine batch, and on compiled backends the ``+fused`` row with
    ``kernel="pallas"`` must hold >= ``floor``x the ``kernel="xla"`` run —
    kernel ownership buys lowering quality, not overhead."""
    problems = []
    for name, row in kernels.items():
        if isinstance(row, dict) and "identical" in row \
                and not row["identical"]:
            problems.append(
                f"kernel {name}: NOT bit-identical to the xla reference")
    if "fused_pallas_ops_s" in kernels:
        p, x = kernels["fused_pallas_ops_s"], kernels["fused_xla_ops_s"]
        if p < x * floor:
            problems.append(
                f"kernel pallas: +fused {p:.0f} ops/s < {floor:g}x "
                f"xla ({x:.0f} ops/s)")
    return problems


def check_replication_gate(repl: Dict[str, Dict[str, float]],
                           ladder: Dict[str, Dict[str, float]],
                           floor: float = 0.9) -> List[str]:
    """The transport-redesign gate (ISSUE 5 acceptance): ``local/all`` —
    the redesigned replica path — must hold >= ``floor``x the ``+dbs``
    column (the identical engine configuration) on the pure-data rows. The
    boundary buys pluggability, not overhead."""
    problems = []
    for row in ("full_engine", "without_storage"):
        ops, base = repl["local/all"][row], ladder["+dbs"][row]
        if ops < base * floor:
            problems.append(
                f"replication local/all/{row}: {ops:.0f} ops/s < {floor:g}x "
                f"+dbs ({base:.0f} ops/s)")
    return problems


def check_blockdev_gate(blockdev: Dict[str, float],
                        floor: float = 0.9) -> List[str]:
    """The public-API gate (ISSUE 4 acceptance): byte-addressed aligned
    spans through ``VolumeManager`` must hold >= ``floor``x the raw
    request-level ``+ring`` throughput on the identical op stream — the
    ublk-style surface is allowed geometry translation, not host hops."""
    if blockdev["aligned"] < blockdev["raw_ring"] * floor:
        return [f"blockdev: aligned {blockdev['aligned']:.0f} ops/s < "
                f"{floor:g}x raw +ring ({blockdev['raw_ring']:.0f} ops/s)"]
    return []


def snapshot_degradation(*, n_snapshots=(0, 4, 16, 64), n_reads: int = 256,
                         pages: int = 64) -> Dict[str, List[dict]]:
    """Reads vs snapshot count. Two metrics per point:

    - ops/s (wall time; at CPU scale dict walks are ~ns, so this mostly
      shows engine overheads),
    - **layers touched per read** — the structural cost the paper describes
      ("reads may have to go through the whole chain"): grows linearly for
      the chained sparse-file-style store, constant 1 for DBS's flattened
      in-memory extent map.
    All data is written *before* the first snapshot, so chained reads must
    walk to the bottom of the chain — the paper's worst case.
    """
    res: Dict[str, List[dict]] = {"chained": [], "dbs": []}
    payload = jnp.ones((16,), jnp.float32)
    rng = np.random.default_rng(0)
    for col, key in (("+comm", "chained"), ("+dbs", "dbs")):
        for ns in n_snapshots:
            eng = make_engine(col, "full_engine", payload_shape=(16,),
                              max_pages=pages, n_extents=pages * (ns + 2) + 64)
            vol = eng.create_volume()
            for p in range(pages):              # base data in the oldest layer
                eng.submit(Request(req_id=p, kind="write", volume=vol,
                                   page=p, block=0, payload=payload))
            eng.drain()
            for s in range(ns):                 # empty-ish newer layers
                eng.snapshot(vol)
                eng.submit(Request(req_id=0, kind="write", volume=vol,
                                   page=0, block=0, payload=payload))
                eng.drain()
            for i in range(n_reads):
                eng.submit(Request(req_id=i, kind="read", volume=vol,
                                   page=int(rng.integers(1, pages)), block=0))
            t0 = time.perf_counter()
            done = eng.drain()
            dt = time.perf_counter() - t0
            if key == "chained":
                store = eng.backend.stores[0]
                walked = sum(s.layers_walked for s in eng.backend.stores)
                nreads = sum(s.reads for s in eng.backend.stores)
                depth = walked / max(nreads, 1)
            else:
                depth = 1.0                     # one table gather, always
            res[key].append({"snapshots": ns, "ops_per_s": done / dt,
                             "layers_per_read": depth})
    return res


def run_serve(*, smoke: bool = False, n_sessions: int = 16, max_new: int = 8,
              repeats: int = 2, **_ignored) -> Dict[str, Any]:
    """Serving throughput (PR 8): zero-copy KV-on-volumes
    (``kv_backend="fused"`` — extent pool IS the cache, one fused decode
    program) vs the copy-based baseline (``kv_backend="host"`` — model-owned
    pools, per-layer ``dbs_copy`` CoW, unfused step).

    Two clocks per backend: wall-clock (sessions/s, per-token P99 seconds)
    and the engine step clock (per-session steps to completion) — both
    through ``harness.stats.summarize``. Plus the fork-O(1) probe: the cost
    of ``ServeEngine.fork`` at a short vs a long context must be flat
    (``check_serve_gate``). Returns the BENCH ``serve`` document."""
    from repro.configs import smoke_config
    from repro.harness.stats import summarize
    from repro.models import init_params
    from repro.serving import GenRequest, ServeEngine

    cfg = smoke_config("granite-3-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    if smoke:
        n_sessions, max_new = min(n_sessions, 10), min(max_new, 6)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(6 + (i % 5),))
               for i in range(n_sessions)]

    def _measure(kv_backend: str) -> Dict[str, Any]:
        best = None
        for _ in range(max(repeats, 1)):
            eng = ServeEngine(cfg, params, n_slots=8, max_len=64,
                              kv_backend=kv_backend)
            # warm the engine's compile caches outside the timed window
            eng.submit(GenRequest(req_id=10 ** 6, prompt=prompts[0].copy(),
                                  max_new=2))
            eng.run(max_steps=8)
            t0 = time.perf_counter()
            for rid in range(n_sessions):
                eng.submit(GenRequest(req_id=rid, prompt=prompts[rid].copy(),
                                      max_new=max_new))
            token_wall: List[float] = []
            done_steps: Dict[int, int] = {}
            for _step in range(64 * n_sessions):
                ts = time.perf_counter()
                out = eng.step()
                dt = time.perf_counter() - ts
                token_wall.extend(dt for _ in out)
                for rid, _tok in out:
                    if eng.live[rid].done and rid not in done_steps:
                        done_steps[rid] = eng._steps
                if len(done_steps) == n_sessions:
                    break
            total = time.perf_counter() - t0
            doc = {"sessions_per_s": n_sessions / total,
                   "tokens_per_s": len(token_wall) / total,
                   "token_wall_s": summarize(token_wall),
                   "session_steps": summarize(list(done_steps.values()))}
            if best is None or doc["sessions_per_s"] > best["sessions_per_s"]:
                best = doc
        return best

    def _fork_cost(ctx_len: int, k: int = 5) -> float:
        eng = ServeEngine(cfg, params, n_slots=4, max_len=128,
                          kv_backend="fused")
        eng.submit(GenRequest(req_id=0,
                              prompt=rng.integers(0, cfg.vocab_size,
                                                  size=(ctx_len,)),
                              max_new=64))
        eng.step()
        times = []
        for i in range(k):
            t0 = time.perf_counter()
            child = eng.fork(0, 100 + i, max_new=1)
            times.append(time.perf_counter() - t0)
            eng._finish(child)
        return min(times)

    short_ctx, long_ctx = 8, 96
    cost_short = _fork_cost(short_ctx)
    cost_long = _fork_cost(long_ctx)
    return {"n_sessions": n_sessions, "max_new": max_new,
            "zero_copy": _measure("fused"),
            "copy_based": _measure("host"),
            "fork": {"short_ctx": short_ctx, "long_ctx": long_ctx,
                     "cost_short_s": cost_short, "cost_long_s": cost_long,
                     "ctx_ratio": long_ctx / short_ctx,
                     "cost_ratio": cost_long / max(cost_short, 1e-9)}}


def _np_checksum(buf: bytes, page_bytes: int) -> int:
    """Vectorized-numpy host checksum over read-back bytes — the strongest
    practical read-back-and-compute baseline (same spec as the in-band
    ``checksum`` storage function; repro/compute/functions.py)."""
    a = (np.frombuffer(buf, np.uint8).astype(np.uint32)
         .reshape(-1, page_bytes) + np.uint32(1))
    j = np.arange(page_bytes, dtype=np.uint32) % np.uint32(31)
    rot = (a << j) | (a >> ((np.uint32(32) - j) % np.uint32(32)))
    psums = np.bitwise_xor.reduce(rot, axis=1)
    p = np.arange(psums.shape[0], dtype=np.uint32) % np.uint32(31)
    rot2 = (psums << p) | (psums >> ((np.uint32(32) - p) % np.uint32(32)))
    total = int(np.bitwise_xor.reduce(rot2))
    return total - (1 << 32) if total >= (1 << 31) else total


def run_compute(*, payload_elems: int = 64, pages: int = 256,
                n_volumes: int = 4, n_shards: int = 4, repeats: int = 1,
                **_ignored) -> Dict[str, Any]:
    """Computational storage (ISSUE 9): the in-band volume scan — ONE
    ``COMPUTE`` SQE running the ``checksum`` storage function inside the
    ring step — against the read-back baseline: ``pread`` the full volume
    through the same API (full SQE fan-out) and checksum the bytes on the
    host with vectorized numpy. Both sides run on the SAME manager and
    data, interleaved best-of-``repeats``; both results are checked
    bit-identical to the registry entry's pure-Python mirror. Lands in
    BENCH json under ``compute``; ``check_compute_gate`` pins in-band to
    >= 2x read-back and bit-identity."""
    from repro.compute import make_storage_fn

    nv = min(n_volumes, 4)                  # full-capacity reads are the
    mgr = VolumeManager(backend="ring", n_shards=n_shards,  # baseline cost
                        payload_elems=payload_elems, max_pages=pages,
                        n_extents=4 * pages * nv, max_volumes=16)
    vols = [mgr.create() for _ in range(nv)]
    cap, pby = mgr.capacity, mgr.page_bytes
    blobs = {}
    for k, v in enumerate(vols):
        blobs[v.vid] = bytes((k * 37 + i * 11) % 251 for i in range(cap))
        v.write(0, blobs[v.vid])
    mgr.flush()
    entry = make_storage_fn("checksum")
    expected = {v.vid: entry.mirror(bytearray(blobs[v.vid]), pby,
                                    mgr.block_bytes, 0, cap // pby, 0,
                                    None)[0]
                for v in vols}

    def in_band_round():
        t0 = time.perf_counter()
        futs = [(v.vid, v.compute("checksum")) for v in vols]
        mgr.flush()
        vals = {vid: f.result().value for vid, f in futs}
        return time.perf_counter() - t0, vals

    def read_back_round():
        t0 = time.perf_counter()
        futs = [(v.vid, v.pread(0, cap)) for v in vols]
        mgr.flush()
        vals = {vid: _np_checksum(f.result(), pby) for vid, f in futs}
        return time.perf_counter() - t0, vals

    # warm both program shapes outside the clock
    in_band_round(), read_back_round()
    identical = True
    t_in = t_back = float("inf")
    for _ in range(max(repeats, 3)):        # interleaved best-of
        dt, vals = in_band_round()
        t_in = min(t_in, dt)
        identical &= vals == expected
        dt, vals = read_back_round()
        t_back = min(t_back, dt)
        identical &= vals == expected
    scanned = nv * cap
    return {"volumes": nv, "capacity_bytes": cap,
            "in_band_scans_per_s": nv / t_in,
            "read_back_scans_per_s": nv / t_back,
            "in_band_bytes_per_s": scanned / t_in,
            "read_back_bytes_per_s": scanned / t_back,
            "speedup": t_back / t_in, "identical": identical}


def check_compute_gate(compute: Dict[str, Any],
                       floor: float = 2.0) -> List[str]:
    """The computational-storage gate (ISSUE 9 acceptance): the in-band
    volume scan must be bit-identical to the host reference AND hold
    >= ``floor``x the read-back-and-compute-on-host baseline — pushing the
    function to the data is only worth an opcode if it beats shipping the
    bytes."""
    problems = []
    if not compute["identical"]:
        problems.append("compute: in-band/read-back checksum NOT "
                        "bit-identical to the host reference mirror")
    ib, rb = compute["in_band_bytes_per_s"], compute["read_back_bytes_per_s"]
    if ib < rb * floor:
        problems.append(
            f"compute: in-band volume scan {ib:.3g} B/s < {floor:g}x "
            f"read-back baseline ({rb:.3g} B/s)")
    return problems


def run_durability(*, payload_elems: int = 64, pages: int = 64,
                   n_requests: int = 512, repeats: int = 1,
                   **_ignored) -> Dict[str, Any]:
    """Durability subsystem (ISSUE 10), three measurements on the fused
    engine:

    (a) **journal overhead** — the same aligned-block write stream with
        the write-ahead journal attached vs detached (interleaved
        best-of-``repeats``). Group commit makes the bound ONE file append
        per pump, not per op, so the attached column must hold the
        ``check_durability_gate`` floor (<= 30% overhead).
    (b) **crash recovery** — after the journaled run the manager is
        ABANDONED (never closed — a dead process) and recovered from the
        WAL; the recovered volume must read back byte-identical to the
        original (the gate's correctness half).
    (c) **spill-tier read throughput** — full-volume reads with the extent
        pool 2x over-subscribed (``tier=`` budget at half the mapped
        extents, spill/fill cycles every round) vs the all-resident pool;
        reported as bytes/s + the achieved ratio.
    """
    import shutil
    import tempfile

    from repro.durability import recover

    tmp = tempfile.mkdtemp(prefix="repro-durability-bench-")
    geo = dict(backend="fused", payload_elems=payload_elems, page_blocks=4,
               max_pages=pages, n_extents=4 * pages, max_volumes=8,
               batch=32)
    burst = 32
    payloads = [bytes((k * 31 + i) % 251 for i in range(payload_elems))
                for k in range(burst)]

    def write_stream(mgr, vid, n_blocks):
        t0 = time.perf_counter()
        for i in range(n_requests):
            mgr.pwrite(vid, ((i * 7919) % n_blocks) * payload_elems,
                       payloads[i % burst])
            if (i + 1) % burst == 0:
                mgr.flush()
        mgr.flush(durable=True)
        return time.perf_counter() - t0

    try:
        jp = f"{tmp}/wal.dbsj"
        mgr_on = VolumeManager(journal=jp, **geo)
        mgr_off = VolumeManager(**geo)
        cap = mgr_on.capacity
        n_blocks = cap // payload_elems
        vid_on = mgr_on.create().vid
        vid_off = mgr_off.create().vid
        write_stream(mgr_on, vid_on, n_blocks)      # warm both programs
        write_stream(mgr_off, vid_off, n_blocks)
        t_on = t_off = float("inf")
        for _ in range(max(repeats, 3)):            # interleaved best-of
            t_on = min(t_on, write_stream(mgr_on, vid_on, n_blocks))
            t_off = min(t_off, write_stream(mgr_off, vid_off, n_blocks))
        want = mgr_on.open(vid_on).read(0, cap)
        mgr_off.close()
        del mgr_on                                  # crash: abandoned
        mgr_rec = recover(jp, **geo)
        got = mgr_rec.open(vid_on).read(0, cap)
        rec_info = dict(mgr_rec.recovery_info)
        rec_info.pop("installed", None)
        mgr_rec.close()

        def read_tput(tier):
            kwt = dict(geo, **({} if tier is None else {"tier": tier}))
            m = VolumeManager(**kwt)
            vids = [m.create().vid for _ in range(2)]
            pby = m.page_bytes
            for v in vids:                          # map 2 x pages extents
                for p in range(pages):
                    m.pwrite(v, p * pby, payloads[p % burst] * 4)
            m.flush()
            best = float("inf")
            for _ in range(max(repeats, 3)):
                t0 = time.perf_counter()
                for v in vids:
                    m.open(v).read(0, cap)
                best = min(best, time.perf_counter() - t0)
            spills = (m.stats()["tier"]["extents_spilled"]
                      if tier is not None else 0)
            m.close()
            return 2 * cap / best, spills

        resident_bps, _ = read_tput(None)
        tiered_bps, spilled = read_tput(pages)      # budget = half the map
        return {
            "journal_on_ops_per_s": n_requests / t_on,
            "journal_off_ops_per_s": n_requests / t_off,
            "journal_overhead": t_on / t_off - 1.0,
            "recovered_identical": got == want,
            "recovery": rec_info,
            "tier_read_bytes_per_s": tiered_bps,
            "resident_read_bytes_per_s": resident_bps,
            "tier_read_ratio": tiered_bps / resident_bps,
            "tier_extents_spilled": spilled,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def check_durability_gate(durability: Dict[str, Any],
                          floor: float = 0.77) -> List[str]:
    """ISSUE 10 acceptance: recovery is byte-identical, and the write-ahead
    journal costs at most 30% of the unjournaled write stream (group
    commit: one append per pump — a per-op fsync would fail this)."""
    problems = []
    if not durability["recovered_identical"]:
        problems.append("durability: WAL recovery is NOT byte-identical "
                        "to the crashed manager's volume")
    on = durability["journal_on_ops_per_s"]
    off = durability["journal_off_ops_per_s"]
    if on < off * floor:
        problems.append(
            f"durability: journaled writes {on:.0f} ops/s < {floor:g}x "
            f"unjournaled ({off:.0f} ops/s) — journal overhead "
            f"{durability['journal_overhead'] * 100:.0f}% exceeds "
            f"{(1 - floor) / floor * 100:.0f}%")
    if durability["tier_extents_spilled"] <= 0:
        problems.append("durability: spill-tier bench never spilled — the "
                        "2x over-subscription did not exercise the tier")
    return problems


def check_serve_gate(serve: Dict[str, Any], floor: float = 1.0,
                     fork_flat: float = 4.0) -> List[str]:
    """PR 8 acceptance: zero-copy serving holds >= ``floor``x the
    copy-based baseline's sessions/s, and fork cost stays flat in context
    length (a 12x longer context may cost at most ``fork_flat``x — noise
    margin on an O(1) operation, far below the 12x an O(context) copy
    would show)."""
    problems = []
    zc = serve["zero_copy"]["sessions_per_s"]
    cb = serve["copy_based"]["sessions_per_s"]
    if zc < cb * floor:
        problems.append(f"serve: zero-copy {zc:.2f} sessions/s < {floor:g}x "
                        f"copy-based ({cb:.2f} sessions/s)")
    fork = serve["fork"]
    if fork["cost_ratio"] > fork_flat:
        problems.append(
            f"serve: fork cost ratio {fork['cost_ratio']:.2f} at "
            f"{fork['ctx_ratio']:.0f}x context exceeds {fork_flat:g} "
            "(fork must be O(1) in context length)")
    return problems


# ---------------------------------------------------------------------------
# CLI — the CI bench-smoke job (and quick local runs)
# ---------------------------------------------------------------------------
# repeats=3 (best-of): shared CI runners inject multi-ms scheduling spikes;
# max-over-repeats recovers the machine-limited number per cell
SMOKE = dict(n_requests=512, payload_elems=16, pages=64, n_volumes=8,
             n_shards=4, repeats=3)


def check_no_regression(ladder: Dict[str, Dict[str, float]],
                        columns=("+fused", "+sharded", "+ring"),
                        baseline: str = "+dbs",
                        floor: float = 0.7) -> List[str]:
    """The fused/sharded columns must not collapse below the device-resident
    baseline column (``+dbs``, the pre-fused engine) on any row — the floor
    the CI bench job enforces per run.

    Why not the ``upstream`` column: at smoke geometry on a CPU runner the
    upstream baseline is a pure-Python dict loop with no device dispatch at
    all, so it outruns every device-resident column by construction (there
    is no real storage medium to dominate the clock, the situation the
    paper measures). Regressions in the columns this repo *adds* show up as
    losing to ``+dbs`` within one run; ``floor`` leaves margin for shared-
    runner noise (cross-run absolute numbers are meaningless there).
    """
    problems = []
    for col in columns:
        for row, ops in ladder.get(col, {}).items():
            base = ladder[baseline][row] * floor
            if ops < base:
                problems.append(
                    f"{col}/{row}: {ops:.0f} ops/s < {floor:g}x "
                    f"{baseline} ({ladder[baseline][row]:.0f} ops/s)")
    return problems


def check_ring_gates(ladder: Dict[str, Dict[str, float]],
                     mixed: Optional[Dict[str, float]] = None,
                     floor: float = 0.7) -> List[str]:
    """The ring column's two contracts (ISSUE 3 acceptance):

    - pure-data rows: ``+ring`` holds the ``+fused`` column (the SQ/CQ
      protocol must not tax the data path it generalizes),
    - the mixed data+control workload: in-band control beats the
      fence-per-control-op baseline.

    ``floor`` leaves shared-runner noise margin within one run.
    """
    problems = check_no_regression(ladder, columns=("+ring",),
                                   baseline="+fused", floor=floor)
    if mixed is not None and mixed["+ring"] < mixed["fence"] * floor:
        problems.append(
            f"mixed_control: +ring {mixed['+ring']:.0f} ops/s < {floor:g}x "
            f"fence baseline ({mixed['fence']:.0f} ops/s)")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometry (CI per-PR run)")
    ap.add_argument("--kind", default="mixed",
                    choices=("mixed", "read", "write"))
    ap.add_argument("--n-requests", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="write the ladder as JSON (the CI artifact)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if +fused/+sharded regress below the "
                         "+dbs baseline (see check_no_regression)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of sections to run "
                         "(ladder,mixed,blockdev,replication,trace,"
                         "kernels,serve,compute,durability); default runs "
                         "everything")
    args = ap.parse_args(argv)
    place_compile_cache()

    sections = ("ladder", "mixed", "blockdev", "replication", "trace",
                "kernels", "serve", "compute", "durability")
    if args.only is None:
        want = set(sections)
    else:
        want = {s.strip() for s in args.only.split(",") if s.strip()}
        unknown = want - set(sections)
        if unknown:
            ap.error(f"--only: unknown sections {sorted(unknown)}")

    kw = dict(SMOKE) if args.smoke else {}
    if args.n_requests is not None:
        kw["n_requests"] = args.n_requests
    ladder = run_ladder(kind=args.kind, **kw) if "ladder" in want else None
    mixed = run_mixed_control(**kw) if "mixed" in want else None
    blockdev = run_blockdev(**kw) if "blockdev" in want else None
    replication = (run_replication(kind=args.kind, **kw)
                   if "replication" in want else None)
    trace = run_trace(smoke=bool(args.smoke)) if "trace" in want else None
    kernels = run_kernels(**kw) if "kernels" in want else None
    serve = run_serve(smoke=bool(args.smoke), **kw) if "serve" in want else None
    compute = run_compute(**kw) if "compute" in want else None
    durability = run_durability(**kw) if "durability" in want else None

    if ladder is not None:
        width = max(len(c) for c in COLUMNS) + 2
        print("row".ljust(18) + "".join(c.rjust(width) for c in COLUMNS))
        for row in ROWS:
            cells = "".join(f"{ladder[c][row]:{width}.0f}" for c in COLUMNS)
            print(row.ljust(18) + cells + "   ops/s")
    if mixed is not None:
        print("mixed data+control (~5% snapshot/unmap): "
              f"+ring {mixed['+ring']:.0f} ops/s vs fence-per-control-op "
              f"{mixed['fence']:.0f} ops/s")
    if blockdev is not None:
        print("blockdev (byte-addressed VolumeManager, ring backend): "
              f"aligned {blockdev['aligned']:.0f} ops/s vs raw +ring "
              f"{blockdev['raw_ring']:.0f} ops/s; mixed-size ~10% unaligned "
              f"{blockdev['mixed']:.0f} ops/s")
    if replication is not None:
        repl_cells = "  ".join(
            f"{name} {rows['full_engine']:.0f}ops/s"
            f"/{rows['wait_ticks_per_op']:.2f}tk"
            for name, rows in replication.items())
        print("replication transports/policies (slots engine, full_engine, "
              "simnet straggler link; ops/s wall + controller wait "
              f"ticks/op): {repl_cells}")
    if trace is not None:
        det = trace.get("determinism", {})
        trace_cells = "  ".join(
            f"{name} ok={doc['oracle_ok']}"
            f"/p99={doc['latency']['all']['p99']:g}tk"
            for name, doc in trace.items() if name != "determinism")
        print("chaos harness (trace-driven load + fault schedule, byte "
              f"oracle; per-scenario oracle verdict + pump-tick P99): "
              f"{trace_cells}  determinism match={det.get('match')}")
    if kernels is not None:
        kern_cells = "  ".join(
            f"{name} w={row['write_bytes_per_s']:.3g}B/s "
            f"r={row['read_bytes_per_s']:.3g}B/s ok={row['identical']}"
            for name, row in kernels.items()
            if isinstance(row, dict) and "write_us" in row)
        prof = kernels["profile"]
        print("dbs kernels (registry; nominal achieved bytes/s + "
              "bit-identity vs the xla reference; profile "
              f"{prof['name'] if prof else 'none: interpret mode'}): "
              f"{kern_cells}")
    if serve is not None:
        print("serving (zero-copy KV-on-volumes vs copy-based host "
              "baseline; sessions/s + per-token wall P99): zero-copy "
              f"{serve['zero_copy']['sessions_per_s']:.2f}sess/s"
              f"/p99={serve['zero_copy']['token_wall_s']['p99']:.4f}s  "
              f"copy-based {serve['copy_based']['sessions_per_s']:.2f}sess/s"
              f"/p99={serve['copy_based']['token_wall_s']['p99']:.4f}s  "
              f"fork x{serve['fork']['ctx_ratio']:.0f}ctx cost ratio "
              f"{serve['fork']['cost_ratio']:.2f}")
    if compute is not None:
        print("computational storage (in-band checksum volume scan vs "
              "read-back + host numpy): in-band "
              f"{compute['in_band_bytes_per_s']:.3g} B/s vs read-back "
              f"{compute['read_back_bytes_per_s']:.3g} B/s "
              f"(x{compute['speedup']:.1f}); bit-identical to the mirror: "
              f"{compute['identical']}")
    if durability is not None:
        print("durability (write-ahead journal + WAL recovery + spill "
              "tier): journaled "
              f"{durability['journal_on_ops_per_s']:.0f} ops/s vs "
              f"unjournaled {durability['journal_off_ops_per_s']:.0f} "
              f"ops/s ({durability['journal_overhead'] * 100:+.0f}%); "
              "recovered byte-identical: "
              f"{durability['recovered_identical']}; tiered reads at 2x "
              f"over-subscription {durability['tier_read_bytes_per_s']:.3g}"
              f" B/s vs all-resident "
              f"{durability['resident_read_bytes_per_s']:.3g} B/s "
              f"(x{durability['tier_read_ratio']:.2f})")

    if args.out:
        doc = {"bench": "ladder", "kind": args.kind,
               "smoke": bool(args.smoke), "params": kw,
               "columns": list(COLUMNS), "rows": list(ROWS)}
        for key, val in (("ops_per_s", ladder), ("mixed_control", mixed),
                         ("blockdev", blockdev), ("replication", replication),
                         ("trace", trace), ("kernels", kernels),
                         ("serve", serve), ("compute", compute),
                         ("durability", durability)):
            if val is not None:
                doc[key] = val
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.out}")

    if args.check:
        problems = []
        if ladder is not None:
            problems += check_no_regression(ladder)
        if ladder is not None and mixed is not None:
            problems += check_ring_gates(ladder, mixed)
        if blockdev is not None:
            problems += check_blockdev_gate(blockdev)
        if replication is not None and ladder is not None:
            problems += check_replication_gate(replication, ladder)
        if trace is not None:
            problems += check_trace_gates(trace)
        if kernels is not None:
            problems += check_kernel_gate(kernels)
        if serve is not None:
            problems += check_serve_gate(serve)
        if compute is not None:
            problems += check_compute_gate(compute)
        if durability is not None:
            problems += check_durability_gate(durability)
        if problems:
            print("REGRESSION:\n  " + "\n  ".join(problems), file=sys.stderr)
            return 1
        print("check OK: +fused/+sharded/+ring hold the +dbs floor on every "
              "row, +ring holds +fused on pure data and beats the fence on "
              "mixed data+control, the VolumeManager byte API holds "
              "0.9x raw +ring on aligned spans, the replica-transport "
              "local/all path holds 0.9x the +dbs column on pure data, "
              "the chaos harness is oracle-clean, replay-deterministic and "
              "inside its straggler tail bounds, every registered DBS "
              "kernel is bit-identical to the xla reference, zero-copy "
              "serving holds the copy-based floor with O(1) fork, the "
              "in-band volume scan is bit-identical to the host reference "
              "at >= 2x the read-back baseline, and the write-ahead "
              "journal holds its overhead bound with byte-identical WAL "
              "recovery (sections gated by --only run their checks only)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
