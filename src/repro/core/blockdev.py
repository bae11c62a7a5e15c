"""The ublk-style public block-device API: byte-addressed async volumes.

This is the repo's analogue of the paper's third pillar — the **ublk
frontend** that exposes the optimized engine as a plain virtual block
device, so consumers never see slot tables, SQE batches or page/block
geometry. Callers open a ``VolumeManager`` (which owns one registered
engine backend — core/backends.py — and its pump loop), get ``Volume``
handles, and issue **byte-addressed asynchronous I/O**:

    mgr = VolumeManager(backend="ring", n_shards=4)
    vol = mgr.create()
    fut = vol.pwrite(4096, b"hello")       # async: an IOFuture
    assert vol.read(4096, 5) == b"hello"   # sync convenience wrapper

Byte -> page translation (one ``Volume`` spans ``max_pages`` DBS pages):

    block_bytes = payload_elems          # one engine payload lane = 1 block
    page_bytes  = page_blocks * block_bytes
    byte off    -> page  off // page_bytes,
                   block (off % page_bytes) // block_bytes

Each byte is carried in one float32 payload lane (values 0..255 are exact in
float32, so round-trips are bit-faithful on every backend). **Aligned spans
map straight onto batched block ops**: one ``pwrite``/``pread`` fans out to
one SQE per covered block, they ride the engine's normal admission batches,
and complete on the pump's single CQ fetch — the API adds no host hops.
**Unaligned edges** take an in-API read-modify-write path: the partial edge
blocks are read back synchronously (ordered behind every in-flight op),
merged on the host, and written as whole blocks.

Ordering semantics (standard for async block devices — NVMe/ublk give no
ordering between in-flight commands either, but this API is stricter where
it is free to be):

- per volume, **submission order is execution order** for write->read,
  write->write (disjoint blocks), and anything->control: a volume's
  requests ride one admission queue, batches apply writes before reads and
  data before control, and the manager routes control ops through the same
  stream (in-band SQEs on ``backend="ring"``, flush-then-host-dispatch
  elsewhere),
- **overlapping-block hazards** (a write racing an in-flight read or write
  of the same block) are detected by the manager and fenced with a flush,
  so even adversarial interleavings keep sequential semantics.

``discard`` TRIMs: fully-covered pages are unmapped (in-band ``UNMAP`` SQEs
on the ring), partial edge spans are zero-filled through the RMW write
path; reads of discarded or never-written bytes return zeros (the engines'
hole-masked read path).

Snapshot/clone are volume-granular: ``vol.snapshot()`` freezes the head,
``vol.clone()`` forks a CoW copy whose writes diverge extent-by-extent.

The manager's geometry parameters mirror ``EngineConfig``; ``backend=``
names any registered backend ("loop" | "slots" | "fused" | "sharded" |
"ring" | "upstream" | "host"), and ``transport=`` / ``write_policy=`` /
``read_policy=`` name the controller<->replica wire and its mirroring
policies (core/transport.py — host-dispatch backends take the full policy
matrix; the in-program engines mirror-to-all inside the step). The manager
is a context manager: ``with VolumeManager(...) as mgr:`` drains all
in-flight I/O (including write-behind replica traffic) on exit, and
``close()`` makes further submissions raise. See docs/ARCHITECTURE.md
("Public API").
"""
from __future__ import annotations

import gc
import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.engine import Engine, EngineConfig
from repro.core.frontend import Request
from repro.core.transport import (MSG_CLONE, MSG_CREATE, MSG_DELETE,
                                  MSG_SNAPSHOT, MSG_UNMAP, MSG_WRITE,
                                  WireMsg)

# control kinds the durability journal records (core -> journal opcode)
_JOURNAL_CTRL = {"snapshot": MSG_SNAPSHOT, "clone": MSG_CLONE,
                 "delete": MSG_DELETE}

# the open ``py.gc`` span: ``gc.callbacks`` is process-wide, and Python
# runs one collection at a time
_gc_spans: List[TraceAnnotation] = []


def _gc_span(phase: str, info: Dict[str, int]) -> None:
    """``gc.callbacks`` hook: a ``py.gc`` profiler span around each Python
    garbage collection (``generation=`` its generation), so a pause in a
    trace can be told from the engine's own work."""
    if phase == "start":
        span = TraceAnnotation("py.gc", generation=info["generation"])
        span.__enter__()
        _gc_spans.append(span)
    elif _gc_spans:
        _gc_spans.pop().__exit__(None, None, None)


def _bytes_to_lanes(data: bytes) -> np.ndarray:
    """One byte per float32 payload lane (0..255 — exact in float32)."""
    return np.frombuffer(data, np.uint8).astype(np.float32)


def _lanes_to_bytes(arr) -> bytes:
    return np.asarray(arr).astype(np.uint8).tobytes()


class IOFuture:
    """Completion handle for one byte-addressed I/O call.

    Wraps the engine ``Request`` fan-out of a single ``pread``/``pwrite``/
    ``discard``: ``done()`` polls the requests' completion statuses,
    ``result()`` drives the manager's pump loop until complete and returns
    the call's value (``bytes`` for reads, the byte count for writes and
    discards). The value is assembled ONCE and cached: repeated ``result()``
    calls are idempotent — no re-assembly and no redundant flush after the
    first success. Raises ``OSError`` if any constituent op completed with
    a non-OK status."""

    _UNSET = object()

    __slots__ = ("_mgr", "_reqs", "_assemble", "_value", "_cached")

    def __init__(self, mgr: "VolumeManager", reqs: List[Request],
                 assemble: Optional[Callable[[], Any]] = None,
                 value: Any = None):
        self._mgr = mgr
        self._reqs = reqs
        self._assemble = assemble
        self._value = value
        self._cached = IOFuture._UNSET

    def done(self) -> bool:
        return (self._cached is not IOFuture._UNSET
                or all(r.status is not None for r in self._reqs))

    def latency(self) -> int:
        """Max completion latency (pump ticks) across the fan-out."""
        return max((r.latency or 0 for r in self._reqs), default=0)

    def completion_tick(self) -> int:
        """Absolute pump tick the last fan-out op completed on
        (``submission tick + latency - 1``; the frontend stamps both ends
        on the same clock). Deterministic across replays — the harness's
        replay-determinism gate compares per-op completion ticks."""
        return max((r.tick + (r.latency or 1) - 1 for r in self._reqs),
                   default=0)

    def result(self) -> Any:
        if self._cached is not IOFuture._UNSET:
            return self._cached
        if not self.done():
            self._mgr.flush()
        if not self.done():
            raise RuntimeError("I/O did not complete after a full drain")
        # negative statuses are I/O errors; positive ones (ST_MISMATCH from
        # compare_and_write / verify_on_read) are op-level outcomes the
        # caller inspects on the result — not exceptions
        bad = [r for r in self._reqs if r.status < 0]
        if bad:
            raise OSError(f"{bad[0].kind} failed with status {bad[0].status} "
                          f"(volume {bad[0].volume}, page {bad[0].page})")
        self._cached = (self._assemble() if self._assemble is not None
                        else self._value)
        return self._cached


@dataclass
class ComputeResult:
    """Outcome of one ``Volume.compute`` call.

    ``value`` is the function's scalar result (checksum, match count,
    actual blocksum for ``compare_and_write``...), ``status`` its op status
    (0 = OK, ``ST_MISMATCH`` = compare/verify failed — a *result*, not an
    I/O error), and ``payload`` the output lanes (matching pages for
    ``filter_pages``, the block contents for ``verify_on_read``)."""
    fn: str
    value: int
    status: int
    payload: np.ndarray = field(repr=False)

    @property
    def ok(self) -> bool:
        return self.status == 0

    def pages(self) -> List[int]:
        """Decode the payload as a page list (``filter_pages``): the
        non-negative lanes, in ascending order."""
        return [int(v) for v in np.asarray(self.payload).reshape(-1)
                if v >= 0]

    def data(self) -> bytes:
        """Decode the payload as block bytes (``verify_on_read``)."""
        return _lanes_to_bytes(self.payload)


class Volume:
    """A byte-addressed block-device handle (one DBS volume)."""

    def __init__(self, mgr: "VolumeManager", vid: int):
        self.mgr = mgr
        self.vid = vid

    # -- async byte I/O -----------------------------------------------------
    def pread(self, off: int, nbytes: int) -> IOFuture:
        return self.mgr.pread(self.vid, off, nbytes)

    def pwrite(self, off: int, data: bytes) -> IOFuture:
        return self.mgr.pwrite(self.vid, off, data)

    def discard(self, off: int, nbytes: int) -> IOFuture:
        return self.mgr.discard(self.vid, off, nbytes)

    def flush(self, durable: bool = False) -> None:
        """Drain in-flight I/O; ``durable=True`` additionally fsyncs the
        durability journal (repro/durability) — the write barrier."""
        self.mgr.flush(durable=durable)

    # -- computational storage ------------------------------------------------
    def compute(self, fn: str, off: int = 0, nbytes: Optional[int] = None,
                *, arg: int = 0, data: Optional[bytes] = None) -> IOFuture:
        """Run a registered storage function **in-band** against this
        volume's bytes (repro/compute). ``fn`` names a registry entry
        (``available_storage_fns()``); range-scoped functions take a
        page-aligned ``[off, off+nbytes)`` span (default: the whole
        device), block-scoped ones a single block at ``off``. ``arg`` is
        the function's scalar parameter, ``data`` the input block for
        writing functions (``compare_and_write``'s new contents). Returns
        an ``IOFuture`` resolving to a ``ComputeResult``."""
        return self.mgr.compute(self.vid, fn, off, nbytes, arg=arg,
                                data=data)

    # -- sync convenience wrappers -------------------------------------------
    def read(self, off: int, nbytes: int) -> bytes:
        return self.pread(off, nbytes).result()

    def write(self, off: int, data: bytes) -> int:
        return self.pwrite(off, data).result()

    # -- volume lifecycle -----------------------------------------------------
    def snapshot(self):
        """Freeze the volume head; returns the snapshot id (backends whose
        stores don't name snapshots return None)."""
        return self.mgr.snapshot(self.vid)

    def clone(self) -> Optional["Volume"]:
        return self.mgr.clone(self.vid)

    def delete(self) -> None:
        self.mgr.delete(self.vid)

    @property
    def capacity(self) -> int:
        return self.mgr.capacity

    @property
    def block_bytes(self) -> int:
        return self.mgr.block_bytes

    @property
    def page_bytes(self) -> int:
        return self.mgr.page_bytes

    def __repr__(self):
        return (f"Volume(vid={self.vid}, capacity={self.capacity}B, "
                f"backend={self.mgr.backend_name!r})")


class VolumeManager:
    """Owns one registered engine backend and hands out ``Volume`` handles.

    ``backend`` names a registry entry (core/backends.py); engine geometry
    kwargs mirror ``EngineConfig``. The manager owns the pump loop: every
    data op is submitted asynchronously and completed by ``flush()`` /
    ``IOFuture.result()`` driving the backend's (pipelined, single-fetch)
    drain.

    Per-volume ordering: all of a volume's requests are routed onto one
    admission queue (request ids are minted so ``req_id % n_queues`` is a
    function of the volume), which — together with the engines'
    writes-before-reads-before-control batch phases — makes submission
    order execution order. Overlapping-block write hazards are fenced with
    a flush (module docstring).
    """

    def __init__(self, backend: str = "ring", *, n_shards: int = 1,
                 n_replicas: int = 2, payload_elems: int = 64,
                 page_blocks: int = 32, n_extents: int = 1024,
                 max_volumes: int = 16, max_pages: int = 256,
                 n_queues: int = 4, n_slots: int = 256, batch: int = 64,
                 storage: str = "dbs", null_backend: bool = False,
                 null_storage: bool = False, cow: str = "auto",
                 kernel: str = "auto", transport: str = "local",
                 write_policy: str = "all", read_policy: str = "rr",
                 transport_opts: Optional[Dict[str, Any]] = None,
                 payload_shape: Optional[Tuple[int, ...]] = None,
                 journal: Any = None, tier: Any = None,
                 replica_chips: int = 1):
        # payload_shape overrides the byte-API's flat (payload_elems,) lane
        # layout with an arbitrary per-block tensor — the serving engine
        # stores one token's K/V for every layer in one block
        # ((n_planes, KV, hd), serving/engine.py). The byte-addressed
        # pread/pwrite surface assumes the flat layout; embedders with a
        # custom shape drive raw Requests + the device views below instead.
        self.payload_shape = (tuple(payload_shape)
                              if payload_shape is not None
                              else (payload_elems,))
        self.engine = Engine(EngineConfig(
            comm=backend, n_shards=n_shards, n_replicas=n_replicas,
            payload_shape=self.payload_shape, page_blocks=page_blocks,
            n_extents=n_extents, max_volumes=max_volumes,
            max_pages=max_pages, n_queues=n_queues, n_slots=n_slots,
            batch=batch, storage=storage, null_backend=null_backend,
            null_storage=null_storage, cow=cow, kernel=kernel,
            transport=transport,
            write_policy=write_policy, read_policy=read_policy,
            transport_opts=transport_opts, journal=journal, tier=tier,
            replica_chips=replica_chips))
        # durability journal (repro/durability/journal.py): the manager
        # buffers one WireMsg per mutating public-API op and group-commits
        # the buffer — ONE append + seal — at every pump boundary, BEFORE
        # the engine applies the batch (write-ahead)
        self._journal = self.engine.journal
        self._jbuf: List[WireMsg] = []
        self._closed = False
        self.backend_name = backend
        self.block_bytes = payload_elems
        self.page_blocks = page_blocks
        self.page_bytes = page_blocks * payload_elems
        self.capacity = max_pages * self.page_bytes
        self._nq = max(1, n_queues)
        self._ns = max(1, n_shards)
        self._seq = itertools.count()
        # control ops ride the data stream when the backend's submission
        # path accepts them (the ring); otherwise they fence host-side
        self._inband = "snapshot" in self.engine.data_kinds
        # the hot-path submit: the manager only mints valid data kinds, so
        # aligned spans go straight to the backend's frontend (the same
        # queues Engine.submit feeds, minus the per-request kind check)
        fe = self.engine.frontend
        self._fast_submit = (fe.submit if fe is not None
                             else self.engine.impl.submit)
        self.volumes: Dict[int, Volume] = {}
        # per-volume in-flight absolute-block sets, for the
        # overlapping-write hazard fence (O(span) per op; the counter
        # makes the no-traffic fence check O(1))
        self._pending_w: Dict[int, set] = {}
        self._pending_r: Dict[int, set] = {}
        self._n_pending = 0
        # hazard-fence flushes and the engine steps they dispatched
        self._fence_flushes = 0
        self._fence_steps = 0
        if _gc_span not in gc.callbacks:
            gc.callbacks.append(_gc_span)

    # ------------------------------------------------------------ plumbing
    def _rid(self, vid: int) -> int:
        """Mint a request id that pins this volume's stream to one admission
        queue of its shard (``req_id % n_queues`` is volume-determined), so
        per-volume FIFO survives the round-robin drain."""
        return next(self._seq) * self._nq + (vid // self._ns) % self._nq

    def _vid(self, vol) -> int:
        return vol.vid if isinstance(vol, Volume) else int(vol)

    def _check_span(self, off: int, nbytes: int) -> None:
        if off < 0 or nbytes < 0 or off + nbytes > self.capacity:
            raise ValueError(f"byte span [{off}, {off + nbytes}) outside "
                             f"device capacity {self.capacity}")

    def _fence_write(self, vid: int, lo: int, hi: int) -> None:
        """A write overlapping an in-flight read or write of the same block
        must not share its batch window — flush first (sequential
        semantics; disjoint-block and same-page traffic needs no fence)."""
        pw = self._pending_w.get(vid)
        pr = self._pending_r.get(vid)
        if pw is None and pr is None:
            return
        span = range(lo, hi)
        if ((pw and not pw.isdisjoint(span))
                or (pr and not pr.isdisjoint(span))):
            impl = self.engine.impl
            steps = getattr(impl, "dispatches", 0)
            with TraceAnnotation("vm.fence"):
                self.flush()
            self._fence_flushes += 1
            self._fence_steps += getattr(impl, "dispatches", 0) - steps

    def _track(self, table: Dict[int, set], vid: int, lo: int,
               hi: int) -> None:
        self._n_pending += 1
        s = table.get(vid)
        if s is None:
            table[vid] = set(range(lo, hi))
        else:
            s.update(range(lo, hi))

    def submit(self, req: Request) -> None:
        """Raw request-level escape hatch (validated at the backend's
        submission boundary)."""
        self._check_open()
        self.engine.submit(req)

    # ------------------------------------------------------------ journaling
    def _journal_seal(self) -> None:
        """Group commit: append the buffered records + ONE seal as a single
        file write (write-ahead: called before the engine pumps/drains)."""
        if self._journal is not None and self._jbuf:
            self._journal.append_batch(self._jbuf)
            self._jbuf.clear()

    def attach_journal(self, journal) -> None:
        """Adopt a (recovered, tail-truncated) journal: subsequent mutating
        ops append to it. ``durability.recovery.recover``'s reattach hook."""
        self._journal = journal
        self.engine.journal = journal
        self.engine._journal_owned = True

    def pump(self) -> int:
        if self._jbuf:
            self._journal_seal()
        done = self.engine.pump()
        if self._n_pending and self.engine.depth() == 0:
            # queues empty after a pump => every submitted op completed:
            # drop the hazard tracking so incremental pump() callers don't
            # accumulate stale blocks (and spurious fences) until a flush
            self._pending_w.clear()
            self._pending_r.clear()
            self._n_pending = 0
        return done

    def drain(self) -> int:
        return self.flush()

    def flush(self, durable: bool = False) -> int:
        """Complete everything in flight (the backends' pipelined drain —
        one device fetch per pump). Returns the number of completions.

        ``durable=True`` is the durability barrier: after the drain the
        journal is fsync'd, so every acked op survives a crash (without it,
        sealed records sit in OS buffers — crash-consistent but only as
        durable as the page cache)."""
        self._journal_seal()
        done = self.engine.drain()
        if self._n_pending:
            self._pending_w.clear()
            self._pending_r.clear()
            self._n_pending = 0
        if durable and self._journal is not None:
            self._journal.sync()
        return done

    def close(self) -> int:
        """Drain every in-flight I/O (including write-behind replica
        transport traffic) and close the manager: further submissions
        raise, ``flush``/``pump`` stay callable no-ops, handed-out
        ``IOFuture``s resolve (their requests completed in the drain).
        Idempotent. Returns the number of completions the final drain
        delivered."""
        if self._closed:
            return 0
        done = self.flush()
        storage = self.engine.backend
        if storage is not None and hasattr(storage, "drain_transports"):
            storage.drain_transports()    # quorum/async stragglers land
        if self._journal is not None:
            self._journal.sync()
            if self.engine._journal_owned:
                self._journal.close()
        self._closed = True
        return done

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "VolumeManager":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def _check_open(self) -> None:
        if self._closed:
            raise ValueError("I/O on a closed VolumeManager")

    def stats(self) -> Dict[str, Any]:
        """Counters for operators, all totals since the manager was built:

        - ``completed``, ``queued``: requests the engine completed / holds;
        - ``fence_flushes``: flushes the overlapping-block hazard fence
          forced inside ``pwrite`` (a write racing an in-flight op of the
          same block), and, on the backends that count their steps (ring,
          sharded), ``fence_steps``: the engine steps those flushes
          dispatched;
        - on ``backend="ring"``, ``write_rows`` and ``write_kernel_calls``:
          the ``dbs_rw_write`` kernel's extent-row moves and calls
          (``RingEngine.work_counters``; reading them syncs the device), so
          it moves ``4 * (write_rows * page_blocks + write_kernel_calls *
          batch) * block_bytes`` HBM bytes (one float32 lane per byte);
        - on ``backend="ring"``, ``upload_transfers``, ``upload_bytes`` and
          ``upload_payload_skips``: the pump's packed SQE uploads, one
          host-to-device transfer per dispatched step, their bytes, and
          those that left the payload out because the step's program was
          read-only (``RingEngine.upload_counters``; no device sync);
        - on ``backend="ring"``, ``fetch_transfers`` and ``fetch_bytes``:
          the pump's packed CQE fetches, one device-to-host transfer per
          completed step, and their bytes (``RingEngine.fetch_counters``;
          no device sync);
        - on ``backend="ring"``, ``fanout_bytes`` and ``remote_read_lanes``:
          with the replicas on separate chips (``replica_chips``), the
          user bytes owed to the remote replicas (written blocks x (R - 1)
          x ``block_bytes``; the step sends them the whole packed SQE) and
          the read lanes a remote replica served
          (``RingEngine.replica_counters``; no device sync; 0 on one
          chip);
        - ``slots_active``, ``journal``, ``tier`` where the backend has them.

        While a profiler trace runs (``jax.profiler``), the pump records
        the ``ring.*`` spans (core/ring.py), a hazard-fence flush a
        ``vm.fence`` span, and each Python garbage collection a ``py.gc``
        span."""
        out = {"completed": self.engine.completed,
               "queued": self.engine.depth(),
               "backend": self.backend_name,
               "fence_flushes": self._fence_flushes}
        impl = self.engine.impl
        if hasattr(impl, "dispatches"):
            out["fence_steps"] = self._fence_steps
        if hasattr(impl, "work_counters"):
            out.update(impl.work_counters())
            out.update(impl.upload_counters())
            out.update(impl.fetch_counters())
            out.update(impl.replica_counters())
        table = getattr(self.engine.frontend, "table", None)
        if table is not None:
            from repro.core import slots
            if hasattr(impl, "node0"):       # one copy per replica chip
                table = impl.node0(table)
            out["slots_active"] = int(np.asarray(slots.n_active(table)))
        if self._journal is not None:
            out["journal"] = {"seq": self._journal.seq,
                              "appends": self._journal.appends,
                              "records": self._journal.records}
        tier = getattr(self.engine.impl, "tier", None)
        if tier is not None:
            out["tier"] = tier.to_dict()
        return out

    # ------------------------------------------------------------ lifecycle
    def create(self) -> Volume:
        self._check_open()
        vid = self.engine.create_volume()
        if vid is None or vid < 0:
            raise RuntimeError("volume table full")
        if self._journal is not None:
            self._jbuf.append(WireMsg(op=MSG_CREATE, volume=vid,
                                      meta=(vid, 0)))
        vol = Volume(self, vid)
        self.volumes[vid] = vol
        return vol

    def open(self, vid: int) -> Volume:
        return self.volumes.get(vid) or self.volumes.setdefault(
            vid, Volume(self, vid))

    def _control_sync(self, kind: str, vid: int, **kw):
        """One control op, ordered behind the volume's in-flight stream:
        in-band SQE through the volume's own queue on the ring, host-side
        dispatch behind a flush elsewhere. Drains to completion either way."""
        self._check_open()
        if self._inband and kind in ("snapshot", "clone", "delete"):
            r = Request(req_id=self._rid(vid), kind=kind, volume=vid)
            self.engine.submit(r)
            self.flush()
            res = r.result
        else:
            self.flush()
            res = self.engine.control(kind, volume=vid, **kw)
        op = _JOURNAL_CTRL.get(kind)
        if op is not None and self._journal is not None:
            # the engine's result id rides meta so recovery can ASSERT its
            # replay allocated the same volume/snapshot ids
            rid = -1 if res is None else int(res)
            self._jbuf.append(WireMsg(op=op, volume=vid, meta=(rid, 0)))
        return res

    def snapshot(self, vol) -> Any:
        return self._control_sync("snapshot", self._vid(vol))

    def clone(self, vol) -> Optional[Volume]:
        """Fork a CoW copy; returns the new Volume (None on failure)."""
        new_vid = self._control_sync("clone", self._vid(vol))
        if new_vid is None or new_vid < 0:
            return None
        child = Volume(self, new_vid)
        self.volumes[new_vid] = child
        return child

    def delete(self, vol) -> None:
        vid = self._vid(vol)
        self._control_sync("delete", vid)
        self.volumes.pop(vid, None)

    # ------------------------------------------------------------ byte I/O
    def pread(self, vol, off: int, nbytes: int) -> IOFuture:
        self._check_open()
        vid = self._vid(vol)
        self._check_span(off, nbytes)
        if nbytes == 0:
            return IOFuture(self, [], value=b"")
        bb, pb = self.block_bytes, self.page_blocks
        first, last = off // bb, (off + nbytes - 1) // bb
        reqs = []
        submit = self._fast_submit
        for ab in range(first, last + 1):
            r = Request(req_id=self._rid(vid), kind="read", volume=vid,
                        page=ab // pb, block=ab % pb)
            submit(r)
            reqs.append(r)
        self._track(self._pending_r, vid, first, last + 1)
        head = off - first * bb

        def assemble() -> bytes:
            if len(reqs) == 1:                   # fast path: one block
                r = reqs[0]
                lanes = (np.zeros(bb, np.float32) if r.result is None
                         else np.asarray(r.result))
                return _lanes_to_bytes(lanes)[head:head + nbytes]
            parts = [np.zeros(bb, np.float32) if r.result is None
                     else np.asarray(r.result, np.float32) for r in reqs]
            return _lanes_to_bytes(np.concatenate(parts))[head:head + nbytes]
        return IOFuture(self, reqs, assemble=assemble)

    def _read_span_sync(self, vid: int, off: int, nbytes: int) -> bytes:
        fut = self.pread(vid, off, nbytes)
        return fut.result()          # drains: ordered behind all in-flight

    def pwrite(self, vol, off: int, data) -> IOFuture:
        self._check_open()
        vid = self._vid(vol)
        data = bytes(data)
        n = len(data)
        self._check_span(off, n)
        if n == 0:
            return IOFuture(self, [], value=0)
        bb, pb = self.block_bytes, self.page_blocks
        first, last = off // bb, (off + n - 1) // bb
        head = off - first * bb
        tail = (last + 1) * bb - (off + n)
        if head or tail:
            # in-API read-modify-write: fetch the partial edge blocks
            # synchronously (the read drains behind every in-flight op, so
            # it observes the volume's full submission history), merge the
            # new bytes in, and write whole blocks. A span inside ONE block
            # has both edges in that block: one read covers both.
            span = bytearray((last - first + 1) * bb)
            if first == last:
                span[:] = self._read_span_sync(vid, first * bb, bb)
            else:
                if head:
                    span[:bb] = self._read_span_sync(vid, first * bb, bb)
                if tail:
                    span[-bb:] = self._read_span_sync(vid, last * bb, bb)
            span[head:head + n] = data
            data = span
        if self._n_pending:
            self._fence_write(vid, first, last + 1)
        submit = self._fast_submit
        if first == last:                        # fast path: one block
            r = Request(req_id=self._rid(vid), kind="write", volume=vid,
                        page=first // pb, block=first % pb,
                        payload=_bytes_to_lanes(data))
            submit(r)
            reqs = [r]
        else:
            view = memoryview(data)
            reqs = []
            for i, ab in enumerate(range(first, last + 1)):
                r = Request(req_id=self._rid(vid), kind="write", volume=vid,
                            page=ab // pb, block=ab % pb,
                            payload=_bytes_to_lanes(
                                view[i * bb:(i + 1) * bb]))
                submit(r)
                reqs.append(r)
        self._track(self._pending_w, vid, first, last + 1)
        if self._journal is not None:
            # ONE record per pwrite: the POST-RMW block-aligned lanes, so
            # replay applies them directly — no re-merge needed (replay has
            # already applied every earlier record, so the merged edge
            # bytes are exactly what this record carries)
            # bytes(data) is the post-RMW whole-block span already in hand:
            # the record costs two list comprehensions, no numpy, and the
            # journal stores one uint8 per lane
            self._jbuf.append(WireMsg(
                op=MSG_WRITE, volume=vid,
                pages=[r.page for r in reqs],
                blocks=[r.block for r in reqs],
                payload=bytes(data)))
        return IOFuture(self, reqs, value=n)

    def _replay_write(self, vid: int, pages, blocks, lanes) -> None:
        """Recovery replay of one journaled ``MSG_WRITE`` record: re-submit
        its block lanes through the normal path — hazard fence included, so
        replay re-serializes exactly the overlapping spans the original run
        fenced (durability/recovery.py)."""
        self._check_open()
        pb = self.page_blocks
        abs_blocks = np.asarray(pages, np.int64) * pb + np.asarray(blocks)
        lo, hi = int(abs_blocks.min()), int(abs_blocks.max()) + 1
        if self._n_pending:
            self._fence_write(vid, lo, hi)
        submit = self._fast_submit
        for p, b, lane in zip(pages, blocks, lanes):
            submit(Request(req_id=self._rid(vid), kind="write", volume=vid,
                           page=int(p), block=int(b),
                           payload=np.asarray(lane, np.float32)))
        self._track(self._pending_w, vid, lo, hi)

    def discard(self, vol, off: int, nbytes: int) -> IOFuture:
        """TRIM ``[off, off+nbytes)``: fully covered pages are unmapped
        (extents freed — in-band UNMAP SQEs on the ring), partial edges are
        zero-filled through the write path. Reads of the span return zeros
        afterwards."""
        self._check_open()
        vid = self._vid(vol)
        self._check_span(off, nbytes)
        if nbytes == 0:
            return IOFuture(self, [], value=0)
        pby = self.page_bytes
        end = off + nbytes
        first_full = -(-off // pby)              # ceil
        last_full = end // pby
        reqs: List[Request] = []
        if first_full < last_full:
            reqs.extend(self._unmap_pages(vid,
                                          list(range(first_full, last_full))))
            edges = [(off, first_full * pby), (last_full * pby, end)]
        else:
            edges = [(off, end)]
        for a, b in edges:
            if b > a:
                reqs.extend(self.pwrite(vid, a, b"\x00" * (b - a))._reqs)
        return IOFuture(self, reqs, value=nbytes)

    def _unmap_pages(self, vid: int, pages: List[int]) -> List[Request]:
        """Unmap fully covered pages (extents freed): in-band UNMAP SQEs on
        the ring, flush-then-host-dispatch elsewhere. Journaled as ONE
        ``MSG_UNMAP`` record; also recovery's replay entry for that record."""
        reqs: List[Request] = []
        if self._inband:
            for p in pages:
                r = Request(req_id=self._rid(vid), kind="unmap",
                            volume=vid, page=p)
                self.engine.submit(r)
                reqs.append(r)
        else:
            self.flush()                     # order: behind in-flight ops
            self.engine.unmap(vid, pages)
        if self._journal is not None and pages:
            self._jbuf.append(WireMsg(op=MSG_UNMAP, volume=vid,
                                      pages=np.asarray(pages, np.int32)))
        return reqs

    # ------------------------------------------------- computational storage
    def compute(self, vol, fn: str, off: int = 0,
                nbytes: Optional[int] = None, *, arg: int = 0,
                data: Optional[bytes] = None) -> IOFuture:
        """In-band storage function over a volume's bytes (see
        ``Volume.compute``). On backends whose submission path accepts
        ``kind="compute"`` (the ring executes it inside the fused step; the
        host oracle runs the sequential reference in its pump FIFO) this is
        one async SQE riding the volume's queue — ordered like any other
        request. Elsewhere (fused/sharded) it fences with a flush and runs
        the same device computation against the replica pools
        (repro.compute.exec.device_compute)."""
        self._check_open()
        if hasattr(self.engine.impl, "refuse"):
            self.engine.impl.refuse("compute")
        from repro.compute import make_storage_fn, storage_fn_id
        vid = self._vid(vol)
        entry = make_storage_fn(fn)           # unknown names raise here
        bb, pby = self.block_bytes, self.page_bytes
        if entry.scope == "range":
            if nbytes is None:
                nbytes = self.capacity - off
            if off % pby or nbytes % pby or nbytes <= 0:
                raise ValueError(
                    f"range-scoped {fn!r} needs a page-aligned non-empty "
                    f"span (page_bytes={pby}), got [{off}, {off + nbytes})")
            self._check_span(off, nbytes)
            page, block = off // pby, nbytes // pby   # start page, page count
        else:                                  # scope == "block"
            if off % bb:
                raise ValueError(f"block-scoped {fn!r} needs a block-aligned "
                                 f"offset (block_bytes={bb}), got {off}")
            if nbytes is None:
                nbytes = bb
            if nbytes != bb:
                raise ValueError(f"block-scoped {fn!r} covers exactly one "
                                 f"block ({bb}B), got nbytes={nbytes}")
            self._check_span(off, nbytes)
            ab = off // bb
            page, block = ab // self.page_blocks, ab % self.page_blocks
        payload = None
        if entry.writes:
            if data is None:
                raise ValueError(f"{fn!r} writes: pass data= (the new "
                                 "block contents)")
            data = bytes(data)
            if len(data) != bb:
                raise ValueError(f"{fn!r} data must be one block "
                                 f"({bb}B), got {len(data)}")
            payload = _bytes_to_lanes(data)
        elif data is not None:
            raise ValueError(f"{fn!r} does not take data=")

        if entry.writes and self._journal is not None:
            # only MUTATING storage functions are journaled (read-only ones
            # don't change state); replay re-executes them in place — their
            # outcome is a pure function of the replayed device state
            from repro.durability.journal import OP_COMPUTE
            self._jbuf.append(WireMsg(
                op=OP_COMPUTE, volume=vid,
                pages=np.asarray([page], np.int32),
                blocks=np.asarray([block], np.int32),
                extents=fn.encode(),
                meta=(int(arg), 1 if entry.scope == "range" else 0),
                payload=data))

        def wrap(value, status, lanes) -> ComputeResult:
            return ComputeResult(fn=fn, value=int(value), status=int(status),
                                 payload=np.asarray(lanes, np.float32))

        if "compute" in self.engine.data_kinds:    # ring + host: in-queue
            r = Request(req_id=self._rid(vid), kind="compute", volume=vid,
                        page=page, block=block, payload=payload, fn=fn,
                        arg=int(arg), fnid=storage_fn_id(fn))
            self._fast_submit(r)

            def assemble() -> ComputeResult:
                value, lanes = (r.result if r.result is not None
                                else (0, np.zeros(self.payload_shape,
                                                  np.float32)))
                return wrap(value, r.status, lanes)
            return IOFuture(self, [r], assemble=assemble)
        # device backends without an in-band compute path: fence with a
        # flush (ordering behind in-flight I/O), then run the very same
        # device computation against the replica pools
        from repro.compute.exec import device_compute
        self.flush()
        value, status, lanes = device_compute(
            self.engine, vid, fn, page, block, int(arg), payload)
        return IOFuture(self, [], value=wrap(value, status, lanes))

    # ------------------------------------- embedder control-plane passthrough
    @property
    def state(self):
        """The backing DBSState (``backend="host"`` only) — the control
        plane embedders read block tables from (serving/engine.py)."""
        return self.engine.impl.state

    def alloc_pages(self, vols, pages, mask=None, bits=None):
        """Page-granular allocation/CoW on the host backend's state; returns
        the DBS ``WriteOps`` for an external data plane (serving KV pools).
        Host backend only — on the fused/sharded engines page allocation IS
        the write SQE path: submit zero-payload writes and ``flush()``, and
        every lane's allocation + CoW resolution rides ONE pumped program
        (the batching the serving engine's per-step admission relies on)."""
        return self.engine.impl.alloc_pages(vols, pages, mask=mask,
                                            bits=bits)

    # --------------------------------------------- device-resident KV views
    # The zero-copy serving path (serving/engine.py) reads these: the
    # extent map a paged-attention kernel indexes through, and the engine
    # payload pools it treats as the KV cache. All views are device arrays —
    # nothing here syncs to the host.
    def device_extent_map(self):
        """The device-resident flattened extent map as ONE (V, P) int32
        table over *global* volume ids (holes/unallocated pages -1).

        host backend: the oracle state's table. fused: replica 0's (the
        replicas execute identical control sequences — their tables agree).
        sharded: the per-shard (S, V_local, P) tables are fused into global
        coordinates — extent ids are offset by ``shard * (E+1)`` to index
        the flattened pool of ``device_pools`` and rows are reordered so
        row ``v`` is global volume ``v`` (= local * S + shard)."""
        impl = self.engine.impl
        if hasattr(impl, "state"):                      # host oracle
            return impl.state.table
        storage = self.engine.backend
        if storage is None:
            raise RuntimeError("null backend holds no extent map")
        if hasattr(storage, "states"):                  # sharded (stacked)
            import jax.numpy as jnp
            tbl = storage.states[0].table               # (S, Vl, P)
            s = storage.n_shards
            stride = self.engine.cfg.n_extents + 1      # pool rows per shard
            off = (jnp.arange(s, dtype=tbl.dtype) * stride)[:, None, None]
            flat = jnp.where(tbl >= 0, tbl + off, -1)
            return flat.transpose(1, 0, 2).reshape(-1, tbl.shape[2])
        states, _pools = storage.device_state()         # fused ReplicaGroup
        return states[0].table

    def device_pools(self):
        """The engine payload pools as a tuple of device arrays, one per
        (healthy) replica, each ``(rows, page_blocks, *payload_shape)`` —
        rows = E+1 on the fused engine, S*(E+1) on the sharded pool (the
        per-shard pools concatenated; ``device_extent_map`` hands out row
        ids in exactly this coordinate system)."""
        storage = self.engine.backend
        if storage is None:
            raise RuntimeError("null backend holds no pools")
        if hasattr(storage, "states"):                  # sharded (stacked)
            _st, pools, _h = storage.device_state()
            return tuple(p.reshape((-1,) + p.shape[2:]) for p in pools)
        _st, pools = storage.device_state()
        return tuple(pools)

    def set_device_pools(self, pools) -> None:
        """Write mutated pools (same shapes ``device_pools`` returned) back
        to the replicas — the commit half of an external compute step that
        scattered into the pools (the serving decode program)."""
        storage = self.engine.backend
        if storage is None:
            raise RuntimeError("null backend holds no pools")
        if hasattr(storage, "states"):                  # sharded (stacked)
            states, cur, _h = storage.device_state()
            reshaped = tuple(p.reshape(c.shape)
                             for p, c in zip(pools, cur))
            storage.set_device_state(states, reshaped)
            return
        states, _cur = storage.device_state()
        storage.set_device_state(states, tuple(pools))

    def __repr__(self):
        return (f"VolumeManager(backend={self.backend_name!r}, "
                f"block_bytes={self.block_bytes}, "
                f"page_bytes={self.page_bytes}, capacity={self.capacity})")
