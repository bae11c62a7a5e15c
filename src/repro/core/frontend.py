"""Frontends: multi-queue (ublk-style) vs single-loop (TGT-style upstream).

The paper's frontend finding (§IV-B): the TGT/iSCSI path serializes — every
I/O crosses a synchronous unix-socket hop, one at a time; ublk with *multiple
frontend queues* raises queue depth and throughput ~14x. On a TPU host the
analogue is request admission into the compiled engine:

- ``UpstreamFrontend``: one queue, one dispatcher, one request per device
  call (a dict tracks in-flight requests) — deliberately faithful to the
  upstream structure, used as the measured baseline.
- ``RingFrontend`` (core/ring.py): THE drain protocol since the SQ/CQ
  refactor — S shards × N admission queues drained into one opcode-tagged
  ``SQE`` batch per pump (data ops AND control ops through the same path).
- ``MultiQueueFrontend`` / ``ShardedFrontend``: thin adapters over a
  RingFrontend that keep the legacy drain surfaces alive: ``poll_batch``
  (the unfused ``comm="slots"`` engine), ``drain_batch`` (single-engine
  ``comm="fused"``), and ``drain_sharded`` (the vmapped EnginePool). Each
  converts the staged ring drain into its legacy batch shape; none owns
  drain logic of its own anymore.

See docs/ARCHITECTURE.md for where the frontend sits in the pipeline.
"""
from __future__ import annotations

import collections
import itertools
from dataclasses import dataclass
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import slots
from repro.core.fused import FusedBatch
from repro.core.ring import KIND_CLASS, OP_WRITE, RingFrontend


@dataclass
class Request:
    req_id: int
    kind: str                 # read | write | snapshot | clone | unmap |
                              # delete | fail | rebuild | compute | noop
                              # (ring opcodes)
    volume: int = -1
    page: int = 0
    block: int = 0            # block offset; replica index for fail/rebuild;
                              # page count (range fns) / block (block fns)
                              # for compute
    payload: Any = None
    shard: Optional[int] = None  # explicit shard (fail/rebuild; else by vol)
    result: Any = None        # read payload / snapshot id / clone volume id
                              # / (value, CQ payload lanes) for compute
    status: Any = None        # CQE status (ring.ST_*); 0 = completed OK
    latency: Any = None       # completion latency in pump ticks (ring path)
    tick: int = 0             # submission pump tick (stamped by the frontend)
    fn: Optional[str] = None  # storage-function name (kind="compute")
    arg: int = 0              # storage-function immediate argument
    fnid: int = 0             # resolved registry id (stamped at submit)


class UpstreamFrontend:
    """Single queue + single loop function + dynamic map (paper Fig. 4 left)."""

    def __init__(self, max_inflight: int = 256):
        self.queue: Deque[Request] = collections.deque()
        self.messages: Dict[int, Request] = {}      # the Messages Map
        self._next_id = itertools.count()
        self.max_inflight = max_inflight
        self.step = 0               # pump tick (latency accounting)

    def submit(self, req: Request) -> None:
        req.tick = self.step        # unified latency semantics: every comm
        self.queue.append(req)      # mode stamps submission in pump ticks

    def poll_one(self) -> Optional[Tuple[int, Request]]:
        """The loop function: take ONE request, assign a unique id, store it
        in the map. Sequential by construction (the paper's bottleneck).
        Each poll is one pump tick; the popped request's ``latency`` is
        stamped in ticks, like the ring CQE's."""
        if not self.queue or len(self.messages) >= self.max_inflight:
            return None
        req = self.queue.popleft()
        req.latency = self.step - req.tick + 1
        self.step += 1
        mid = next(self._next_id)
        self.messages[mid] = req
        return mid, req

    def complete(self, mid: int) -> Request:
        return self.messages.pop(mid)

    def __len__(self):
        return len(self.queue)


def _reject_control(req) -> None:
    """Legacy (data-only) frontends refuse control kinds at SUBMIT time:
    rejecting at drain would have already popped the whole batch — dropping
    innocent data requests alongside the offending one."""
    if KIND_CLASS.get(req.kind) in ("vol", "repl", "compute"):
        raise ValueError("control/compute opcodes require comm='ring' "
                         f"(got kind={req.kind!r} on a data-only frontend)")


def _check_data_only(classes) -> None:
    # defensive: unreachable via submit(), which rejects control kinds
    ctrl = set(classes) - {"read", "write", "noop"}
    if ctrl:
        raise ValueError("control opcodes require comm='ring' "
                         f"(got {sorted(ctrl)} on a legacy drain path)")


class MultiQueueFrontend:
    """N admission queues + batched slot admission (paper Fig. 4 right).

    A thin adapter over a single-shard ``RingFrontend``: submission,
    requeueing and the round-robin drain live there; this class keeps the
    legacy surfaces — ``poll_batch`` (admission as its own device op, slot
    ids fetched back) and ``drain_batch`` (raw FusedBatch arrays for the
    fused step) — by converting the staged ring drain.

    ``with_table=False`` builds only the host-side admission rings (the
    composing caller owns the authoritative slot table).
    """

    def __init__(self, n_queues: int, n_slots: int, batch: int = 64,
                 with_table: bool = True):
        self.ring = RingFrontend(1, n_queues, n_slots, batch,
                                 with_table=False)
        self.table = slots.make_table(n_slots) if with_table else None
        self.batch = batch
        self._by_slot: Dict[int, Request] = {}

    @property
    def queues(self) -> List[Deque[Request]]:
        return self.ring.queues[0]

    @property
    def step(self) -> int:
        return self.ring.step[0]

    @step.setter
    def step(self, v: int) -> None:
        self.ring.step[0] = v

    def submit(self, req: Request) -> None:
        _reject_control(req)
        self.ring.submit(req)

    def depth(self) -> int:
        return self.ring.depth()

    def requeue(self, req: Request) -> None:
        """Put a not-admitted request back at the front of its queue."""
        self.ring.requeue(req)

    def _drain(self, limit: int) -> List[Request]:
        """Host-only round-robin drain of up to ``limit`` requests — the
        shared ring drain, shard 0."""
        return self.ring._drain_shard(0, limit)

    def drain_batch(self, payload_shape: Tuple[int, ...] = ()
                    ) -> Tuple[List[Request], Optional[FusedBatch]]:
        """Drain up to ``batch`` requests into the fixed-shape raw arrays the
        fused engine step consumes. Pure host->device traffic: admission
        itself happens *inside* ``fused_step`` (core/fused.py), so no slot id
        is ever read back — the admission state (``self.table``) stays on
        device across ``pump()`` iterations."""
        drained, st, classes = self.ring._stage(payload_shape)
        if st is None:
            return [], None
        _check_data_only(classes)
        # shard 0's numpy lanes cross as ONE transfer per leaf, as before
        batch = FusedBatch(
            want=jnp.asarray(st["want"][0]),
            is_write=jnp.asarray(st["op"][0] == OP_WRITE),
            volume=jnp.asarray(st["volume"][0]),
            page=jnp.asarray(st["page"][0]),
            block=jnp.asarray(st["block"][0]),
            payload=jnp.asarray(st["payload"][0]),
            queue=jnp.asarray(st["queue"][0]),
            step=jnp.int32(int(st["step"][0])),
        )
        return drained[0], batch

    def poll_batch(self) -> Tuple[jnp.ndarray, List[Request]]:
        """Drain up to ``batch`` requests round-robin across queues and admit
        them in ONE device op. Returns (slot_ids (k,), requests)."""
        reqs = self._drain(self.batch)
        if not reqs:
            return jnp.zeros((0,), jnp.int32), []
        # fixed-shape admission (pad to the batch size): one compiled program
        # regardless of how many requests arrived — the Messages-Array idiom
        n = len(reqs)
        want = jnp.arange(self.batch) < n
        vols = jnp.asarray([r.volume for r in reqs]
                           + [0] * (self.batch - n), jnp.int32)
        queues = jnp.asarray([r.req_id % len(self.queues) for r in reqs]
                             + [0] * (self.batch - n), jnp.int32)
        self.table, ids, ok = slots.admit(self.table, want, vols, queues,
                                          jnp.int32(self.step))
        ids = ids[:n]
        ok = ok[:n]
        self.step += 1
        ids_host = np.asarray(jax.device_get(ids))
        ok_host = np.asarray(jax.device_get(ok))
        admitted, requeues = [], []
        for i, r in enumerate(reqs):
            if ok_host[i]:
                self._by_slot[int(ids_host[i])] = r
                admitted.append(r)
            else:  # no slot: requeue at the front
                requeues.append(r)
        self.ring.requeue_all(requeues)
        return ids[:len(reqs)], admitted

    def complete(self, slot_ids: jnp.ndarray) -> List[Request]:
        self.table = slots.retire(self.table, slot_ids)
        out = []
        for sid in jax.device_get(slot_ids):
            if int(sid) >= 0 and int(sid) in self._by_slot:
                out.append(self._by_slot.pop(int(sid)))
        return out


class ShardedFrontend:
    """S volume-hashed shards feeding ONE vmapped admission program.

    A thin adapter over an S-shard ``RingFrontend`` (which owns the queues,
    the stacked shard-major ``SlotTable`` and the drain); ``drain_sharded``
    converts the staged ring drain into the legacy stacked (S, B, ...)
    ``FusedBatch`` the EnginePool step consumes. Volume ids are translated
    to shard-local ids (``volume // S``) by the ring stage.
    """

    def __init__(self, n_shards: int, n_queues: int, n_slots: int,
                 batch: int = 64):
        self.ring = RingFrontend(n_shards, n_queues, n_slots, batch,
                                 with_table=True)
        self.n_shards = n_shards
        self.batch = batch

    @property
    def table(self) -> slots.SlotTable:
        return self.ring.table

    @table.setter
    def table(self, t: slots.SlotTable) -> None:
        self.ring.table = t

    def shard_of(self, volume: int) -> int:
        return volume % self.n_shards

    def submit(self, req: Request) -> None:
        _reject_control(req)
        self.ring.submit(req)

    def requeue(self, req: Request) -> None:
        self.ring.requeue(req)

    def depth(self) -> int:
        return self.ring.depth()

    def drain_sharded(self, payload_shape: Tuple[int, ...] = ()
                      ) -> Tuple[List[List[Request]], Optional[FusedBatch]]:
        """Drain every shard into one stacked (S, B, ...) FusedBatch.

        Returns (per-shard request lists, stacked batch) — batch is None
        when no shard had traffic. Request lists line up with batch lanes:
        shard s's request i rode lane (s, i); shards with no traffic
        contribute all-inert (want=False) rows, so the program geometry
        never depends on which shards are busy. One device transfer per
        leaf, from the named views of the ring stage's packed buffer (the
        ring pump itself sends that buffer as one transfer).
        """
        drained, st, classes = self.ring._stage(payload_shape)
        if st is None:
            return [], None
        _check_data_only(classes)
        batch = FusedBatch(
            want=jnp.asarray(st["want"]),
            is_write=jnp.asarray(st["op"] == OP_WRITE),
            volume=jnp.asarray(st["volume"]), page=jnp.asarray(st["page"]),
            block=jnp.asarray(st["block"]),
            payload=jnp.asarray(st["payload"]),
            queue=jnp.asarray(st["queue"]), step=jnp.asarray(st["step"]))
        return drained, batch
