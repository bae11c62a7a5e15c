"""Unified SQ/CQ ring protocol: ONE opcode-tagged submission path for data
AND control ops through the fused/sharded step (paper §IV-B/C).

The paper's second pillar restructures the communication protocol between
the ublk frontend and the replicas into one queue pair carrying everything,
instead of per-request synchronous hops. PR 1/2 built a fast device-resident
*data* plane (fused step, vmapped shard pool), but every *control* op —
snapshot, clone, unmap, delete, replica fail/rebuild — was still a separate
host-side dispatch that fenced the pump, and each engine spoke its own drain
protocol. This module is the io_uring-style fix:

- **SQE** — an opcode-tagged submission record (READ / WRITE / SNAPSHOT /
  CLONE / UNMAP / DELETE / FAIL_REPLICA / REBUILD_REPLICA / NOOP barrier),
  admitted through the SlotTable like any other request. The Messages Array
  records each slot's opcode (``slots.SlotTable.opcode``).
- **CQ** — a device-resident buffer of completion records indexed by slot id
  (the "payload slot"): status, op result value, op latency in pump ticks,
  and the read payload. The step scatters a CQE per admitted lane; the host
  fetches the per-lane view once per pump, packed into one int32 array.
- **ring_step_core** — the opcode-dispatched engine iteration: the batched
  data phase (mirrored CoW writes, rr reads — identical to fused.step_core),
  then a lane-ordered ``lax.scan`` applying the volume-control tail
  (``lax.switch`` over op class), then the masked replica-control op against
  the *traced* health mask. Everything is vmap-safe, so the sharded pool
  gets in-band control ops for free — per-shard fail/rebuild happens inside
  the same single jitted program as foreground I/O, no host branch between
  pumps.
- **RingFrontend** — THE drain protocol. S shards × Q admission queues, one
  opcode-aware drain (``drain_ring``). The legacy ``MultiQueueFrontend`` /
  ``ShardedFrontend`` are thin adapters over it (core/frontend.py).
- **RingEngine** — ``EngineConfig(comm="ring")``: S engine shards (S=1 runs
  the program unmapped), pipelined double-buffered pump, one compiled
  program per (batch geometry, opcode-class signature).

Batch-ordering contract (what makes in-band control bit-exact against the
host-side sequential reference): within one SQE batch, data lanes precede
control lanes (the frontend cuts the drain so that once a control op is
drained only further control ops may join, and a replica op closes the
batch). The step applies the data phase first, then the control tail in
lane order — exactly the submission order. Ordering *between* batches is
program order as always.
"""
from __future__ import annotations

import collections
import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.compute import registry as compute_registry
from repro.compute.phase import apply_compute_ops
from repro.core import dbs, slots
from repro.core.control import ControlDispatch
from repro.core.fused import _cow_apply, _cow_work, _rr_gather
from repro.core.replication import (ShardedReplicaGroup, mirror_write,
                                    rr_select)
from repro.core.transport import clone_page_rev, stamp_page_rev

# ---------------------------------------------------------------------------
# the opcode table (SQE.op) and completion statuses (CQE.status)
# ---------------------------------------------------------------------------
OP_NOOP = 0        # barrier: admit + complete, touches nothing
OP_READ = 1
OP_WRITE = 2
OP_SNAPSHOT = 3    # volume-control ops (applied in lane order)
OP_CLONE = 4
OP_UNMAP = 5
OP_DELETE = 6
OP_FAIL = 7        # replica-control ops (close their batch)
OP_REBUILD = 8
OP_COMPUTE = 9     # in-band storage function (repro/compute registry)

OP_NAMES = ("NOOP", "READ", "WRITE", "SNAPSHOT", "CLONE", "UNMAP", "DELETE",
            "FAIL_REPLICA", "REBUILD_REPLICA", "COMPUTE")

KIND_TO_OP = {"noop": OP_NOOP, "read": OP_READ, "write": OP_WRITE,
              "snapshot": OP_SNAPSHOT, "clone": OP_CLONE, "unmap": OP_UNMAP,
              "delete": OP_DELETE, "fail": OP_FAIL, "rebuild": OP_REBUILD,
              "compute": OP_COMPUTE}

# opcode classes: which phases of the step a batch needs (static per program)
KIND_CLASS = {"noop": "noop", "read": "read", "write": "write",
              "snapshot": "vol", "clone": "vol", "unmap": "vol",
              "delete": "vol", "fail": "repl", "rebuild": "repl",
              "compute": "compute"}

ST_OK = 0          # completed
ST_ERR = -1        # op rejected (bad volume / snapshot table full / bad arg)
ST_LAST = -2       # FAIL would lose the shard's last healthy replica
ST_HEALTHY = -3    # REBUILD target is healthy — nothing to rebuild
# positive status: the op ran, its predicate did not hold (CAS expectation
# miss, verify_on_read checksum mismatch) — NOT an I/O error, IOFuture only
# raises on status < 0. Canonical value lives in repro/compute/registry.py
# (this module imports the compute package; never the reverse).
ST_MISMATCH = compute_registry.ST_MISMATCH

# max control ops per batch: the in-program control scan covers a fixed
# K-lane window (control lanes are contiguous — the drain policy admits only
# further control ops once one is drained — so a dynamic-slice window at the
# first control lane sees them all). Small K keeps the scan cheap under
# vmap, where every lane executes every switch branch.
CTRL_TAIL = 8

# max COMPUTE ops per batch — the compute phase's scan window, same idiom
# (EngineConfig.compute_tail overrides per engine). Compute is its own batch
# rank between data and control: data < compute < control, the drain cuts on
# every rank change, and a *writing* storage function (compare_and_write)
# additionally closes the compute window so the phase commits at most one
# CoW write per batch.
COMPUTE_TAIL = 8


# ---------------------------------------------------------------------------
# SQE / CQ records
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclass
class SQE:
    """One fixed-shape submission batch: the opcode-tagged generalisation of
    ``fused.FusedBatch``. All lane arrays are (B,) ((S, B) stacked), inert
    padding lanes marked want=False. ``block`` doubles as the replica index
    for FAIL/REBUILD lanes; ``tick`` is the submission pump tick (latency =
    completion step - tick + 1)."""
    want: jnp.ndarray       # (B,) bool
    op: jnp.ndarray         # (B,) int32 opcode (OP_*)
    volume: jnp.ndarray     # (B,) int32 shard-local volume (-1 = none)
    page: jnp.ndarray       # (B,) int32
    block: jnp.ndarray      # (B,) int32 block offset / replica index
    payload: jnp.ndarray    # (B, *payload) write payloads
    queue: jnp.ndarray      # (B,) int32 admission queue
    tick: jnp.ndarray       # (B,) int32 submission pump tick
    fn: jnp.ndarray         # (B,) int32 storage-fn id (COMPUTE lanes)
    arg: jnp.ndarray        # (B,) int32 storage-fn immediate argument
    step: jnp.ndarray       # ()   int32 admission step (this pump's tick)


# The packed upload: one int32 row per shard carries a whole SQE. The header
# holds the lane fields below in this order, each B words wide (``want`` as
# 0/1), then the ``step`` word, padded to whole 128-word lane tiles so the
# payload starts on a tile; the payload's float32 bits follow,
# B * prod(payload_shape) words.
SQE_LANES = ("want", "op", "volume", "page", "block", "queue", "tick", "fn",
             "arg")
LANE_TILE = 128


def header_words(b_n: int) -> int:
    """Width of the packed upload's header for a B-lane batch."""
    return -(-(len(SQE_LANES) * b_n + 1) // LANE_TILE) * LANE_TILE


def unpack_sqe(packed: jnp.ndarray, b_n: int,
               payload_shape: Tuple[int, ...]) -> SQE:
    """Rebuild the stacked (S, B, ...) SQE from its packed (S, W) int32
    upload, inside the jitted step: slices for the lane fields, the payload
    bit-cast back to float32. An upload of the header alone (the read tier's,
    whose program never reads payloads) gets zero payloads."""
    s_n, h = packed.shape[0], header_words(b_n)
    lanes = {name: packed[:, k * b_n:(k + 1) * b_n]
             for k, name in enumerate(SQE_LANES)}
    lanes["want"] = lanes["want"] != 0
    shape = (s_n, b_n) + tuple(payload_shape)
    if packed.shape[1] > h:
        payload = jax.lax.bitcast_convert_type(
            packed[:, h:], jnp.float32).reshape(shape)
    else:
        payload = jnp.zeros(shape, jnp.float32)
    return SQE(payload=payload, step=packed[:, len(SQE_LANES) * b_n],
               **lanes)


@jax.tree_util.register_dataclass
@dataclass
class CQ:
    """Device-resident completion records, indexed by slot id (the "payload
    slot" of the CQE). A slot's record lives until the slot is reacquired —
    the Messages-Array idiom applied to completions."""
    status: jnp.ndarray     # (N,) int32 ST_*
    value: jnp.ndarray      # (N,) int32 op result (snapshot id / clone vol)
    latency: jnp.ndarray    # (N,) int32 completion latency in pump ticks
    payload: jnp.ndarray    # (N, *payload) read payload slots
    work: jnp.ndarray       # (2,) int32 dbs_rw_write rows moved, kernel
                            # calls: running totals that wrap (read by
                            # RingEngine.work_counters)


@jax.tree_util.register_dataclass
@dataclass
class CQEView:
    """The per-lane view of this pump's completion records — what the host's
    single per-pump ``device_get`` fetches, packed (``pack_cqe``)."""
    ok: jnp.ndarray         # (B,) bool  lane admitted (and thus completed)
    status: jnp.ndarray     # (B,) int32
    value: jnp.ndarray      # (B,) int32
    latency: jnp.ndarray    # (B,) int32
    reads: jnp.ndarray      # (B, *payload)


# The packed fetch: one int32 row per lane carries the lane's whole CQE —
# the fields below in this order (``ok`` as 0/1), then the read payload's
# float32 bits, prod(payload_shape) words (one for a scalar payload).
CQE_LANES = ("ok", "status", "value", "latency")


def pack_cqe(view: CQEView) -> jnp.ndarray:
    """The stacked (S, B) CQE view as one (S, B, 4 + P) int32 array, inside
    the jitted step, so the host fetches it in one device-to-host transfer.
    The payload is bit-cast, not converted: NaN payload bits survive."""
    s_n, b_n = view.ok.shape
    reads = jax.lax.bitcast_convert_type(view.reads, jnp.int32)
    lanes = [getattr(view, name).astype(jnp.int32)[..., None]
             for name in CQE_LANES]
    return jnp.concatenate(lanes + [reads.reshape(s_n, b_n, -1)], axis=-1)


def unpack_cqe(buf: np.ndarray,
               payload_shape: Tuple[int, ...]) -> CQEView:
    """The fetched (S, B, 4 + P) int32 buffer as the stacked CQE view, by
    slicing and viewing its memory: every field but the (S, B) ``ok``
    comparison is a view of ``buf``, the payload viewed as float32."""
    lanes = {name: buf[..., k] for k, name in enumerate(CQE_LANES)}
    lanes["ok"] = lanes["ok"] != 0
    reads = buf[..., len(CQE_LANES):].view(np.float32).reshape(
        buf.shape[:2] + tuple(payload_shape))
    return CQEView(reads=reads, **lanes)


def make_cq(n_slots: int, payload_shape: Tuple[int, ...] = ()) -> CQ:
    z = lambda: jnp.zeros((n_slots,), jnp.int32)
    return CQ(status=z(), value=z(), latency=z(),
              payload=jnp.zeros((n_slots,) + tuple(payload_shape),
                                jnp.float32),
              work=jnp.zeros((2,), jnp.int32))


def make_sharded_cq(n_shards: int, n_slots: int,
                    payload_shape: Tuple[int, ...] = ()) -> CQ:
    cq = make_cq(n_slots, payload_shape)
    return jax.tree.map(
        lambda x: jnp.tile(x[None], (n_shards,) + (1,) * x.ndim), cq)


# ---------------------------------------------------------------------------
# the opcode-dispatched step
# ---------------------------------------------------------------------------
def _apply_vol_ops(states, page_revs, batch: SQE, ok, value, status):
    """Apply the SNAPSHOT/CLONE/UNMAP/DELETE tail in lane order.

    A ``lax.scan`` over a ``CTRL_TAIL``-lane window keeps submission-order
    semantics with a fixed trace structure; each lane is a masked
    ``lax.switch`` over op class (non-control and padding lanes take the
    NOOP branch). The window is a dynamic slice anchored at the first
    control lane — control lanes are contiguous (drain policy) and capped
    at CTRL_TAIL per batch, so the window covers every one of them without
    scanning the whole batch. Control ops apply to EVERY replica slice,
    healthy or not — the lock-step convention of the sharded group's
    mirrored control path, which lets rebuild copy metadata wholesale
    instead of replaying control ops. The per-replica watermark arrays ride
    the scan carry because CLONE must copy the source's row
    (``transport.clone_page_rev`` — delta rebuild would otherwise miss
    extents reachable only through the clone's table)."""
    b_n = batch.op.shape[0]
    k = min(CTRL_TAIL, b_n)
    is_vol = ok & (batch.op >= OP_SNAPSHOT) & (batch.op <= OP_DELETE)
    start = jnp.clip(jnp.argmax(is_vol), 0, b_n - k)
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, k)
    op_w, vol_w, page_w = sl(batch.op), sl(batch.volume), sl(batch.page)
    is_vol_w = sl(is_vol)       # data lanes caught by edge-clamping: masked

    def lane(carry, xs):
        op, vol, page, live = xs
        branch = jnp.where(live, op - OP_SNAPSHOT + 1, 0)

        def b_noop(c):
            return c, jnp.int32(-1)

        def each(fn):
            def b(c):
                sts, prs = c
                outs = [fn(st) for st in sts]
                return (tuple(st for st, _ in outs), prs), outs[0][1]
            return b

        def b_clone(c):
            sts, prs = c
            outs = [dbs.clone(st, vol) for st in sts]
            # each replica clones its OWN state (lock-step ids) and its
            # watermark row inherits the source's
            prs = tuple(clone_page_rev(pr, vol, vid)
                        for pr, (_, vid) in zip(prs, outs))
            return (tuple(st for st, _ in outs), prs), outs[0][1]

        b_snap = each(lambda st: dbs.snapshot(st, vol))
        b_unmap = each(
            lambda st: (dbs.unmap(st, vol, page[None]), jnp.int32(-1)))
        b_delete = each(
            lambda st: (dbs.delete_volume(st, vol), jnp.int32(-1)))
        c, val = jax.lax.switch(
            branch, [b_noop, b_snap, b_clone, b_unmap, b_delete], carry)
        return c, val

    (states, page_revs), vals = jax.lax.scan(
        lane, (states, page_revs), (op_w, vol_w, page_w, is_vol_w))
    value = jax.lax.dynamic_update_slice_in_dim(
        value, jnp.where(is_vol_w, vals, sl(value)), start, axis=0)
    # snapshot/clone report failure (table full / dead volume) through a
    # negative result id; unmap/delete are unconditional no-op-on-miss
    signals = is_vol_w & ((op_w == OP_SNAPSHOT) | (op_w == OP_CLONE))
    status = jax.lax.dynamic_update_slice_in_dim(
        status, jnp.where(signals & (vals < 0), ST_ERR, sl(status)),
        start, axis=0)
    return states, page_revs, value, status


def _apply_repl_ops(states, pools, page_revs, healthy, batch: SQE, ok,
                    status):
    """Apply the (at most one — the frontend closes the batch on it)
    FAIL/REBUILD lane against the traced health mask.

    FAIL flips the mask bit unless the target is the shard's last healthy
    replica (→ ST_LAST, mask untouched: an all-failed shard would silently
    ack writes and fabricate zero reads). REBUILD copies the most-up-to-date
    healthy replica's state+pool+watermarks into the target and re-marks it
    healthy (in-band rebuild is a whole-copy — it happens inside one
    program; the host-side *streamed delta* rebuild lives in
    core/replication.py); rebuilding a healthy replica is a protocol error
    (→ ST_HEALTHY). All of it is traced — in-band failover never leaves the
    compiled program."""
    n_rep = len(states)
    is_repl = ok & ((batch.op == OP_FAIL) | (batch.op == OP_REBUILD))
    has = jnp.any(is_repl)
    lane = jnp.argmax(is_repl)                   # first repl lane
    op = batch.op[lane]
    arg = batch.block[lane]                      # replica index rides block
    valid = has & (arg >= 0) & (arg < n_rep)
    tgt = jnp.clip(arg, 0, n_rep - 1)
    h = healthy
    n_h = jnp.sum(h.astype(jnp.int32))
    tgt_h = h[tgt]
    do_fail = valid & (op == OP_FAIL) & (~tgt_h | (n_h > 1))
    rej_last = valid & (op == OP_FAIL) & tgt_h & (n_h <= 1)
    do_rebuild = valid & (op == OP_REBUILD) & ~tgt_h & (n_h >= 1)
    rej_healthy = valid & (op == OP_REBUILD) & tgt_h

    # donor = healthy replica with the highest metadata revision
    revs = jnp.stack([st.revision for st in states])
    donor = jnp.argmax(jnp.where(h, revs, jnp.int32(-(2 ** 31) + 1)))

    def pick(leaves):                            # donor leaf, traced index
        out = leaves[0]
        for r in range(1, n_rep):
            out = jnp.where(donor == r, leaves[r], out)
        return out

    donor_state = jax.tree.map(lambda *ls: pick(ls), *states)
    states = tuple(
        jax.tree.map(lambda cur, d: jnp.where(do_rebuild & (tgt == r), d, cur),
                     st, donor_state)
        for r, st in enumerate(states))
    if pools:
        donor_pool = pick(pools)
        pools = tuple(
            jnp.where(do_rebuild & (tgt == r), donor_pool, p)
            for r, p in enumerate(pools))
    if page_revs:
        donor_pr = pick(page_revs)
        page_revs = tuple(
            jnp.where(do_rebuild & (tgt == r), donor_pr, p)
            for r, p in enumerate(page_revs))

    new_tgt = jnp.where(do_fail, False, jnp.where(do_rebuild, True, tgt_h))
    healthy = h.at[tgt].set(jnp.where(has, new_tgt, tgt_h))
    lane_status = jnp.where(
        rej_last, ST_LAST,
        jnp.where(rej_healthy, ST_HEALTHY,
                  jnp.where(do_fail | do_rebuild, ST_OK, ST_ERR)))
    b_n = batch.op.shape[0]
    status = jnp.where((jnp.arange(b_n) == lane) & has, lane_status, status)
    return states, pools, page_revs, healthy, status


def _write_ack(pool, held, wlane, status, axis: str):
    """The acknowledgement across chips: a write lane completes ``ST_OK``
    only if every replica on the ``axis`` holds it. ``held`` marks the
    lanes this node's replica wrote; the barrier ties it to the pool the
    write kernel returned, so the count summed over the nodes — and the
    status every node emits, node 0's CQE included — exists only once every
    replica's kernel has run. Returns (pool, status)."""
    pool, held = jax.lax.optimization_barrier((pool, held.astype(jnp.int32)))
    with jax.named_scope("replica_ack"):
        n_held = jax.lax.psum(held, axis)
    need = jax.lax.axis_size(axis)
    return pool, jnp.where(wlane & (n_held < need), ST_ERR, status)


def ring_step_core(table: slots.SlotTable, cq: CQ,
                   states: Tuple[dbs.DBSState, ...],
                   pools: Tuple[jnp.ndarray, ...],
                   page_revs: Tuple[jnp.ndarray, ...], batch: SQE,
                   rr: jnp.ndarray, healthy: jnp.ndarray, *,
                   classes: Tuple[str, ...], null_backend: bool = False,
                   null_storage: bool = False, kernel: str = "pallas",
                   compute_tail: int = COMPUTE_TAIL,
                   replica_axis: Optional[str] = None):
    """One ring iteration, un-jitted (vmap-safe over a leading shard axis).

    ``classes`` (static) names the opcode classes present in this batch
    ("read" / "write" / "compute" / "vol" / "repl" / "noop") — the host
    knows them at drain time, so each signature compiles its own program
    and a pure-data batch pays exactly the fused step's cost plus the CQE
    scatter. ``page_revs`` are the per-replica last-write watermarks
    (``transport.stamp_page_rev``), stamped with the write phase and copied
    whole on in-band REBUILD. ``cq.work`` accumulates the extent rows and
    calls of every ``dbs_rw_write`` kernel the step runs. Returns
    ``(table', cq', states', pools', page_revs', healthy', CQEView)``.

    ``replica_axis`` names the mesh axis of replicas on separate chips
    (inside ``shard_map``; RingEngine's ``replica_chips``): the tuples
    then hold this node's one replica, ``healthy`` still covers all R. The
    node writes its own replica, a write lane is acknowledged only once
    every replica holds it (``_write_ack``), and the read lanes'
    data comes back from the serving replica's node (``rr_select``). The
    compute and replica-control phases are not lowered that way (the
    engine refuses those ops at submission).
    """
    table, ids, ok = slots.transact(table, batch.want, batch.volume,
                                    batch.queue, batch.step,
                                    opcodes=batch.op, fnids=batch.fn)
    b_n = batch.op.shape[0]
    status = jnp.zeros((b_n,), jnp.int32)
    value = jnp.full((b_n,), -1, jnp.int32)
    reads = jnp.zeros_like(batch.payload)
    work = jnp.zeros((2,), jnp.int32)

    # replica i of the tuples is replica ``rep[i]`` of the health mask
    rep = (range(len(states)) if replica_axis is None
           else [jax.lax.axis_index(replica_axis)])
    if not null_backend and states:
        if "write" in classes:                   # mirrored CoW data phase
            wmask = ok & (batch.op == OP_WRITE)
            bits = jnp.uint32(1) << batch.block.astype(jnp.uint32)
            out_states, out_pools, out_prs = [], [], []
            for i, st in enumerate(states):
                st, wops = dbs.write_pages(st, batch.volume, batch.page,
                                           bits, wmask & healthy[rep[i]])
                if not null_storage:
                    work = work + _cow_work(pools[i], wops, batch.block,
                                            kernel)
                    out_pools.append(_cow_apply(pools[i], wops,
                                                batch.payload, batch.block,
                                                kernel))
                    out_prs.append(stamp_page_rev(
                        page_revs[i], batch.volume, batch.page, wops.ok,
                        st.revision))
                out_states.append(st)
            if replica_axis is not None and not null_storage:
                out_pools[0], status = _write_ack(
                    out_pools[0], wops.ok & wmask & healthy[rep[0]], wmask,
                    status, replica_axis)
            states = tuple(out_states)
            if not null_storage:
                pools = tuple(out_pools)
                page_revs = tuple(out_prs)
        if "read" in classes and not null_storage:
            rmask = ok & (batch.op == OP_READ)
            if replica_axis is None:
                reads = _rr_gather(states, pools, batch, rr, rmask, reads,
                                   healthy, kernel)
            else:
                # this node's replica serves the batch's reads when it is
                # the round-robin pick; its data returns to every node
                mine = _rr_gather(states, pools, batch, rr, rmask, reads,
                                  None, kernel)
                with jax.named_scope("replica_read_return"):
                    reads = rr_select(mine, replica_axis, rr)
        if "compute" in classes and not null_storage:
            # in-band storage functions: between data and control (the drain
            # never mixes compute with control lanes, so this phase and the
            # control tail are mutually exclusive per batch)
            states, pools, page_revs, value, status, reads, cas_work = (
                apply_compute_ops(states, pools, page_revs, healthy, batch,
                                  ok & (batch.op == OP_COMPUTE), value,
                                  status, reads, kernel=kernel,
                                  tail=compute_tail))
            work = work + cas_work
        if "vol" in classes:                     # lane-ordered control tail
            states, page_revs, value, status = _apply_vol_ops(
                states, page_revs, batch, ok, value, status)
        if "repl" in classes:                    # in-band fail/rebuild
            states, pools, page_revs, healthy, status = _apply_repl_ops(
                states, pools, page_revs, healthy, batch, ok, status)

    latency = (batch.step - batch.tick + 1).astype(jnp.int32)
    # CQE emission: one record per admitted lane, at its slot id
    idx = jnp.where(ok, ids, cq.status.shape[0])
    cq = CQ(status=cq.status.at[idx].set(status, mode="drop"),
            value=cq.value.at[idx].set(value, mode="drop"),
            latency=cq.latency.at[idx].set(latency, mode="drop"),
            payload=cq.payload.at[idx].set(reads, mode="drop"),
            work=cq.work + work)
    # mirror the status into the Messages Array's status lane
    table = dataclasses.replace(
        table, status=table.status.at[idx].set(status, mode="drop"))
    view = CQEView(ok=ok, status=status, value=value, latency=latency,
                   reads=reads)
    return table, cq, states, pools, page_revs, healthy, view


def vmap_shards(fn, n_shards: int):
    """Map ``fn`` over a leading (S,) shard axis. At S=1 the program runs
    unmapped (squeeze/unsqueeze fuse away): vmap's batched-scatter lowering
    only costs there — the same trick EnginePool uses (core/sharded.py)."""
    if n_shards == 1:
        def unmapped(*args):
            sq = lambda t: jax.tree.map(lambda x: x[0], t)
            out = fn(*(sq(a) for a in args))
            return jax.tree.map(lambda x: x[None], out)
        return unmapped
    return lambda *args: jax.vmap(fn)(*args)


# ---------------------------------------------------------------------------
# RingFrontend — THE drain protocol (legacy frontends adapt over it)
# ---------------------------------------------------------------------------
class RingFrontend:
    """S shards × Q admission queues feeding one opcode-tagged SQE drain.

    Requests hash to shards by volume (``volume % S``; replica-control ops
    carry an explicit ``Request.shard``), then to a queue by request id.
    ``drain_ring`` pulls up to ``batch`` requests per shard under the
    batch-ordering contract (module docstring): once a control op is
    drained only further control ops may join that shard's batch, and a
    replica op closes it — so "data phase, then control tail in lane order"
    reproduces submission order exactly.

    The submission tick is stamped on ``Request.tick`` at submit (requeues
    keep the original tick), giving the CQE its latency in pump ticks.
    """

    def __init__(self, n_shards: int, n_queues: int, n_slots: int,
                 batch: int = 64, with_table: bool = True,
                 compute_tail: int = COMPUTE_TAIL):
        self.n_shards = n_shards
        self.n_queues = n_queues
        self.n_slots = n_slots
        self.batch = batch
        self.compute_tail = compute_tail
        self.queues: List[List[collections.deque]] = [
            [collections.deque() for _ in range(n_queues)]
            for _ in range(n_shards)]
        self.table = (slots.make_sharded_table(n_shards, n_slots)
                      if with_table else None)
        self.step: List[int] = [0] * n_shards

    def shard_of(self, req) -> int:
        if getattr(req, "shard", None) is not None:
            return req.shard % self.n_shards
        return req.volume % self.n_shards if req.volume >= 0 else 0

    def submit(self, req) -> None:
        if req.kind not in KIND_TO_OP:
            raise ValueError(f"unknown request kind {req.kind!r} "
                             f"(expected one of {sorted(KIND_TO_OP)})")
        if req.kind == "compute":
            # resolve name -> registry id at the submission boundary (the
            # uniform unknown-name ValueError fires here, not at drain time)
            req.fnid = compute_registry.storage_fn_id(req.fn)
        s = self.shard_of(req)
        req.tick = self.step[s]
        self.queues[s][req.req_id % self.n_queues].append(req)

    def requeue(self, req) -> None:
        """Put a not-admitted request back at the front of its queue (its
        original submission tick is kept, so latency keeps counting)."""
        self.queues[self.shard_of(req)][req.req_id % self.n_queues].appendleft(
            req)

    def requeue_all(self, reqs: Sequence[Any]) -> None:
        """Requeue a completion's not-admitted lanes, back-to-front:
        admission starves the batch SUFFIX (prefix-sum compaction), and an
        appendleft in forward order would reverse the starved lanes'
        relative order in their queues — the ordering contract must survive
        starvation. Every completer funnels through here."""
        for req in reversed(list(reqs)):
            self.requeue(req)

    def depth(self) -> int:
        return sum(len(q) for qs in self.queues for q in qs)

    def _drain_shard(self, s: int, limit: int) -> List[Any]:
        """Round-robin drain of one shard under the batch-ordering contract:
        batch rank is data < compute < control, and the drain cuts on EVERY
        rank change (so compute lanes are contiguous, follow all data lanes,
        and never share a batch with control lanes — the step applies
        data, then the compute window, then the control tail, in lane
        order = submission order). A replica-control op closes the batch;
        at most CTRL_TAIL control / ``compute_tail`` compute ops per batch
        (the step's in-program scan windows), and a *writing* storage
        function closes the compute window so the compute phase commits at
        most one CoW write.

        The drain never exceeds ``n_slots``: with the transact lifecycle a
        pump starts with every slot free, so a batch that fits the slot
        count cannot starve — which is what lets the *pipelined* drain
        launch iteration N+1 before N's completion without a starved
        suffix of N re-entering the queues behind N+1 (out of submission
        order)."""
        reqs: List[Any] = []
        ctrl_seen = False
        comp_seen = False
        comp_closed = False
        n_ctrl = 0
        n_comp = 0
        limit = min(limit, self.n_slots)
        tail = min(CTRL_TAIL, limit)
        ctail = min(self.compute_tail, limit)
        qs = [q for q in self.queues[s] if q]
        while qs and len(reqs) < limit:
            for q in list(qs):
                if not q:
                    qs.remove(q)
                    continue
                k = KIND_CLASS[q[0].kind]
                if ctrl_seen and k not in ("vol", "repl"):
                    return reqs                  # rank downgrade: cut
                if comp_seen and k not in ("compute", "vol", "repl"):
                    return reqs                  # data after compute: cut
                if comp_seen and k in ("vol", "repl"):
                    return reqs                  # compute never joins control
                if k in ("vol", "repl") and n_ctrl >= tail:
                    return reqs                  # control window full
                if k == "compute" and (comp_closed or n_comp >= ctail):
                    return reqs                  # compute window closed/full
                r = q.popleft()
                # provisional latency in pump ticks, stamped at drain (the
                # unified semantics across every comm mode — requeued lanes
                # are re-stamped on their next drain, and the ring path's CQE
                # overwrites with the identical in-program value)
                r.latency = self.step[s] - getattr(r, "tick", 0) + 1
                reqs.append(r)
                if k in ("vol", "repl"):
                    ctrl_seen = True
                    n_ctrl += 1
                if k == "compute":
                    comp_seen = True
                    n_comp += 1
                    if compute_registry.fn_writes(getattr(r, "fnid", 0)):
                        comp_closed = True
                if k == "repl" or len(reqs) >= limit:
                    return reqs
        return reqs

    def _stage(self, payload_shape: Tuple[int, ...] = ()):
        """Drain every shard and fill ONE fresh host buffer for the pump:
        ``staged["packed"]``, int32 (S, W), laid out as ``unpack_sqe``
        reads it. The other entries are numpy views of it under the SQE's
        field names (``payload`` a float32 view, so payloads are copied once
        and keep their bits; ``want`` a bool copy), for the legacy adapters
        (core/frontend.py). The buffer is never reused: an upload may alias
        it. Returns (per-shard request lists, staged dict | None, opcode
        classes)."""
        with TraceAnnotation("ring.admit"):
            drained = [self._drain_shard(s, self.batch)
                       for s in range(self.n_shards)]
        if not any(drained):
            return [], None, set()
        with TraceAnnotation("ring.stage"):
            s_n, b_n = self.n_shards, self.batch
            h = header_words(b_n)
            p_n = int(np.prod(payload_shape, dtype=np.int64))
            buf = np.zeros((s_n, h + b_n * p_n), np.int32)
            stage = {name: buf[:, k * b_n:(k + 1) * b_n]
                     for k, name in enumerate(SQE_LANES)}
            stage["step"] = buf[:, len(SQE_LANES) * b_n]
            stage["payload"] = buf[:, h:].view(np.float32).reshape(
                (s_n, b_n) + tuple(payload_shape))
            classes: Set[str] = set()
            for s, reqs in enumerate(drained):
                stage["step"][s] = self.step[s]
                if reqs:
                    self.step[s] += 1
                for i, r in enumerate(reqs):
                    classes.add(KIND_CLASS[r.kind])
                    stage["want"][s, i] = 1
                    stage["op"][s, i] = KIND_TO_OP[r.kind]
                    stage["volume"][s, i] = (r.volume // s_n
                                             if r.volume >= 0 else -1)
                    stage["page"][s, i] = r.page
                    stage["block"][s, i] = r.block
                    stage["queue"][s, i] = r.req_id % self.n_queues
                    stage["tick"][s, i] = getattr(r, "tick", 0)
                    stage["fn"][s, i] = getattr(r, "fnid", 0)
                    stage["arg"][s, i] = getattr(r, "arg", 0)
                    if r.payload is not None:
                        stage["payload"][s, i] = np.asarray(r.payload)
            stage["want"] = stage["want"] != 0
            stage["packed"] = buf
        return drained, stage, classes

    def drain_ring(self, payload_shape: Tuple[int, ...] = ()):
        """The unified drain: one stacked (S, B, ...) SQE batch per pump,
        sent as ONE host-to-device transfer of ``_stage``'s packed buffer
        (``unpack_sqe`` rebuilds the SQE in the step). A batch whose program
        is the read tier (``RingEngine._canon``) takes no payload, so only
        the header columns go. Returns (per-shard request lists, packed
        device array | None, opcode classes)."""
        drained, st, classes = self._stage(payload_shape)
        if st is None:
            return [], None, set()
        with TraceAnnotation("ring.upload"):
            packed = st["packed"]
            if RingEngine._canon(classes) == ("read",):
                packed = packed[:, :header_words(self.batch)]
            packed = jax.device_put(packed)
        return drained, packed, classes


# ---------------------------------------------------------------------------
# RingEngine — comm="ring": S shards, one opcode-dispatched program per pump
# ---------------------------------------------------------------------------
@dataclass
class PendingRing:
    """Completion handle from ``pump_async``: the step's packed CQE view
    (``pack_cqe``; a device future) plus the host-side request lists that
    rode the batch, and the step's number (``RingEngine.dispatches`` when
    it launched)."""
    reqs: List[List[Any]]
    cqe: jax.Array
    step: int


class RingEngine(ControlDispatch):
    """S engine shards behind the opcode-dispatched ring step.

    API-compatible with ``EnginePool`` (create_volume/snapshot/submit/pump/
    pump_async/drain/completed/read_volume), plus in-band control: snapshot,
    clone, unmap, delete_volume, fail, rebuild are *ring submissions* that
    execute inside the same jitted step as foreground I/O. One compiled
    program exists per (batch geometry, opcode-class signature), jitted
    under the tier's name (``ring_step_read_write``, ``ring_step_read``, ...:
    the name a profiler trace's module line and the compile events show);
    ``trace_counts``/``dispatches`` pin that contract in tests.

    Each pump records host spans (``jax.profiler.TraceAnnotation``, which
    cost next to nothing unless a profiler trace is running): ``ring.admit``
    (the shards' drains), ``ring.stage`` (the one packed host buffer),
    ``ring.upload`` (its single host-to-device transfer, the header alone
    for the read tier), ``ring.dispatch`` (device state, program lookup,
    launch, state hand-back; ``step=`` its number),
    ``ring.fetch`` (the blocking fetch of the step's CQE view, one packed
    device-to-host transfer; ``step=`` the step it fetches) and
    ``ring.deliver`` (per-lane completion, requeues).

    Registered as ``backend="ring"`` in core/backends.py — the only backend
    whose submission path (``data_kinds``) accepts control opcodes.

    ``cfg.replica_chips = R`` (= ``n_replicas``, one shard) puts replica r
    on ``jax.devices()[r]``: the step is lowered through ``shard_map`` over
    the one-axis mesh (``"node"``) of the replica chips, each node runs
    ``ring_step_core`` on its own replica and keeps its own copy of the
    slot table and the CQ (every node runs the same slot transaction on
    the same SQEs). The pump still uploads the packed SQE once, to node 0
    (the engine's chip); the step sends it on to the other nodes over the
    chips' interconnect (``replication.mirror_write``, named scope
    ``replica_fanout``), and the packed CQE view is fetched from node 0
    alone.
    In-band FAIL/REBUILD and COMPUTE are refused at submission there.
    """

    is_pool = True
    data_kinds = frozenset(KIND_TO_OP)

    def __init__(self, cfg):
        if cfg.storage != "dbs":
            raise ValueError("RingEngine requires storage='dbs'")
        s = getattr(cfg, "n_shards", 1)
        if s < 1:
            raise ValueError(f"n_shards must be >= 1, got {s}")
        self.cfg = cfg
        self.n_shards = s
        self.replica_chips = getattr(cfg, "replica_chips", 1)
        devices = self._replica_devices(cfg)
        self._compute_tail = getattr(cfg, "compute_tail", COMPUTE_TAIL)
        self.frontend = RingFrontend(s, cfg.n_queues, cfg.n_slots, cfg.batch,
                                     compute_tail=self._compute_tail)
        if cfg.null_backend:
            self.backend = None
        else:
            self.backend = ShardedReplicaGroup(
                s, cfg.n_replicas, cfg.n_extents, cfg.max_volumes,
                cfg.max_pages, cfg.page_blocks, cfg.payload_shape,
                null_storage=cfg.null_storage, transport=cfg.transport,
                write_policy=cfg.write_policy, read_policy=cfg.read_policy,
                transport_opts=cfg.transport_opts, devices=devices)
        self.cq = make_sharded_cq(s, cfg.n_slots, cfg.payload_shape)
        self.mesh = None if devices is None else self.backend.mesh
        # ops the step is not lowered for on this layout
        self.refused_kinds = frozenset()
        # host totals (no device sync): user bytes owed to the remote
        # replicas, and read lanes a remote replica served
        self.fanout_bytes = 0
        self.remote_read_lanes = 0
        if self.mesh is not None:
            self.refused_kinds = frozenset({"fail", "rebuild", "compute"})
            per_node = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec("node"))
            copies = lambda x: np.tile(np.asarray(x),
                                       (len(devices),) + (1,) * (x.ndim - 1))
            self.frontend.table = jax.device_put(
                jax.tree.map(copies, self.frontend.table), per_node)
            self.cq = jax.device_put(jax.tree.map(copies, self.cq), per_node)
            self._rr_host = 0            # the read cursor, mirrored
            self._sqe_fill: Dict[int, List[Any]] = {}
        from repro.kernels.dbs.registry import resolve_kernel_name
        self._kernel = resolve_kernel_name(cfg)
        self._vol_rr = 0
        self._ctl_seq = 1 << 30      # control-op request ids (own queue slot)
        self.completed = 0
        self.dispatches = 0
        # host totals of the packed SQE uploads (``upload_counters``)
        self.upload_transfers = 0
        self.upload_bytes = 0
        self.upload_payload_skips = 0
        # host totals of the packed CQE fetches (``fetch_counters``)
        self.fetch_transfers = 0
        self.fetch_bytes = 0
        # host totals of CQ.work and the last raw (wrapping) device reading
        self._work = np.zeros(2, np.int64)
        self._work_raw = np.zeros(2, np.int64)
        self.trace_counts: Dict[Tuple[str, ...], int] = {}
        self._steps: Dict[Tuple[str, ...], Any] = {}

    @staticmethod
    def _replica_devices(cfg) -> Optional[List[Any]]:
        """The chips of the replicas (None: all on the default device);
        raises ValueError for a layout the ring cannot lower."""
        chips = getattr(cfg, "replica_chips", 1)
        if chips == 1:
            return None
        if chips != cfg.n_replicas:
            raise ValueError(
                f"replica_chips={chips}: put all replicas on one chip "
                f"(replica_chips=1) or each on its own "
                f"(replica_chips=n_replicas={cfg.n_replicas})")
        if getattr(cfg, "n_shards", 1) != 1:
            raise ValueError("replica_chips > 1 needs n_shards=1 (one "
                             "replica group across the chips)")
        if cfg.null_backend or cfg.null_storage:
            raise ValueError("replica_chips > 1 needs the storage layers "
                             "(null_backend / null_storage off)")
        devices = jax.devices()
        if len(devices) < chips:
            raise ValueError(f"replica_chips={chips} but JAX sees "
                             f"{len(devices)} device(s)")
        return devices[:chips]

    def node0(self, tree):
        """``tree`` as node 0 (the engine's chip) holds it: per-node
        arrays of the replica mesh are cut to node 0's block; on one chip
        ``tree`` itself."""
        if self.mesh is None:
            return tree
        return jax.tree.map(lambda x: x.addressable_data(0), tree)

    def replica_counters(self) -> Dict[str, int]:
        """Traffic between the replica chips since the engine was built
        (host totals, no device sync; 0 with every replica on one chip):
        ``fanout_bytes``, the user bytes owed to the remote replicas —
        written blocks x (R - 1) x block bytes, one byte per payload lane,
        counted when staged; the interconnect carries more, since the step
        sends the whole packed SQE to every remote node (R - 1 times
        ``upload_bytes`` / ``upload_transfers`` per step) — and
        ``remote_read_lanes``, read lanes a replica on another chip than
        node 0 served."""
        return {"fanout_bytes": self.fanout_bytes,
                "remote_read_lanes": self.remote_read_lanes}

    # ------------------------------------------------------------ programs
    @staticmethod
    def _canon(classes: Set[str]) -> Tuple[str, ...]:
        """Canonical program signature for a drained batch. Each tier
        includes the cheaper ones (masked lanes are inert), so at most
        SEVEN programs exist per batch geometry — a mixed workload can't
        trace a program per opcode combination, and heavyweight machinery
        (the control-tail scan, the rebuild pool copy, the storage-function
        switch) is only in the programs that need it. Compute gets its OWN
        tier (the drain never mixes compute with control lanes *within a
        shard*), so the control programs never pay for the full-volume
        content gather — but ``classes`` merges across shards, and one
        pump can drain control on shard 0 while shard 1 drains computes,
        so the control tiers gain compute-including variants for exactly
        that cross-shard mix."""
        if "repl" in classes:
            base = ("read", "repl", "vol", "write")
        elif "vol" in classes:
            base = ("read", "vol", "write")
        elif "compute" in classes:
            return ("compute", "read", "write")
        elif "write" in classes:
            return ("read", "write")
        else:
            return ("read",)
        if "compute" in classes:
            return ("compute",) + base
        return base

    def _get_step(self, classes: Set[str]):
        key = self._canon(classes)
        cache_key = key
        if "compute" in key:
            # compute programs bake the registry's branch table in: a
            # storage fn registered after first compile must retrace
            cache_key = key + (f"sfns:{compute_registry.registry_version()}",)
        if cache_key in self._steps:
            return self._steps[cache_key], key
        self.trace_counts.setdefault(cache_key, 0)
        read_only = key == ("read",)
        unpack = partial(unpack_sqe, b_n=self.frontend.batch,
                         payload_shape=tuple(self.cfg.payload_shape))
        core = partial(ring_step_core, classes=key,
                       null_backend=self.cfg.null_backend,
                       null_storage=self.cfg.null_storage,
                       kernel=self._kernel,
                       compute_tail=self._compute_tail)
        if self.mesh is not None:
            fn = self._mesh_step(key, cache_key, core, unpack)
            self._steps[cache_key] = fn
            return fn, key
        mapped = vmap_shards(core, self.n_shards)

        if read_only:
            # replica state, pools, watermarks and health are inputs only —
            # returning them would materialize pass-through copies
            # (fused_step_read's rationale); only the table and the CQ
            # round-trip.
            def stepped(table, cq, states, pools, page_revs, packed, rr,
                        healthy):
                self.trace_counts[cache_key] += 1
                table, cq, _, _, _, _, view = mapped(
                    table, cq, states, pools, page_revs, unpack(packed), rr,
                    healthy)
                return table, cq, pack_cqe(view)
            donate = (0, 1)
        else:
            def stepped(table, cq, states, pools, page_revs, packed, rr,
                        healthy):
                self.trace_counts[cache_key] += 1
                *out, view = mapped(table, cq, states, pools, page_revs,
                                    unpack(packed), rr, healthy)
                return (*out, pack_cqe(view))
            donate = (0, 1, 2, 3, 4)
        # the tier names the program (jit_ring_step_read_write, ...)
        stepped.__name__ = stepped.__qualname__ = "ring_step_" + "_".join(key)
        fn = jax.jit(stepped, donate_argnums=donate)
        self._steps[cache_key] = fn
        return fn, key

    def _mesh_step(self, key: Tuple[str, ...], cache_key, core, unpack):
        """The tier's program over the replica mesh: ``shard_map`` of one
        node's step. Node 0 holds the uploaded SQE; the other nodes' input
        blocks are placeholders that ``mirror_write`` overwrites with it.
        Every argument and result is split per node (``P("node")``) except
        the read cursor and the health mask, which all nodes share."""
        from jax.sharding import PartitionSpec as P
        read_only = key == ("read",)
        mapped = vmap_shards(partial(core, replica_axis="node"),
                             self.n_shards)
        node = P("node")

        def body(table, cq, state, pool, page_rev, packed, rr, healthy):
            self.trace_counts[cache_key] += 1
            with jax.named_scope("replica_fanout"):
                packed = mirror_write(packed, "node", 0)
            table, cq, (state,), (pool,), (page_rev,), _, view = mapped(
                table, cq, (state,), (pool,), (page_rev,), unpack(packed),
                rr, healthy)
            cqe = pack_cqe(view)
            if read_only:
                return table, cq, cqe
            return table, cq, state, pool, page_rev, cqe

        n_out = 3 if read_only else 6
        stepped = jax.shard_map(
            body, mesh=self.mesh, in_specs=(node,) * 6 + (P(), P()),
            out_specs=(node,) * n_out, check_vma=False)
        stepped.__name__ = stepped.__qualname__ = "ring_step_" + "_".join(key)
        return jax.jit(stepped,
                       donate_argnums=(0, 1) if read_only else (0, 1, 2, 3, 4))

    def _mesh_sqe(self, packed):
        """The uploaded (S, W) SQE as node 0's block of a (R*S, W) array
        split over the mesh; the other nodes' blocks are zeros kept on
        their chips per width (never donated, so allocated once)."""
        w = packed.shape[1]
        fill = self._sqe_fill.get(w)
        if fill is None:
            fill = self._sqe_fill[w] = [
                jax.device_put(np.zeros(packed.shape, np.int32), d)
                for d in self.mesh.devices.flat[1:]]
        parts = [jax.device_put(packed, self.mesh.devices.flat[0])] + fill
        return jax.make_array_from_single_device_arrays(
            (len(parts) * packed.shape[0], w),
            jax.sharding.NamedSharding(self.mesh,
                                       jax.sharding.PartitionSpec("node")),
            parts)

    def _count_replica_traffic(self, reqs) -> None:
        """``replica_counters``' host totals for one dispatched step."""
        n_write = n_read = 0
        for shard_reqs in reqs:
            for r in shard_reqs:
                n_write += r.kind == "write"
                n_read += r.kind == "read"
        n_rep = self.replica_chips
        self.fanout_bytes += (n_write * (n_rep - 1)
                              * int(np.prod(self.cfg.payload_shape)))
        # every replica is healthy on this layout: rr % R serves the reads
        if self._rr_host % n_rep:
            self.remote_read_lanes += n_read
        self._rr_host += 1

    # ------------------------------------------------------------ volumes
    def create_volume(self) -> int:
        """Create a volume on the next shard (round-robin placement);
        global id = local * S + shard, as in EnginePool."""
        shard = self._vol_rr % self.n_shards
        self._vol_rr += 1
        local = 0 if self.backend is None else self.backend.create_volume(shard)
        return local * self.n_shards + shard

    def read_volume(self, vol: int, pages, block_offsets):
        """Host read path for verification (the pump serves reads in-band)."""
        if self.backend is None:
            raise RuntimeError("null backend holds no volumes")
        return self.backend.read(vol % self.n_shards, vol // self.n_shards,
                                 pages, block_offsets)

    # ----------------------------------------------------- in-band control
    def _control(self, kind: str, *, volume: int = -1, page: int = 0,
                 block: int = 0, shard: Optional[int] = None):
        """Submit one control SQE and drain to completion — the synchronous
        convenience wrapper over the in-band path (callers that want control
        ops interleaved with foreground traffic submit Requests directly).

        Matches the host-side controllers' error surface: replica-protocol
        violations raise (like ``ShardedReplicaGroup.fail/rebuild``), while
        failed snapshot/clone report through a negative result id (like
        ``dbs.snapshot``/``ReplicaGroup.clone`` and ``EnginePool.clone``)."""
        from repro.core.frontend import Request
        r = Request(req_id=self._ctl_seq, kind=kind, volume=volume,
                    page=page, block=block, shard=shard)
        self._ctl_seq += 1
        self.submit(r)
        self.drain()
        if r.status == ST_LAST:
            raise RuntimeError(
                f"replica {block} is shard {shard}'s last healthy replica; "
                "failing it would lose the shard's volumes")
        if r.status == ST_HEALTHY:
            raise ValueError(f"shard {shard} replica {block} is healthy; "
                             "only a failed replica can be rebuilt")
        return r.result

    def snapshot(self, vol: int):
        """Freeze the volume head — as a ring submission. Returns the
        (shard-local) snapshot id, -1 on failure (dead volume / table
        full), like the host-side backends."""
        return self._control("snapshot", volume=vol)

    def clone(self, vol: int) -> int:
        """Fork a volume in-band. Returns the new *global* volume id, -1 on
        failure — the same surface as ``EnginePool.clone``."""
        out = self._control("clone", volume=vol)
        return -1 if out is None or out < 0 else out

    def unmap(self, vol: int, pages: Sequence[int]) -> None:
        """TRIM pages in-band (one SQE per page; they share batches)."""
        from repro.core.frontend import Request
        for p in pages:
            r = Request(req_id=self._ctl_seq, kind="unmap", volume=vol,
                        page=int(p))
            self._ctl_seq += 1
            self.submit(r)
        self.drain()

    def delete_volume(self, vol: int) -> None:
        self._control("delete", volume=vol)

    def fail(self, shard: int, replica: int) -> None:
        """In-band replica failover (raises like the host-side controller
        on protocol violations, from the CQE status)."""
        if self.backend is not None:
            self.backend._check(shard, replica)
        self._control("fail", shard=shard, block=replica)

    def rebuild(self, shard: int, replica: int) -> None:
        if self.backend is not None:
            self.backend._check(shard, replica)
        self._control("rebuild", shard=shard, block=replica)

    # -------------------------------------------------- backend protocol
    @property
    def storage(self):
        """The replica storage behind this backend (core/backends.py)."""
        return self.backend

    def _control_repl(self, kind, shard, replica):
        # in-band FAIL/REBUILD SQEs (ControlDispatch.control routes here)
        fn = self.fail if kind == "fail" else self.rebuild
        return fn(shard, replica)

    def depth(self) -> int:
        return self.frontend.depth()

    # ------------------------------------------------------------- pumping
    def refuse(self, kind: str) -> None:
        """Raise ValueError for a kind of op this layout does not run."""
        if kind in self.refused_kinds:
            raise ValueError(
                f"kind={kind!r} is not supported with the replicas on "
                f"separate chips (replica_chips={self.replica_chips}) yet")

    def submit(self, req) -> None:
        if req.kind not in self.data_kinds:
            raise ValueError(f"unknown request kind {req.kind!r} "
                             f"(expected one of {sorted(self.data_kinds)})")
        self.refuse(req.kind)
        self.frontend.submit(req)

    def pump_async(self) -> Optional[PendingRing]:
        """Admit one opcode-tagged batch per shard and launch the ring step;
        do NOT block. Control lanes execute inside the same program as the
        data lanes — no host dispatch per control op."""
        reqs, packed, classes = self.frontend.drain_ring(
            self.cfg.payload_shape)
        if packed is None:
            return None
        self.upload_transfers += 1
        self.upload_bytes += packed.nbytes
        self.upload_payload_skips += (
            packed.shape[1] == header_words(self.frontend.batch))
        self.dispatches += 1
        if self.mesh is not None:
            return self._pump_mesh(reqs, packed, classes)
        with TraceAnnotation("ring.dispatch", step=self.dispatches):
            if self.backend is None:
                states, pools, page_revs = (), (), ()
                healthy = jnp.ones((self.n_shards, 1), bool)
                rr = jnp.zeros((self.n_shards,), jnp.int32)
            else:
                states, pools, healthy = self.backend.device_state()
                page_revs = self.backend.device_page_revs()
                rr = self.backend.bump_rr()
            step, key = self._get_step(classes)
            if key == ("read",):
                table, cq, cqe = step(self.frontend.table, self.cq, states,
                                      pools, page_revs, packed, rr, healthy)
            else:
                table, cq, states, pools, page_revs, healthy, cqe = step(
                    self.frontend.table, self.cq, states, pools, page_revs,
                    packed, rr, healthy)
                if self.backend is not None:
                    self.backend.set_device_state(states, pools)
                    self.backend.set_device_page_revs(page_revs)
                    if "repl" in key:
                        # only the repl program can change health; adopting
                        # on every pump would mark the host mirror stale and
                        # make each .healthy access pay a device sync for
                        # nothing
                        self.backend.adopt_health(healthy)
            self.frontend.table = table
            self.cq = cq
        return PendingRing(reqs=reqs, cqe=cqe, step=self.dispatches)

    def _pump_mesh(self, reqs, packed, classes) -> PendingRing:
        """``pump_async``'s dispatch on the replica mesh: the launch on the
        replicas' global arrays (``ShardedReplicaGroup.node_arrays``),
        which take the step's outputs as they are, and node 0's packed CQE
        view kept for the fetch."""
        self._count_replica_traffic(reqs)
        with TraceAnnotation("ring.dispatch", step=self.dispatches):
            backend = self.backend
            rr = backend.bump_rr()
            step, key = self._get_step(classes)
            args = (self.frontend.table, self.cq, *backend.node_arrays,
                    self._mesh_sqe(packed), rr, backend.device_health())
            if key == ("read",):
                table, cq, cqe = step(*args)
            else:
                table, cq, *arrays, cqe = step(*args)
                backend.node_arrays = tuple(arrays)
            self.frontend.table = table
            self.cq = cq
            cqe = self.node0(cqe)
        return PendingRing(reqs=reqs, cqe=cqe, step=self.dispatches)

    def _complete(self, p: PendingRing) -> int:
        """The pump's single host hop: fetch the packed per-lane CQE view
        (one device-to-host transfer), deliver result/status/latency,
        requeue not-admitted requests."""
        with TraceAnnotation("ring.fetch", step=p.step):
            buf = jax.device_get(p.cqe)
        self.fetch_transfers += 1
        self.fetch_bytes += buf.nbytes
        with TraceAnnotation("ring.deliver"):
            v = unpack_cqe(buf, self.cfg.payload_shape)
            ok, status, value, latency, reads = (v.ok, v.status, v.value,
                                                 v.latency, v.reads)
            done = 0
            requeues = []
            for s, shard_reqs in enumerate(p.reqs):
                for i, r in enumerate(shard_reqs):
                    if not ok[s][i]:
                        requeues.append(r)
                        continue
                    r.status = int(status[s][i])
                    r.latency = int(latency[s][i])
                    if r.kind == "read":
                        r.result = reads[s, i]
                    elif r.kind == "snapshot":
                        r.result = int(value[s][i])
                    elif r.kind == "clone":
                        local = int(value[s][i])
                        r.result = (local * self.n_shards + s if local >= 0
                                    else -1)
                    elif r.kind == "compute":
                        # (scalar result, CQ payload lanes) — blockdev wraps
                        r.result = (int(value[s][i]), reads[s, i])
                    done += 1
            self.frontend.requeue_all(requeues)
            self.completed += done
        return done

    def work_counters(self) -> Dict[str, int]:
        """The ``dbs_rw_write`` kernel's traffic since the engine was built,
        summed over shards and replicas: ``write_rows`` (extent-row fetches
        plus row write-backs its grid's index maps requested, a row that
        repeats the previous grid step's counting none; each row is
        ``page_blocks`` blocks of the pool's dtype) and
        ``write_kernel_calls`` (one payload fetch each). The device keeps
        int32 totals in ``CQ.work`` that wrap; this reads them (a device
        sync) and adds the deltas modulo 2**32 to host totals, so call it at
        least once per 2**31 rows."""
        raw = np.asarray(jax.device_get(self.cq.work), np.int64)
        raw = raw.reshape(-1, 2).sum(axis=0) % (1 << 32)
        self._work += (raw - self._work_raw) % (1 << 32)
        self._work_raw = raw
        return {"write_rows": int(self._work[0]),
                "write_kernel_calls": int(self._work[1])}

    def upload_counters(self) -> Dict[str, int]:
        """The packed SQE uploads since the engine was built (host totals,
        no device sync): ``upload_transfers`` (one per dispatched step),
        ``upload_bytes`` and ``upload_payload_skips`` (read-tier uploads
        that left the payload out)."""
        return {"upload_transfers": self.upload_transfers,
                "upload_bytes": self.upload_bytes,
                "upload_payload_skips": self.upload_payload_skips}

    def fetch_counters(self) -> Dict[str, int]:
        """The packed CQE fetches since the engine was built (host totals,
        no device sync): ``fetch_transfers`` (one device-to-host transfer
        per completed step) and ``fetch_bytes``."""
        return {"fetch_transfers": self.fetch_transfers,
                "fetch_bytes": self.fetch_bytes}

    def pump(self) -> int:
        p = self.pump_async()
        return self._complete(p) if p is not None else 0

    def drain(self, max_iters: int = 100_000) -> int:
        """Pipelined drain: launch iteration N+1 before blocking on N
        (EnginePool's double-buffered completion)."""
        total = 0
        pending: Optional[PendingRing] = None
        for _ in range(max_iters):
            nxt = self.pump_async()
            if pending is not None:
                total += self._complete(pending)
            pending = nxt
            if nxt is None and self.frontend.depth() == 0:
                break
        if pending is not None:
            total += self._complete(pending)
        return total
