"""The fused device-resident engine step (see docs/ARCHITECTURE.md).

The paper removes per-request host hops from Longhorn's I/O path three ways:
a multi-queue ublk frontend, the restructured slot-array protocol, and the
direct-to-disk DBS store. ``engine.Engine`` reproduces each layer, but its
``pump()`` still crosses the host *between* layers every batch: slot ids are
``device_get``'d out of admission, and the write path dispatches separate
jitted programs for control-plane resolution, CoW data movement, and reads.

``fused_step`` is the jax analogue of fusing the whole protocol: ONE compiled
program per batch geometry performs

    slot admission  ->  write_pages control-plane resolution (per replica)
                    ->  CoW copies + payload stores, mirrored across all
                        replicas (a REGISTERED KERNEL, kernels/dbs: the
                        ``dbs_rw`` Pallas scatter, or the XLA reference)
                    ->  round-robin read gathers (the same kernel's read)
                    ->  slot retirement

with no intermediate ``device_get``. The host's only jobs are moving raw
request arrays in (``MultiQueueFrontend.drain_batch``) and completed
payloads out (one ``device_get`` at completion). Admission state — the
``SlotTable``, every replica ``DBSState``, and the payload pools — stays on
device across ``pump()`` iterations.

The unfused multi-call path survives as the ladder's ``comm="slots"``
baseline; the benchmark column ``+fused`` measures exactly this change.

``step_core``/``step_core_read`` are the un-jitted bodies, written to be
``jax.vmap``-safe over a leading *shard* axis (core/sharded.py stacks S
independent engines and dispatches one vmapped program for all of them).
Vmap-safety is why they take an optional **traced** ``healthy`` mask: under
vmap the round-robin cursor and the per-replica health bits differ per
shard, so replica selection cannot be a Python-level branch (the host-side
filtering ``ReplicaGroup.device_state`` does for the single-engine path).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import dbs, slots
from repro.core.transport import stamp_page_rev
from repro.kernels.dbs.ops import dbs_rw_write_rows
from repro.kernels.dbs.registry import make_kernel


@jax.tree_util.register_dataclass
@dataclass
class FusedBatch:
    """Fixed-shape admitted-request batch: the raw arrays the host moves in.

    All lane arrays are (B,) with inert padding lanes marked want=False, so
    one program compiles per (B, payload) geometry regardless of how many
    requests actually arrived — the Messages-Array idiom end to end.
    """
    want: jnp.ndarray       # (B,) bool  lane carries a real request
    is_write: jnp.ndarray   # (B,) bool  write (True) vs read (False)
    volume: jnp.ndarray     # (B,) int32
    page: jnp.ndarray       # (B,) int32
    block: jnp.ndarray      # (B,) int32 block offset within the page
    payload: jnp.ndarray    # (B, *payload) write payloads (zeros for reads)
    queue: jnp.ndarray      # (B,) int32 admission queue per lane
    step: jnp.ndarray       # ()   int32 admission step (fairness/arrival)


def _cow_apply(pool, ops: dbs.WriteOps, payload, block_offsets, kernel: str):
    """Data plane of a mirrored write batch — CoW extent copies + payload
    block stores — dispatched through the KERNEL REGISTRY (kernels/dbs):
    ``kernel`` names a registered ``DBSKernel`` (``pallas`` — the dbs_rw
    write kernel owns the whole plane; ``xla`` — apply_write_ops, the old
    ``cow="ref"`` path; ``ref`` — pure-jnp row composition; ``copy`` — the
    PR-3 dbs_copy + XLA-scatter hybrid). All entries assume the engine pool
    convention: ReplicaGroup pools carry one extra extent row past the
    allocator's range as the masked-lane dump, so the Pallas paths stay
    fully input/output-aliased (no concat/slice copies of the pool)."""
    return make_kernel(kernel).write(pool, ops, payload, block_offsets)


def _cow_work(pool, ops: dbs.WriteOps, block_offsets, kernel: str):
    """What one ``_cow_apply`` call adds to the ring's ``CQ.work`` counters:
    int32 ``(extent rows moved, kernel calls)`` of the ``dbs_rw_write``
    kernel, which only the ``pallas`` entry runs (zeros for the others)."""
    if kernel != "pallas":
        return jnp.zeros((2,), jnp.int32)
    return jnp.stack([dbs_rw_write_rows(pool, ops, block_offsets),
                      jnp.int32(1)])


def step_core(table: slots.SlotTable, states: Tuple[dbs.DBSState, ...],
              pools: Tuple[jnp.ndarray, ...],
              page_revs: Tuple[jnp.ndarray, ...], batch: FusedBatch,
              rr: jnp.ndarray, healthy=None, *, null_backend: bool = False,
              null_storage: bool = False, kernel: str = "pallas"):
    """The fused controller iteration, un-jitted (vmap-safe over shards).

    ``healthy``: None for the single-engine path (the caller passes only
    healthy replicas — ``ReplicaGroup.device_state``), or a traced (R,) bool
    mask over a *fixed* replica tuple. With the mask, writes mirror only to
    healthy replicas and reads round-robin over the healthy subset — the
    form core/sharded.py vmaps, where health differs per shard and cannot
    change the pytree structure.

    ``page_revs``: one (V, P) last-write watermark array per replica
    (``transport.stamp_page_rev``), stamped alongside the mirrored writes
    so the streamed delta rebuild (core/replication.py) works after
    in-program traffic; () with ``null_storage``.
    """
    table, ids, ok = slots.transact(table, batch.want, batch.volume,
                                    batch.queue, batch.step)
    reads = jnp.zeros_like(batch.payload)
    if null_backend or not states:
        return table, states, pools, page_revs, ok, reads

    wmask = ok & batch.is_write
    bits = jnp.uint32(1) << batch.block.astype(jnp.uint32)
    out_states, out_pools, out_prs = [], [], []
    for i, st in enumerate(states):            # mirrored write-to-all
        m = wmask if healthy is None else wmask & healthy[i]
        st, wops = dbs.write_pages(st, batch.volume, batch.page, bits, m)
        if not null_storage:
            out_pools.append(_cow_apply(pools[i], wops, batch.payload,
                                        batch.block, kernel))
            out_prs.append(stamp_page_rev(page_revs[i], batch.volume,
                                          batch.page, wops.ok, st.revision))
        out_states.append(st)

    if not null_storage:
        reads = _rr_gather(out_states, out_pools, batch, rr,
                           ok & ~batch.is_write, reads, healthy, kernel)
    return (table, tuple(out_states), tuple(out_pools), tuple(out_prs), ok,
            reads)


@partial(jax.jit, static_argnames=("null_backend", "null_storage", "kernel"),
         donate_argnums=(0, 1, 2, 3))
def fused_step(table: slots.SlotTable, states: Tuple[dbs.DBSState, ...],
               pools: Tuple[jnp.ndarray, ...],
               page_revs: Tuple[jnp.ndarray, ...], batch: FusedBatch,
               rr: jnp.ndarray, *, null_backend: bool = False,
               null_storage: bool = False, kernel: str = "pallas"):
    """One whole controller iteration as a single compiled program.

    states/pools/page_revs: one entry per healthy replica (writes are
    mirrored to all of them; reads gather from replica ``rr % R``; the
    per-page watermarks stamp with the writes). With ``null_storage`` the
    pools are untouched — pass ``pools=()``/``page_revs=()`` so the (large)
    payload arrays never enter the program at all. Returns
    ``(table', states', pools', page_revs', ok (B,) bool,
    reads (B, *payload))`` — ``ok`` marks lanes that were admitted (and
    therefore completed), and ``reads`` carries gathered payloads on read
    lanes, zeros elsewhere.

    The table, replica states, pools and watermarks are DONATED: the engine
    replaces its references with the returned pytrees every pump, so XLA
    updates the (large) pools in place instead of copying them through each
    step — callers must not touch the passed-in arrays afterwards.
    """
    return step_core(table, states, pools, page_revs, batch, rr,
                     null_backend=null_backend, null_storage=null_storage,
                     kernel=kernel)


def _rr_gather(states, pools, batch, rr, rmask, reads, healthy=None,
               kernel: str = "xla"):
    """Round-robin read: resolve + gather from replica ``rr % R``.

    ``healthy=None``: all replicas serve; ``lax.switch`` executes exactly one
    branch (one resolve + one gather per batch — the cheap single-engine
    form). With a traced ``healthy`` mask: reads come from the (rr mod H)-th
    *healthy* replica, selected with a rank-compare one-hot — every replica
    is gathered and the selection is a ``where`` chain, which is what makes
    this form vmap-safe (and is no extra cost under vmap, where a batched
    switch would execute all branches anyway).

    The gather itself is the registry ``kernel``'s ``read``: holes
    (ext < 0: never-written or unmapped pages) read as ZEROS — without the
    mask a clamped gather would leak extent 0's payload (sparse-file
    semantics; core/blockdev.py relies on this for byte-level equivalence
    with a zero-filled device).
    """
    kern = make_kernel(kernel)
    if healthy is None:
        def _read_from(i):
            def branch(_):
                ext = dbs.read_resolve(states[i], batch.volume, batch.page)
                return kern.read(pools[i], ext, batch.block)
            return branch
        vals = jax.lax.switch(rr % len(states),
                              [_read_from(i) for i in range(len(states))], 0)
    else:
        h = healthy.astype(jnp.int32)
        target = rr % jnp.maximum(jnp.sum(h), 1)
        sel = healthy & (jnp.cumsum(h) - 1 == target)    # (R,) one-hot
        vals = jnp.zeros_like(reads)
        for i in range(len(states)):
            ext = dbs.read_resolve(states[i], batch.volume, batch.page)
            vals = jnp.where(sel[i], kern.read(pools[i], ext, batch.block),
                             vals)
    return jnp.where(rmask.reshape(rmask.shape + (1,) * (vals.ndim - 1)),
                     vals, reads)


def step_core_read(table: slots.SlotTable,
                   states: Tuple[dbs.DBSState, ...],
                   pools: Tuple[jnp.ndarray, ...], batch: FusedBatch,
                   rr: jnp.ndarray, healthy=None, *,
                   null_backend: bool = False, null_storage: bool = False,
                   kernel: str = "xla"):
    """``step_core`` specialised to batches with no write lanes (un-jitted,
    vmap-safe; replica state and pools are inputs only)."""
    table, ids, ok = slots.transact(table, batch.want, batch.volume,
                                    batch.queue, batch.step)
    reads = jnp.zeros_like(batch.payload)
    if null_backend or null_storage or not states:
        return table, ok, reads
    return table, ok, _rr_gather(states, pools, batch, rr,
                                 ok & ~batch.is_write, reads, healthy,
                                 kernel)


@partial(jax.jit, static_argnames=("null_backend", "null_storage", "kernel"),
         donate_argnums=(0,))
def fused_step_read(table: slots.SlotTable, states: Tuple[dbs.DBSState, ...],
                    pools: Tuple[jnp.ndarray, ...], batch: FusedBatch,
                    rr: jnp.ndarray, *, null_backend: bool = False,
                    null_storage: bool = False, kernel: str = "xla"):
    """``fused_step`` specialised to batches with no write lanes.

    Replica state and pools are read-only here, so they are inputs only
    (and NOT donated — they stay live across read-only pumps) — returning
    them would force XLA to materialise pass-through copies of the (large)
    pools every batch, which is exactly the cost the unfused read path
    never pays. Only the slot table is donated. Returns
    ``(table', ok, reads)``.
    """
    return step_core_read(table, states, pools, batch, rr,
                          null_backend=null_backend,
                          null_storage=null_storage, kernel=kernel)


# ---------------------------------------------------------------------------
# tiered variants: the same step + per-extent access stamps for the spill
# tier (repro/durability/tier.py). The stamps array is (E+1,) int32 — row E
# is the dump slot invalid lanes scatter into — and every extent a batch
# resolves (read extents, write destinations AND CoW sources) is stamped
# with the batch step INSIDE the same program, so the clock/second-chance
# eviction sweep needs no extra device round-trip on the hot path.
# ---------------------------------------------------------------------------
def _stamp_tier(stamps, state, batch: FusedBatch, ok, cow_src=None):
    """Stamp the batch's resolved extents with the admission step.

    ``state`` is the POST-write replica-0 state, so write lanes resolve to
    their freshly allocated/CoW'd destination extents; ``cow_src`` (the
    write ops' CoW sources, pre-write extents) is stamped too — a CoW read
    is an access. Invalid lanes clamp to the dump row E, which is zeroed
    back so it never looks hot."""
    dump = stamps.shape[0] - 1
    ext = dbs.read_resolve(state, batch.volume, batch.page)
    idx = jnp.where(ok & (ext >= 0), ext, dump)
    stamps = stamps.at[idx].max(batch.step)
    if cow_src is not None:
        src = jnp.where(ok & batch.is_write & (cow_src >= 0), cow_src, dump)
        stamps = stamps.at[src].max(batch.step)
    return stamps.at[dump].set(0)


def step_core_tiered(table: slots.SlotTable,
                     states: Tuple[dbs.DBSState, ...],
                     pools: Tuple[jnp.ndarray, ...],
                     page_revs: Tuple[jnp.ndarray, ...],
                     stamps: jnp.ndarray, batch: FusedBatch,
                     rr: jnp.ndarray, *, kernel: str = "pallas"):
    """``step_core`` + tier stamping (un-jitted). The tier needs the real
    storage plane, so there are no null_backend/null_storage forms."""
    table, ids, ok = slots.transact(table, batch.want, batch.volume,
                                    batch.queue, batch.step)
    reads = jnp.zeros_like(batch.payload)
    wmask = ok & batch.is_write
    bits = jnp.uint32(1) << batch.block.astype(jnp.uint32)
    out_states, out_pools, out_prs = [], [], []
    cow_src = None
    for i, st in enumerate(states):            # mirrored write-to-all
        st, wops = dbs.write_pages(st, batch.volume, batch.page, bits, wmask)
        if cow_src is None:
            cow_src = wops.cow_src             # replicas agree (mirror-all)
        out_pools.append(_cow_apply(pools[i], wops, batch.payload,
                                    batch.block, kernel))
        out_prs.append(stamp_page_rev(page_revs[i], batch.volume,
                                      batch.page, wops.ok, st.revision))
        out_states.append(st)
    stamps = _stamp_tier(stamps, out_states[0], batch, ok, cow_src)
    reads = _rr_gather(out_states, out_pools, batch, rr,
                       ok & ~batch.is_write, reads, None, kernel)
    return (table, tuple(out_states), tuple(out_pools), tuple(out_prs),
            stamps, ok, reads)


@partial(jax.jit, static_argnames=("kernel",),
         donate_argnums=(0, 1, 2, 3, 4))
def fused_step_tiered(table: slots.SlotTable,
                      states: Tuple[dbs.DBSState, ...],
                      pools: Tuple[jnp.ndarray, ...],
                      page_revs: Tuple[jnp.ndarray, ...],
                      stamps: jnp.ndarray, batch: FusedBatch,
                      rr: jnp.ndarray, *, kernel: str = "pallas"):
    """``fused_step`` with the tier's access stamps threaded through — still
    ONE compiled program per batch geometry; the stamps ride the donation
    list like the other per-pump state."""
    return step_core_tiered(table, states, pools, page_revs, stamps, batch,
                            rr, kernel=kernel)


def step_core_read_tiered(table: slots.SlotTable,
                          states: Tuple[dbs.DBSState, ...],
                          pools: Tuple[jnp.ndarray, ...],
                          stamps: jnp.ndarray, batch: FusedBatch,
                          rr: jnp.ndarray, *, kernel: str = "xla"):
    table, ids, ok = slots.transact(table, batch.want, batch.volume,
                                    batch.queue, batch.step)
    reads = jnp.zeros_like(batch.payload)
    stamps = _stamp_tier(stamps, states[0], batch, ok, None)
    reads = _rr_gather(states, pools, batch, rr, ok & ~batch.is_write,
                       reads, None, kernel)
    return table, stamps, ok, reads


@partial(jax.jit, static_argnames=("kernel",), donate_argnums=(0, 3))
def fused_step_read_tiered(table: slots.SlotTable,
                           states: Tuple[dbs.DBSState, ...],
                           pools: Tuple[jnp.ndarray, ...],
                           stamps: jnp.ndarray, batch: FusedBatch,
                           rr: jnp.ndarray, *, kernel: str = "xla"):
    """``fused_step_read`` + tier stamping: states/pools stay inputs-only,
    the slot table and the stamps are donated."""
    return step_core_read_tiered(table, states, pools, stamps, batch, rr,
                                 kernel=kernel)
