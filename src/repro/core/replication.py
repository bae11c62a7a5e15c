"""Controller<->replica layer: write/read policies over a pluggable transport.

Paper §III: "Each write is replicated to all replicas, and each read is
served by one replica in round robin fashion"; the controller detects a
faulty replica and rebuilds it from the most up-to-date copy, using the
per-replica metadata "version" to establish consistency.

Since the transport redesign this module holds the **controller-side
policy objects**; the wire itself lives in core/transport.py:

- every replica is a *transport endpoint* (``transport.Replica`` /
  ``transport.StackedReplica``) reached only through opcode-tagged
  ``WireMsg`` messages over a registered ``ReplicaTransport``
  (local | device | simnet — ``EngineConfig.transport``),
- **write policies** decide when a mirrored write completes: ``all``
  (every healthy replica acked — the paper's default and bit-identical to
  the pre-transport path), ``quorum`` (a majority acked; stragglers catch
  up via per-link FIFO), ``async`` (write-behind: posted everywhere,
  acked immediately),
- **read policies** pick the serving replica: ``rr`` (round-robin, the
  paper's default) or ``latency`` (lowest observed link latency, queue
  depth as tiebreak),
- **rebuild is a streamed delta** through the same transport: the target
  reports its per-page revision watermarks (the endpoint's ``page_rev``
  array, stamped by ``transport.stamp_page_rev`` — held next to the
  ``DBSState``, not inside it), the
  donor computes which extents back newer pages, and only those pool rows
  cross the wire in bounded chunks (WATERMARKS -> FETCH_DELTA ->
  FETCH_PAGES/PUSH_PAGES -> ADOPT_META) — replacing the old
  whole-pool ``jnp.copy``. Transport counters (``pages_moved``) make the
  saving assertable.

Two planes, as before:

- **host-orchestrated replicas** (``ReplicaGroup``): R endpoints behind R
  transports — the loop/slots engines' storage, where the policies bite.
- **mesh collectives** (``mirror_write`` / ``rr_select``): the same
  write-to-all / read-one pattern expressed inside shard_map.

The fused engine step (core/fused.py) threads the endpoint pytrees exposed
by ``device_state``/``set_device_state`` through one compiled program —
mirroring and round-robin selection then happen inside that program, so on
the fused/sharded/ring engines the transport carries control and rebuild
traffic only (and those engines require the in-program policies:
``write_policy="all"``, ``read_policy="rr"``). ``ShardedReplicaGroup``
stacks S such groups along a leading shard axis on ``StackedReplica``
endpoints for the vmapped pool step in core/sharded.py. See
docs/ARCHITECTURE.md.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dbs
from repro.core.transport import (MSG_ADOPT_META, MSG_CLONE, MSG_CREATE,
                                  MSG_DELETE, MSG_FETCH_DELTA,
                                  MSG_FETCH_PAGES, MSG_PUSH_PAGES,
                                  MSG_QUERY_REV, MSG_READ, MSG_SNAPSHOT,
                                  MSG_UNMAP, MSG_WATERMARKS, MSG_WRITE,
                                  MsgFuture, Replica, ReplicaTransport,
                                  StackedReplica, WireMsg, make_transport)

WRITE_POLICIES = ("all", "quorum", "async")
READ_POLICIES = ("rr", "latency")

# extents per rebuild-stream message: bounds the transfer unit so a rebuild
# interleaves with (simulated) foreground traffic instead of one giant copy
REBUILD_CHUNK = 64


def _check_policies(write_policy: str, read_policy: str) -> None:
    if write_policy not in WRITE_POLICIES:
        raise ValueError(f"unknown write_policy {write_policy!r} "
                         f"(expected one of {WRITE_POLICIES})")
    if read_policy not in READ_POLICIES:
        raise ValueError(f"unknown read_policy {read_policy!r} "
                         f"(expected one of {READ_POLICIES})")


def _transport_opts(opts: Optional[Dict[str, Any]], i: int) -> Dict[str, Any]:
    """Per-replica view of the transport options: a list/tuple value is
    indexed per replica (e.g. ``latency=[1, 1, 6]`` — a straggler link), a
    scalar is shared. A *scalar* ``seed`` decorrelates as ``seed + i`` so
    replicas don't drop/reorder in lock-step; an explicit seed list is
    taken verbatim (``seed=[42, 42]`` really pins identical streams)."""
    opts = opts or {}
    out = {k: (v[i] if isinstance(v, (list, tuple)) else v)
           for k, v in opts.items()}
    if isinstance(opts.get("seed"), int):
        out["seed"] += i
    return out


class _Waiter:
    """Controller-side plumbing shared by both replica groups.

    ``_await`` is the completion-wait loop: tick the undelivered futures'
    transports until ``need`` of them have completed (all by default).
    In-process transports deliver at post time, so the loop body never
    runs there. ``wait_ticks`` accumulates the controller-observed wait
    time in simulated ticks — the quantity the write/read policies trade
    (benchmarks/ladder.py ``run_replication`` reports it).

    ``_delta_rebuild`` is THE rebuild wire sequence (flat and sharded
    groups differ only in donor selection and the ``shard`` address):
    target WATERMARKS -> donor FETCH_DELTA (only extents backing pages
    newer than the target's per-page watermarks) -> chunked
    FETCH_PAGES/PUSH_PAGES streams (``pages_moved`` counts them) ->
    ADOPT_META commit."""

    wait_ticks: int = 0
    null_storage: bool = False
    rebuild_chunk: int = REBUILD_CHUNK

    def _await(self, futs: Sequence[MsgFuture],
               need: Optional[int] = None) -> None:
        need = len(futs) if need is None else need
        for _ in range(ReplicaTransport.MAX_WAIT_TICKS):
            if sum(f.done for f in futs) >= need:
                return
            for f in futs:
                if not f.done:
                    f.transport.tick()
            self.wait_ticks += 1
        raise RuntimeError("replica transports livelocked "
                           f"({sum(f.done for f in futs)}/{need} delivered)")

    def _delta_rebuild(self, donor_t, tgt_t,
                       shard: Optional[int] = None) -> None:
        wm = tgt_t.call(WireMsg(op=MSG_WATERMARKS, shard=shard))
        ext_ids, meta = donor_t.call(
            WireMsg(op=MSG_FETCH_DELTA, meta=wm, shard=shard))
        if not self.null_storage:
            for lo in range(0, len(ext_ids), self.rebuild_chunk):
                chunk = jnp.asarray(ext_ids[lo:lo + self.rebuild_chunk])
                rows = donor_t.call(WireMsg(op=MSG_FETCH_PAGES,
                                            extents=chunk, shard=shard))
                tgt_t.call(WireMsg(op=MSG_PUSH_PAGES, extents=chunk,
                                   payload=rows, shard=shard))
        tgt_t.call(WireMsg(op=MSG_ADOPT_META, meta=meta, shard=shard))


# ---------------------------------------------------------------------------
# host-orchestrated replica group (the controller-side policy object)
# ---------------------------------------------------------------------------
class ReplicaGroup(_Waiter):
    """The controller's backend: mirrors control+data ops across replica
    transports under the configured write/read policies."""

    def __init__(self, n_replicas: int, n_extents: int, max_volumes: int,
                 max_pages: int, page_blocks: int, payload_shape=(4,),
                 dtype=jnp.float32, null_storage: bool = False,
                 transport: str = "local", write_policy: str = "all",
                 read_policy: str = "rr",
                 transport_opts: Optional[Dict[str, Any]] = None,
                 rebuild_chunk: int = REBUILD_CHUNK):
        _check_policies(write_policy, read_policy)
        self.null_storage = null_storage
        self.page_blocks = page_blocks
        self.write_policy = write_policy
        self.read_policy = read_policy
        self.transport_name = transport
        self.rebuild_chunk = rebuild_chunk
        # pools carry ONE extra extent row past the allocator's range: the
        # fused CoW kernel's masked-lane dump (dbs_copy_pool scratch=True),
        # which keeps the kernel input/output-aliased with no pool copies.
        # dbs.make_state only ever hands out extents < n_extents.
        self.replicas: List[Replica] = [
            Replica(state=dbs.make_state(n_extents, max_volumes, max_pages),
                    pool=jnp.zeros(
                        (n_extents + 1, page_blocks) + tuple(payload_shape),
                        dtype),
                    page_rev=jnp.zeros((max_volumes, max_pages), jnp.int32),
                    null_storage=null_storage)
            for _ in range(n_replicas)]
        self.transports = [
            make_transport(transport, r, **_transport_opts(transport_opts, i))
            for i, r in enumerate(self.replicas)]
        self._rr = 0

    # -- control plane: mirrored to every healthy replica ---------------------
    def _mirror_ctl(self, op: int, **kw) -> Any:
        """Post one control message to every healthy replica and wait for
        all acks (control ops always fence — a snapshot acked by some
        replicas only would diverge the mirror). Returns the first reply
        value (mirrored ops agree by construction)."""
        msg = WireMsg(op=op, **kw)
        futs = [t.post(msg) for t, r in zip(self.transports, self.replicas)
                if r.healthy]
        self._await(futs)
        return next((f.value for f in futs if f.value is not None), None)

    def create_volume(self) -> int:
        return int(self._mirror_ctl(MSG_CREATE))

    def snapshot(self, vol: int) -> int:
        return int(self._mirror_ctl(MSG_SNAPSHOT, volume=vol))

    def clone(self, vol: int) -> int:
        return int(self._mirror_ctl(MSG_CLONE, volume=vol))

    def unmap(self, vol: int, pages: jnp.ndarray) -> None:
        self._mirror_ctl(MSG_UNMAP, volume=vol,
                         pages=jnp.asarray(pages, jnp.int32))

    def delete_volume(self, vol: int) -> None:
        self._mirror_ctl(MSG_DELETE, volume=vol)

    # -- fused data plane (core/fused.py) ------------------------------------
    def healthy_indices(self) -> List[int]:
        return [i for i, r in enumerate(self.replicas) if r.healthy]

    def device_state(self):
        """(states, pools) tuples for every healthy replica — the pytrees the
        fused engine step threads through one compiled program. Nothing is
        fetched: these are device-resident endpoint arrays (the transport is
        bypassed by design here — the step IS the data plane). With
        ``null_storage`` the pools are withheld (fused_step never touches
        them)."""
        idx = self.healthy_indices()
        states = tuple(self.replicas[i].state for i in idx)
        if self.null_storage:
            return states, ()
        return states, tuple(self.replicas[i].pool for i in idx)

    def set_device_state(self, states, pools) -> None:
        """Write back the fused step's outputs (healthy replicas, in the
        order ``device_state`` returned them)."""
        idx = self.healthy_indices()
        for i, st in zip(idx, states):
            self.replicas[i].state = st
        for i, pool in zip(idx, pools):
            self.replicas[i].pool = pool

    def device_page_revs(self):
        """Per-replica last-write watermark arrays for the fused step to
        stamp in-program (healthy replicas, ``device_state`` order; empty
        with ``null_storage`` — no data plane, nothing to delta-rebuild)."""
        if self.null_storage:
            return ()
        return tuple(self.replicas[i].page_rev
                     for i in self.healthy_indices())

    def set_device_page_revs(self, page_revs) -> None:
        for i, pr in zip(self.healthy_indices(), page_revs):
            self.replicas[i].page_rev = pr

    def bump_rr(self) -> int:
        """Advance and return the round-robin read cursor (shared with the
        unfused ``read`` path so interleaving the two stays fair)."""
        rr = self._rr
        self._rr += 1
        return rr

    # -- data plane ----------------------------------------------------------
    def write(self, vol, pages: jnp.ndarray, block_offsets: jnp.ndarray,
              payload: jnp.ndarray, mask=None) -> None:
        """Mirror a batch of block writes to every healthy replica, then
        complete per the write policy:

        - ``all``: every replica acked (paper: every write creates multiple
          messages that all must execute before completion),
        - ``quorum``: a majority acked; the rest are in flight and deliver
          on later ticks (per-link FIFO keeps each replica's history
          prefix-ordered, so a subsequent read through any link still
          observes that link's full submission history),
        - ``async``: write-behind — acked at post time.
        """
        bits = (jnp.uint32(1) << block_offsets.astype(jnp.uint32))
        vol = jnp.asarray(vol, jnp.int32)
        if mask is None:
            mask = jnp.ones(pages.shape, bool)
        msg = WireMsg(op=MSG_WRITE, volume=vol, pages=pages,
                      blocks=block_offsets, bits=bits, payload=payload,
                      mask=mask)
        futs = [t.post(msg) for t, r in zip(self.transports, self.replicas)
                if r.healthy]
        if self.write_policy == "all":
            self._await(futs)
        elif self.write_policy == "quorum":
            self._await(futs, need=len(futs) // 2 + 1)
        # "async": fire-and-forget; acks land on later ticks / drain

    def _pick_replica(self) -> int:
        """Read-policy replica selection over the healthy set."""
        n = len(self.replicas)
        if self.read_policy == "latency":
            rr = self._rr
            self._rr += 1
            healthy = self.healthy_indices()
            if not healthy:
                raise RuntimeError("no healthy replica")
            # lowest observed link latency; queue depth then the rr cursor
            # break ties (so equal links still round-robin fairly)
            return min(healthy, key=lambda i: (
                self.transports[i].latency_ewma,
                self.transports[i].pending(), (i - rr) % n))
        order = [(self._rr + i) % n for i in range(n)]
        self._rr += 1
        for i in order:
            if self.replicas[i].healthy:
                return i
        raise RuntimeError("no healthy replica")

    def read(self, vol, pages: jnp.ndarray, block_offsets: jnp.ndarray
             ) -> jnp.ndarray:
        """Policy-selected read from one healthy replica. vol: scalar or
        (B,). The read rides the chosen replica's link *behind* any of its
        in-flight writes (FIFO), so it observes that replica's full
        submission history even under quorum/async write policies."""
        if self.null_storage:
            # no replica serves anything: no resolve dispatch AND no rr
            # cursor burn (the layer-cut row must not skew the read
            # distribution real replicas would see — ChainedReplicas.read
            # holds the same contract)
            for r in self.replicas:
                if r.healthy:
                    return jnp.zeros((pages.shape[0],) + r.pool.shape[2:],
                                     r.pool.dtype)
            raise RuntimeError("no healthy replica")
        i = self._pick_replica()
        fut = self.transports[i].post(
            WireMsg(op=MSG_READ, volume=jnp.asarray(vol, jnp.int32),
                    pages=pages, blocks=block_offsets))
        self._await([fut])
        return fut.value

    def drain_transports(self) -> None:
        """Deliver everything still in flight on every link (write-behind
        and quorum stragglers)."""
        for t in self.transports:
            t.drain()

    # -- fault handling ------------------------------------------------------
    def _check_index(self, idx: int) -> None:
        if not 0 <= idx < len(self.replicas):
            raise IndexError(f"replica index {idx} out of range "
                             f"[0, {len(self.replicas)})")

    def fail(self, idx: int) -> None:
        """Mark a replica faulty and tear down its link (undelivered
        messages to a dead replica are lost; rebuild resyncs whatever
        landed). The controller never declares the LAST healthy replica
        dead — that is volume loss, not failover — so a group must keep one
        serving copy (paper §III: reads/writes continue on the surviving
        replicas while the failed one rebuilds)."""
        self._check_index(idx)
        survivors = [r for i, r in enumerate(self.replicas)
                     if r.healthy and i != idx]
        if self.replicas[idx].healthy and not survivors:
            raise RuntimeError(f"replica {idx} is the last healthy replica; "
                               "failing it would lose the volume")
        self.replicas[idx].healthy = False
        self.transports[idx].cancel_pending()

    def consistent(self) -> bool:
        """Healthy replicas agree on the metadata revision. The per-replica
        revision queries ride the links (behind any in-flight writes) and
        the device scalars come back in ONE ``device_get``."""
        futs = [t.post(WireMsg(op=MSG_QUERY_REV))
                for t, r in zip(self.transports, self.replicas) if r.healthy]
        self._await(futs)
        revs = jax.device_get(tuple(f.value for f in futs))
        return len({int(r) for r in revs}) == 1

    def rebuild(self, idx: int) -> None:
        """Restore a failed replica by STREAMING the delta from the most
        up-to-date healthy copy through the transport:

        1. the target reports its per-page revision watermarks (frozen at
           fail time — it stopped receiving writes),
        2. the donor (healthy, highest revision) computes which extents
           back pages newer than those watermarks,
        3. only those pool rows cross the wire, in ``rebuild_chunk``-sized
           messages (FETCH_PAGES -> PUSH_PAGES; ``pages_moved`` counts
           them),
        4. the donor's metadata state is adopted wholesale (it is tiny next
           to the pool — the paper's engine also syncs metadata cheaply and
           streams data), committing the rebuild.

        Unchanged pages need no transfer: healthy replicas execute
        identical op sequences, so the target's pre-fail extents are
        bit-identical to the donor's. Rebuilding a replica the controller
        never marked faulty is a protocol error (the paper's controller
        only schedules rebuilds for failed replicas), as is naming a
        replica that doesn't exist."""
        self._check_index(idx)
        tgt = self.replicas[idx]
        if tgt.healthy:
            raise ValueError(f"replica {idx} is healthy; only a failed "
                             "replica can be rebuilt")
        donors = self.healthy_indices()
        if not donors:
            raise RuntimeError("no healthy replica to rebuild from")
        futs = [self.transports[i].post(WireMsg(op=MSG_QUERY_REV))
                for i in donors]
        self._await(futs)
        revs = jax.device_get(tuple(f.value for f in futs))
        donor_t = self.transports[donors[int(np.argmax(
            [int(r) for r in revs]))]]
        self._delta_rebuild(donor_t, self.transports[idx])
        tgt.healthy = True


# ---------------------------------------------------------------------------
# sharded replica groups (the EnginePool backend, core/sharded.py)
# ---------------------------------------------------------------------------
class ShardedReplicaGroup(_Waiter):
    """S independent replica groups stacked along a leading shard axis.

    Each of R replicas is ONE ``StackedReplica`` transport endpoint whose
    leaves carry a leading (S,) dimension — shard ``s``'s replica ``r`` is
    ``states[r][s]`` — so the vmapped engine step (core/sharded.py) serves
    every shard's mirrored writes and round-robin reads in a single
    compiled program (the transport carries control and rebuild traffic;
    the in-program data plane mandates ``write_policy="all"`` /
    ``read_policy="rr"``). Because vmap cannot vary pytree *structure* per
    shard, replica health is a dense (S, R) bool mask threaded through the
    step as a traced argument rather than the host-side filtering
    ``ReplicaGroup.device_state`` does: a failed replica's shard slice
    simply stops receiving writes and serving reads until ``rebuild`` — a
    per-shard streamed delta through the replica's transport.

    The round-robin read cursors are a device-resident (S,) array bumped
    with a device add — no host sync on the pump path.

    With ``devices`` (one per replica), ``mesh`` is the one-axis
    (``"node"``) mesh over them that the ring step is lowered on
    (core/ring.py), and the replicas are held as ``node_arrays``: (states,
    pools, page_revs), each leaf one global array split ``P("node")`` whose
    node r block is replica r's (S, ...) leaf on ``devices[r]``. The ring
    step takes and returns them as they are. Replica r's endpoint reads and
    writes node r's blocks (``_NodeReplica``), so control messages run on
    each replica's own chip; the read cursors and the health mask are
    replicated over the mesh, so bumping the cursor moves nothing between
    chips.
    """

    def __init__(self, n_shards: int, n_replicas: int, n_extents: int,
                 max_volumes: int, max_pages: int, page_blocks: int,
                 payload_shape=(4,), dtype=jnp.float32,
                 null_storage: bool = False, transport: str = "device",
                 write_policy: str = "all", read_policy: str = "rr",
                 transport_opts: Optional[Dict[str, Any]] = None,
                 rebuild_chunk: int = REBUILD_CHUNK,
                 devices: Optional[Sequence[Any]] = None):
        _check_policies(write_policy, read_policy)   # unknown names first
        if write_policy != "all" or read_policy != "rr":
            raise ValueError(
                "the sharded data plane mirrors writes and round-robins "
                "reads INSIDE the compiled step; write_policy="
                f"{write_policy!r}/read_policy={read_policy!r} need a "
                "host-dispatch backend (loop | slots)")
        self.n_shards = n_shards
        self.n_replicas = n_replicas
        self.null_storage = null_storage
        self.page_blocks = page_blocks
        self.rebuild_chunk = rebuild_chunk
        # "local" names the in-process call semantics; on stacked endpoints
        # that IS the device transport
        self.transport_name = "device" if transport == "local" else transport
        stack = lambda x: jnp.tile(x[None], (n_shards,) + (1,) * x.ndim)
        if devices is not None and len(devices) != n_replicas:
            raise ValueError(f"{len(devices)} devices for {n_replicas} "
                             "replicas: give one device per replica")
        self.mesh = (None if devices is None else jax.sharding.Mesh(
            np.asarray(devices), ("node",)))
        self.node_arrays: Optional[tuple] = None

        def arrays():
            # one extra extent row per pool: the fused CoW kernel's
            # masked-lane dump (same convention as ReplicaGroup)
            return (jax.tree.map(stack, dbs.make_state(
                        n_extents, max_volumes, max_pages)),
                    jnp.zeros((n_shards, n_extents + 1, page_blocks)
                              + tuple(payload_shape), dtype),
                    jnp.zeros((n_shards, max_volumes, max_pages), jnp.int32))

        if devices is None:
            endpoints = [StackedReplica(*arrays(), null_storage=null_storage)
                         for _ in range(n_replicas)]
        else:
            per = [_on_device(d, arrays) for d in devices]
            self.node_arrays = jax.tree.map(
                lambda *blocks: _node_global(self.mesh, blocks), *per)
            endpoints = [_NodeReplica(self, r, null_storage)
                         for r in range(n_replicas)]
        self.transports = [
            make_transport(self.transport_name, ep,
                           **_transport_opts(transport_opts, i))
            for i, ep in enumerate(endpoints)]
        self._healthy_np = np.ones((n_shards, n_replicas), bool)
        self._healthy_dev: Optional[jnp.ndarray] = None   # device-mask cache
        self._healthy_stale = False   # device mask newer than the np mirror
        self._rr = self._replicated(np.zeros((n_shards,), np.int32))

    def _replicated(self, x) -> jnp.ndarray:
        """``x`` on the default device, or replicated over the mesh."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(x, jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()))

    # -- the stacked endpoint pytrees (legacy .states/.pools surface) --------
    @property
    def states(self) -> List[dbs.DBSState]:
        return [t.endpoint.state for t in self.transports]

    @property
    def pools(self) -> List[jnp.ndarray]:
        return [t.endpoint.pool for t in self.transports]

    @property
    def healthy(self) -> np.ndarray:
        """Host-side (S, R) health mirror. After in-band FAIL/REBUILD ops
        (core/ring.py) the *device* mask is authoritative; the mirror
        refreshes lazily here — host control paths pay the sync, never the
        pump."""
        if self._healthy_stale:
            self._healthy_np = np.asarray(jax.device_get(self._healthy_dev))
            self._healthy_stale = False
        return self._healthy_np

    def adopt_health(self, mask: jnp.ndarray) -> None:
        """Adopt the ring step's returned health mask (device-resident;
        in-band fail/rebuild mutated it inside the compiled program)."""
        self._healthy_dev = mask
        self._healthy_stale = True

    # -- control plane (wire messages to every replica; rare ops) ------------
    def _mirror_ctl(self, shard: int, op: int, **kw) -> Any:
        """Post one shard-addressed control message to EVERY replica
        (healthy or not: a failed replica is overwritten wholesale by
        ``rebuild``, and keeping all R slices in lock-step means rebuild
        can adopt metadata without replaying control ops). Returns replica
        0's reply."""
        msg = WireMsg(op=op, shard=shard, **kw)
        futs = [t.post(msg) for t in self.transports]
        self._await(futs)
        return futs[0].value

    def create_volume(self, shard: int) -> int:
        return int(jax.device_get(self._mirror_ctl(shard, MSG_CREATE)))

    def snapshot(self, shard: int, vol: int) -> int:
        return int(jax.device_get(
            self._mirror_ctl(shard, MSG_SNAPSHOT, volume=vol)))

    def clone(self, shard: int, vol: int) -> int:
        return int(jax.device_get(
            self._mirror_ctl(shard, MSG_CLONE, volume=vol)))

    def unmap(self, shard: int, vol: int, pages: jnp.ndarray) -> None:
        self._mirror_ctl(shard, MSG_UNMAP, volume=vol,
                         pages=jnp.asarray(pages, jnp.int32))

    def delete_volume(self, shard: int, vol: int) -> None:
        self._mirror_ctl(shard, MSG_DELETE, volume=vol)

    # -- fused data plane ----------------------------------------------------
    def device_state(self):
        """(states, pools, healthy): R stacked replica pytrees, R stacked
        pools, and the dense (S, R) health mask — the arguments the vmapped
        engine step threads through one compiled program. The mask's device
        copy is cached (health changes only on fail/rebuild control ops) so
        the pump path pays no per-iteration host-to-device transfer."""
        pools = () if self.null_storage else tuple(self.pools)
        return tuple(self.states), pools, self.device_health()

    def device_health(self) -> jnp.ndarray:
        """The (S, R) health mask on the device (cached)."""
        if self._healthy_dev is None:
            self._healthy_dev = self._replicated(self.healthy)
        return self._healthy_dev

    def set_device_state(self, states, pools) -> None:
        if self.mesh is not None:
            # every block at once: the caller may have donated the old ones
            glob = lambda *blocks: _node_global(self.mesh, blocks)
            self.node_arrays = (jax.tree.map(glob, *states), glob(*pools),
                                self.node_arrays[2])
            return
        for t, st in zip(self.transports, states):
            t.endpoint.state = st
        if pools:
            for t, p in zip(self.transports, pools):
                t.endpoint.pool = p

    def device_page_revs(self):
        """Per-replica stacked (S, V, P) watermark arrays for the vmapped
        step to stamp in-program (empty with ``null_storage``)."""
        if self.null_storage:
            return ()
        return tuple(t.endpoint.page_rev for t in self.transports)

    def set_device_page_revs(self, page_revs) -> None:
        for t, pr in zip(self.transports, page_revs):
            t.endpoint.page_rev = pr

    def bump_rr(self) -> jnp.ndarray:
        """Return the (S,) read cursors and advance them — a device-side add,
        so the pump path never syncs on the cursor."""
        rr = self._rr
        self._rr = rr + 1
        return rr

    # -- host read path (verification / non-pump readback) -------------------
    def read(self, shard: int, vol: int, pages: jnp.ndarray,
             block_offsets: jnp.ndarray) -> jnp.ndarray:
        """Read a batch from one healthy replica of ``shard`` (host path,
        used by tests and tooling — the pump serves reads in-program)."""
        for r in range(self.n_replicas):
            if not self.healthy[shard, r]:
                continue
            ep = self.transports[r].endpoint
            if self.null_storage:
                return jnp.zeros((pages.shape[0],) + ep.pool.shape[3:],
                                 ep.pool.dtype)
            fut = self.transports[r].post(
                WireMsg(op=MSG_READ, shard=shard,
                        volume=jnp.asarray(vol, jnp.int32), pages=pages,
                        blocks=block_offsets))
            self._await([fut])
            return fut.value
        raise RuntimeError(f"no healthy replica in shard {shard}")

    def drain_transports(self) -> None:
        for t in self.transports:
            t.drain()

    # -- fault handling (per shard) ------------------------------------------
    def _check(self, shard: int, replica: int) -> None:
        if not 0 <= shard < self.n_shards:
            raise IndexError(f"shard index {shard} out of range "
                             f"[0, {self.n_shards})")
        if not 0 <= replica < self.n_replicas:
            raise IndexError(f"replica index {replica} out of range "
                             f"[0, {self.n_replicas})")

    def fail(self, shard: int, replica: int) -> None:
        """Mark one shard's replica faulty. Refuses to fail the shard's
        last healthy replica (same controller semantics as
        ``ReplicaGroup.fail``): in the vmapped step an all-failed shard
        would silently drop writes and fabricate zero reads, since lane
        completion flags only track slot admission."""
        self._check(shard, replica)
        if self.healthy[shard, replica] and self.healthy[shard].sum() == 1:
            raise RuntimeError(
                f"replica {replica} is shard {shard}'s last healthy "
                "replica; failing it would lose the shard's volumes")
        self.healthy[shard, replica] = False
        self._healthy_dev = None

    def rebuild(self, shard: int, replica: int) -> None:
        """Restore shard ``shard``'s replica ``replica`` from the shard's
        most up-to-date healthy copy — the same streamed per-page-watermark
        delta as ``ReplicaGroup.rebuild``, scoped to one shard's slice and
        carried by the replica's transport."""
        self._check(shard, replica)
        if self.healthy[shard, replica]:
            raise ValueError(f"shard {shard} replica {replica} is healthy; "
                             "only a failed replica can be rebuilt")
        donors = [r for r in range(self.n_replicas) if self.healthy[shard, r]]
        if not donors:
            raise RuntimeError(f"no healthy replica in shard {shard} "
                               "to rebuild from")
        futs = [self.transports[r].post(WireMsg(op=MSG_QUERY_REV))
                for r in donors]
        self._await(futs)
        revs = jax.device_get(tuple(f.value for f in futs))   # each (S,)
        donor_t = self.transports[donors[int(np.argmax(
            [np.asarray(r)[shard] for r in revs]))]]
        self._delta_rebuild(donor_t, self.transports[replica], shard=shard)
        self.healthy[shard, replica] = True
        self._healthy_dev = None

    def consistent(self, shard: Optional[int] = None) -> bool:
        """Healthy replicas of a shard (or of every shard) agree on the
        metadata revision — every replica's stacked (S,) revision vector
        queried over its link, fetched in ONE ``device_get``."""
        futs = [t.post(WireMsg(op=MSG_QUERY_REV)) for t in self.transports]
        self._await(futs)
        revs = [np.asarray(r) for r in
                jax.device_get(tuple(f.value for f in futs))]
        shards = range(self.n_shards) if shard is None else [shard]
        for s in shards:
            vals = {int(revs[r][s]) for r in range(self.n_replicas)
                    if self.healthy[s, r]}
            if len(vals) > 1:
                return False
        return True


def _on_device(device, make):
    """Build ``make()``'s arrays directly on ``device`` (committed there),
    so a pool is never allocated on the default device first."""
    with jax.default_device(device):
        return jax.device_put(make(), device)


def _node_global(mesh, blocks) -> jax.Array:
    """One global array over ``mesh``, split ``P("node")``, whose node r
    block is ``blocks[r]`` (already on node r's device; no copy)."""
    return jax.make_array_from_single_device_arrays(
        (len(blocks) * blocks[0].shape[0],) + blocks[0].shape[1:],
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("node")),
        list(blocks))


class _NodeReplica(StackedReplica):
    """Replica ``r`` of a ``ShardedReplicaGroup`` whose replicas lie on
    separate chips: its state, pool and watermarks are node r's blocks of
    the group's ``node_arrays`` (views, no copy), and setting one of them
    replaces node r's block, the other nodes' blocks kept as they are."""

    def __init__(self, group: ShardedReplicaGroup, r: int,
                 null_storage: bool):
        self._group, self._r = group, r
        self.null_storage = null_storage

    def _block(self, i: int):
        return jax.tree.map(lambda x: x.addressable_data(self._r),
                            self._group.node_arrays[i])

    def _put(self, i: int, value) -> None:
        g = self._group

        def swap(glob, new):
            blocks = [glob.addressable_data(n) for n in range(g.n_replicas)]
            blocks[self._r] = new
            return _node_global(g.mesh, blocks)
        arrays = list(g.node_arrays)
        arrays[i] = jax.tree.map(swap, arrays[i], value)
        g.node_arrays = tuple(arrays)

    state = property(lambda self: self._block(0),
                     lambda self, v: self._put(0, v))
    pool = property(lambda self: self._block(1),
                    lambda self, v: self._put(1, v))
    page_rev = property(lambda self: self._block(2),
                        lambda self, v: self._put(2, v))


# ---------------------------------------------------------------------------
# mesh-collective forms (used inside shard_map; the ring step over replicas
# on separate chips, core/ring.py)
# ---------------------------------------------------------------------------
def mirror_write(x: jnp.ndarray, axis: str, src_index: int = 0) -> jnp.ndarray:
    """Broadcast a written value from ``src_index`` to all replicas on an
    axis — write-to-all as a collective: one point-to-point permute per
    destination (a permute may name each source once), bit for bit."""
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    out = x
    for j in range(n):
        if j != src_index:
            got = jax.lax.ppermute(x, axis, [(src_index, j)])
            out = jnp.where(me == j, got, out)
    return out


def rr_select(x: jnp.ndarray, axis: str, step: jnp.ndarray) -> jnp.ndarray:
    """Read-one-of-N: replica (step % N) contributes, others send zeros; a
    psum delivers the chosen replica's value everywhere. 32-bit values are
    summed as integers, so the value arrives bit for bit."""
    n = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    chosen = (step % n) == me
    mine = jnp.where(chosen, x, jnp.zeros_like(x))
    if x.dtype.itemsize != 4:
        return jax.lax.psum(mine, axis)
    bits = jax.lax.bitcast_convert_type(mine, jnp.int32)
    return jax.lax.bitcast_convert_type(jax.lax.psum(bits, axis), x.dtype)
