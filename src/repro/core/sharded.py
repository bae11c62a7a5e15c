"""EnginePool: S engine shards served by ONE vmapped fused step, pipelined.

The fused step (core/fused.py) removed the host from a single engine's
datapath; this module removes the *per-engine dispatch* from a fleet of
them. Real Longhorn nodes serve many volumes concurrently — one engine
process per volume — and the survey literature on user-space storage
(PAPERS.md) identifies per-tenant scale-out plus submission/completion
overlap as the step after single-path optimization. Here:

- **Shard axis.** S independent engine shards — each its own Messages
  Array (SlotTable), its own R mirrored replica DBS states, payload pools
  and round-robin cursor — are stacked along a leading (S,) axis
  (slots.make_sharded_table, replication.ShardedReplicaGroup). Volumes
  hash to shards (``volume % S``); a volume lives entirely on one shard.
- **One program per pump.** ``jax.vmap`` over the shard axis turns the
  fused step into a single compiled program that performs admission ->
  CoW write -> mirrored store -> rr read -> retire for ALL S shards per
  dispatch. Per-shard divergence that used to be Python-level (the rr
  replica choice, replica health) is traced: health is a dense (S, R)
  mask and rr a (S,) device array (see fused.step_core).
- **Pipelined pump.** ``pump_async`` launches the sharded step and
  returns a completion handle without blocking: JAX's async dispatch
  keeps the device busy while the host returns immediately. ``drain``
  double-buffers completions — it admits and launches iteration N+1
  *before* performing the single blocking ``device_get`` for iteration N,
  so the host-side drain/stack of N+1 overlaps N's device execution.

``EngineConfig(comm="sharded", n_shards=S)`` routes ``Engine`` through a
pool; ``benchmarks/table3_shards.py`` measures throughput vs S and
``benchmarks/ladder.py`` carries the cumulative ``+sharded`` column.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.control import ControlDispatch
from repro.core.frontend import Request, ShardedFrontend
from repro.core.fused import FusedBatch, step_core, step_core_read
from repro.core.replication import ShardedReplicaGroup
from repro.core.ring import vmap_shards


@dataclass
class PendingPump:
    """Completion handle from ``pump_async``: device futures for one
    in-flight sharded step plus the host-side request lists that rode it.
    ``EnginePool._complete`` resolves it with the pump's single device_get."""
    reqs: List[List[Request]]      # per shard, aligned with batch lanes
    ok: jnp.ndarray                # (S, B) bool (device future)
    reads: jnp.ndarray             # (S, B, *payload) (device future)


class EnginePool(ControlDispatch):
    """S engine shards behind one vmapped fused step with a pipelined pump.

    API-compatible with ``Engine`` for the ladder/tests surface
    (create_volume/snapshot/submit/pump/drain/completed), plus
    ``pump_async`` and per-shard failover via ``backend.fail(shard, r)`` /
    ``backend.rebuild(shard, r)``.

    ``trace_counts`` records how many times each step variant was traced
    (i.e. how many distinct compiled programs exist) and ``dispatches`` how
    many pump launches they served — the "one compiled program serves all S
    shards per pump" contract, pinned by tests/test_sharded.py.

    Registered as ``backend="sharded"`` in core/backends.py: the submission
    path carries data ops only (``data_kinds``); control ops go host-side
    through ``control()`` between pumps.
    """

    is_pool = True
    data_kinds = frozenset({"read", "write"})

    def __init__(self, cfg, n_shards: Optional[int] = None):
        self.cfg = cfg
        s = n_shards if n_shards is not None else getattr(cfg, "n_shards", 1)
        if s < 1:
            raise ValueError(f"n_shards must be >= 1, got {s}")
        if cfg.storage != "dbs":
            raise ValueError("EnginePool requires storage='dbs'")
        self.n_shards = s
        self.frontend = ShardedFrontend(s, cfg.n_queues, cfg.n_slots,
                                        cfg.batch)
        if cfg.null_backend:
            self.backend = None
        else:
            self.backend = ShardedReplicaGroup(
                s, cfg.n_replicas, cfg.n_extents, cfg.max_volumes,
                cfg.max_pages, cfg.page_blocks, cfg.payload_shape,
                null_storage=cfg.null_storage, transport=cfg.transport,
                write_policy=cfg.write_policy, read_policy=cfg.read_policy,
                transport_opts=cfg.transport_opts)
        from repro.kernels.dbs.registry import resolve_kernel_name
        self._kernel = resolve_kernel_name(cfg)
        self._vol_rr = 0
        self.completed = 0
        self.dispatches = 0
        self.trace_counts = {"step": 0, "step_read": 0}
        self._step = self._build_step(read_only=False)
        self._step_read = self._build_step(read_only=True)

    def _build_step(self, *, read_only: bool):
        """The pool's single compiled program (per batch geometry): the
        fused step vmapped over the leading shard axis. The trace counter
        bumps only while tracing, so it counts compiled programs, not
        dispatches.

        Donation mirrors fused_step/fused_step_read: the stacked slot
        table (and, on the write path, the stacked replica states/pools)
        are replaced by the outputs every pump, so XLA updates the big
        (S, E, ...) pools in place instead of round-tripping copies."""
        kw = dict(null_backend=self.cfg.null_backend,
                  null_storage=self.cfg.null_storage)
        # same program, unmapped at S=1: vmap only buys the worse batched-
        # scatter lowering there (ring.vmap_shards, shared with RingEngine)
        if read_only:
            mapped = vmap_shards(partial(step_core_read,
                                         kernel=self._kernel, **kw),
                                 self.n_shards)

            def stepped(table, states, pools, batch, rr, healthy):
                self.trace_counts["step_read"] += 1
                return mapped(table, states, pools, batch, rr, healthy)
            return jax.jit(stepped, donate_argnums=(0,))

        mapped = vmap_shards(partial(step_core, kernel=self._kernel, **kw),
                             self.n_shards)

        def stepped(table, states, pools, page_revs, batch, rr, healthy):
            self.trace_counts["step"] += 1
            return mapped(table, states, pools, page_revs, batch, rr,
                          healthy)
        return jax.jit(stepped, donate_argnums=(0, 1, 2, 3))

    # ------------------------------------------------------------ volumes
    def create_volume(self) -> int:
        """Create a volume on the next shard (round-robin placement).
        Returns a *global* volume id encoding its shard: ``local * S +
        shard`` — so ``gid % S`` recovers the shard and ``gid // S`` the
        shard-local id the device-side DBS states use."""
        shard = self._vol_rr % self.n_shards
        self._vol_rr += 1
        local = 0 if self.backend is None else self.backend.create_volume(shard)
        return local * self.n_shards + shard

    def snapshot(self, vol: int):
        """Freeze the volume head. Returns the (shard-local) snapshot id,
        -1 on failure — the same surface as RingEngine.snapshot."""
        if self.backend is None:
            return None
        return self.backend.snapshot(vol % self.n_shards,
                                     vol // self.n_shards)

    def clone(self, vol: int) -> int:
        """Fork a volume on its shard. Returns the new global volume id."""
        if self.backend is None:
            return -1
        shard = vol % self.n_shards
        local = self.backend.clone(shard, vol // self.n_shards)
        return local * self.n_shards + shard if local >= 0 else -1

    def unmap(self, vol: int, pages) -> None:
        if self.backend is not None:
            self.backend.unmap(vol % self.n_shards, vol // self.n_shards,
                               pages)

    def delete_volume(self, vol: int) -> None:
        if self.backend is not None:
            self.backend.delete_volume(vol % self.n_shards,
                                       vol // self.n_shards)

    def read_volume(self, vol: int, pages: jnp.ndarray,
                    block_offsets: jnp.ndarray) -> jnp.ndarray:
        """Host read path for verification (the pump serves reads in-program)."""
        if self.backend is None:
            raise RuntimeError("null backend holds no volumes")
        return self.backend.read(vol % self.n_shards, vol // self.n_shards,
                                 pages, block_offsets)

    # -------------------------------------------------- backend protocol
    @property
    def storage(self):
        """The replica storage behind this backend (core/backends.py).
        Every control op here is a host-side call between pumps — the
        fence the ring backend exists to remove (ControlDispatch)."""
        return self.backend

    def _control_repl(self, kind, shard, replica):
        if self.backend is None:
            raise RuntimeError("null backend holds no replicas")
        fn = self.backend.fail if kind == "fail" else self.backend.rebuild
        return fn(shard, replica)

    def depth(self) -> int:
        return self.frontend.depth()

    # ------------------------------------------------------------- pumping
    def submit(self, req: Request) -> None:
        if req.kind not in self.data_kinds:
            raise ValueError(
                f"kind={req.kind!r} requests need backend='ring' (the "
                "opcode-tagged SQ/CQ path); this backend carries data ops "
                "only — use control() for host-side control ops")
        self.frontend.submit(req)

    def pump_async(self) -> Optional[PendingPump]:
        """Admit one batch per shard and launch the sharded step; do NOT
        block on results. Returns a PendingPump (or None if no traffic).
        JAX async dispatch returns futures immediately, so the caller can
        keep draining/admitting while the device executes."""
        reqs, batch = self.frontend.drain_sharded(self.cfg.payload_shape)
        if batch is None:
            return None
        if self.backend is None:
            states, pools, page_revs = (), (), ()
            healthy = jnp.ones((self.n_shards, 1), bool)
            rr = jnp.zeros((self.n_shards,), jnp.int32)
        else:
            states, pools, healthy = self.backend.device_state()
            page_revs = self.backend.device_page_revs()
            rr = self.backend.bump_rr()
        self.dispatches += 1
        if any(r.kind == "write" for rs in reqs for r in rs):
            table, states, pools, page_revs, ok, reads = self._step(
                self.frontend.table, states, pools, page_revs, batch, rr,
                healthy)
            if self.backend is not None:
                self.backend.set_device_state(states, pools)
                self.backend.set_device_page_revs(page_revs)
        else:
            # read-only pump: replica state untouched — input-only variant
            # (no (S, E, ...) pool pass-through copies)
            table, ok, reads = self._step_read(
                self.frontend.table, states, pools, batch, rr, healthy)
        self.frontend.table = table
        return PendingPump(reqs=reqs, ok=ok, reads=reads)

    def _complete(self, p: PendingPump) -> int:
        """The pump's single host hop: fetch completion flags + read
        payloads, deliver results, requeue not-admitted requests."""
        ok, reads = jax.device_get((p.ok, p.reads))
        done = 0
        requeues = []
        for s, shard_reqs in enumerate(p.reqs):
            for i, r in enumerate(shard_reqs):
                if ok[s][i]:
                    r.status = 0
                    if r.kind == "read":
                        r.result = reads[s, i]
                    done += 1
                else:
                    requeues.append(r)
        self.frontend.ring.requeue_all(requeues)
        self.completed += done
        return done

    def pump(self) -> int:
        """One synchronous pool iteration (launch + complete)."""
        p = self.pump_async()
        return self._complete(p) if p is not None else 0

    def drain(self, max_iters: int = 100_000) -> int:
        """Pipelined drain: launch iteration N+1 before blocking on N.

        The admission/stacking host work and the device execution of the
        new step overlap the previous iteration's ``device_get`` — the
        double-buffered completion that keeps both sides busy. Requeued
        (not-admitted) requests surface at the completion of N and are
        re-drained by N+2's launch.
        """
        total = 0
        pending: Optional[PendingPump] = None
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
