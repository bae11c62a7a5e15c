"""The engine façade + the upstream baseline, per paper Fig. 2/3.

``Engine`` is a THIN FAÇADE over the backend registry (core/backends.py):
``EngineConfig.comm`` names a registered backend (loop | slots | fused |
sharded | ring | upstream | host), ``make_backend`` builds it, and every
engine method delegates — there is no comm string branching here anymore.
The public block-device API (core/blockdev.py ``VolumeManager``) drives the
same registry with byte-addressed async I/O; ``Engine`` keeps the
request-level surface alive for the ladder and the legacy tests.

``UpstreamEngine`` is the faithful baseline (single-loop frontend,
per-request dispatch, chained snapshot lookup on reads) so the benchmark
ladder can reproduce Tables I/II; it also satisfies the backend protocol
(registered as ``"upstream"``).

Null-layer switches implement the paper's §IV-A methodology:
  null_backend  — requests complete at the controller (frontend-only run)
  null_storage  — replicas ack without touching DBS (no-storage run)

Pipeline and ladder columns: docs/ARCHITECTURE.md.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp

from repro.core.control import ControlDispatch
from repro.core.frontend import Request, UpstreamFrontend


@dataclass
class EngineConfig:
    n_replicas: int = 2
    n_queues: int = 4            # ublk frontend hardware queues
    n_slots: int = 256           # Messages Array size (max in-flight)
    batch: int = 64              # admission batch
    n_extents: int = 1024
    max_volumes: int = 16
    max_pages: int = 256
    page_blocks: int = 32        # paper: 32 blocks per extent
    payload_shape: Tuple[int, ...] = (64,)
    null_backend: bool = False
    null_storage: bool = False
    storage: str = "dbs"         # dbs | chained (sparse-file-style baseline)
    comm: str = "slots"          # a REGISTERED BACKEND name (core/backends):
                                 # slots (Messages Array) | loop (per-request)
                                 # | fused (single-program step, core/fused.py)
                                 # | sharded (vmapped EnginePool, core/sharded.py)
                                 # | ring (opcode-tagged SQ/CQ, core/ring.py)
                                 # | upstream (TGT-style baseline)
                                 # | host (sequential host-state oracle)
    cow: str = "auto"            # LEGACY data-plane axis (pre-registry):
                                 # auto | pallas | ref — only consulted
                                 # when kernel="auto" (see below)
    kernel: str = "auto"         # DBS data plane for comm="fused"/"sharded"/
                                 # "ring" (a REGISTERED KERNEL, kernels/dbs
                                 # registry): auto (follow cow: pallas on
                                 # TPU, xla elsewhere) | pallas (dbs_rw
                                 # scatter/gather kernels) | xla
                                 # (apply_write_ops reference) | ref
                                 # (pure-jnp row composition) | copy
                                 # (dbs_copy + XLA scatter hybrid)
    n_shards: int = 1            # engine shards for comm="sharded"/"ring"
    replica_chips: int = 1       # chips the replicas span (comm="ring"): 1
                                 # (all on the default device) or
                                 # n_replicas (replica r on jax.devices()[r],
                                 # writes mirrored between the chips)
    compute_tail: int = 8        # max COMPUTE SQEs per ring batch (the
                                 # in-program storage-function scan window,
                                 # core/ring.py / compute/phase.py)
    transport: str = "local"     # controller<->replica wire (a REGISTERED
                                 # TRANSPORT, core/transport.py): local
                                 # (in-process) | device (stacked device
                                 # endpoints) | simnet (simulated network).
                                 # On in-program backends (fused/sharded/
                                 # ring) it carries control+rebuild traffic
    write_policy: str = "all"    # mirrored-write completion: all | quorum
                                 # | async (host-dispatch backends only)
    read_policy: str = "rr"      # serving-replica pick: rr | latency
    transport_opts: Optional[Dict[str, Any]] = None
                                 # per-transport knobs (simnet: latency /
                                 # window / drop / reorder / seed; list
                                 # values are per-replica)
    journal: Any = None          # durability write-ahead journal: a path or
                                 # a repro.durability.journal.Journal. The
                                 # block-device manager group-commits every
                                 # mutating public-API op to it at each pump
                                 # boundary (repro/durability/journal.py)
    tier: Any = None             # cold-extent spill tier (comm="fused" only):
                                 # an int device-extent budget, a
                                 # dict(device_extents=N), or an ExtentTier
                                 # (repro/durability/tier.py)


class Engine:
    """Thin façade over a registered backend (core/backends.py).

    Construction resolves ``cfg.comm`` through the registry; submission,
    pumping and control ops delegate to the backend. Legacy attribute
    surface is preserved: ``.pool`` is the backend itself when it is a
    shard pool (sharded/ring), ``.frontend`` the backend's frontend, and
    ``.backend`` the replica storage (``ReplicaGroup``/
    ``ShardedReplicaGroup``/``ChainedReplicas``/None) — so pre-registry
    call sites (``eng.pool.backend.fail(...)``, ``eng.backend.read(...)``)
    keep working unchanged.
    """

    def __init__(self, cfg: EngineConfig):
        self.cfg = cfg
        if cfg.cow not in ("auto", "pallas", "ref"):
            raise ValueError(f"unknown cow impl {cfg.cow!r} "
                             "(expected auto | pallas | ref)")
        from repro.kernels.dbs.registry import available_kernels
        if cfg.kernel != "auto" and cfg.kernel not in available_kernels():
            raise ValueError(
                f"unknown kernel {cfg.kernel!r} (expected auto | "
                f"{' | '.join(available_kernels())})")
        if cfg.replica_chips != 1 and cfg.comm != "ring":
            raise ValueError(
                f"replica_chips={cfg.replica_chips} (replicas on separate "
                f"chips) needs comm='ring'; got comm={cfg.comm!r}")
        if cfg.tier is not None and cfg.comm != "fused":
            raise ValueError(
                f"tier= (the cold-extent spill tier) needs comm='fused' — "
                f"the tier's access stamps live in the fused step; got "
                f"comm={cfg.comm!r}")
        from repro.core.backends import make_backend
        self._impl = make_backend(cfg.comm, cfg)
        # the durability journal (repro/durability): resolved here so
        # EngineConfig(journal=path) is enough to enable it; the manager
        # (core/blockdev.py) owns the record buffer and the group commit
        self.journal = None
        self._journal_owned = False
        if cfg.journal is not None:
            from repro.durability.journal import as_journal
            self.journal = as_journal(cfg.journal)
            self._journal_owned = self.journal is not cfg.journal
        self.pool = (self._impl if getattr(self._impl, "is_pool", False)
                     else None)
        self.frontend = self._impl.frontend
        self.backend = self._impl.storage
        self._kernel = getattr(self._impl, "_kernel", None)

    @property
    def impl(self):
        """The registered backend instance behind this façade."""
        return self._impl

    @property
    def data_kinds(self):
        """Request kinds the backend's submission boundary accepts."""
        return self._impl.data_kinds

    @property
    def completed(self) -> int:
        return self._impl.completed

    @completed.setter
    def completed(self, v: int) -> None:
        self._impl.completed = v

    def create_volume(self) -> int:
        return self._impl.create_volume()

    # -- control plane: uniform dispatch through the backend's control()
    # (in-band ring submissions on backend="ring"; host-side elsewhere) ------
    def snapshot(self, vol: int):
        return self._impl.control("snapshot", volume=vol)

    def clone(self, vol: int) -> int:
        return self._impl.control("clone", volume=vol)

    def unmap(self, vol: int, pages) -> None:
        self._impl.control("unmap", volume=vol, pages=pages)

    def delete_volume(self, vol: int) -> None:
        self._impl.control("delete", volume=vol)

    def control(self, kind: str, **kw) -> Any:
        """Raw control-plane passthrough (snapshot/clone/unmap/delete/fail/
        rebuild — see ``backends.Backend.control``)."""
        return self._impl.control(kind, **kw)

    def submit(self, req: Request) -> None:
        # validation happens at the backend's submission boundary — BEFORE
        # any enqueue, so mixed-kind batches never lose innocent data
        # requests to a drain-time rejection
        self._impl.submit(req)

    def depth(self) -> int:
        return self._impl.depth()

    def pump(self) -> int:
        """One backend iteration. Returns the number of completions."""
        return self._impl.pump()

    def drain(self, max_iters: int = 100_000) -> int:
        return self._impl.drain(max_iters)


class ChainedReplicas:
    """ReplicaGroup-shaped adapter over the sparse-file-style ChainedStore
    (the upstream storage scheme behind the modern frontend/comm layers —
    benchmark ladder column '+comm, chained storage')."""

    def __init__(self, cfg: "EngineConfig"):
        self.cfg = cfg
        self.stores = [ChainedStore(cfg.payload_shape)
                       for _ in range(cfg.n_replicas)]
        self._rr = 0

    def _agree(self, ids) -> int:
        """Mirrored control ops must agree on the id every store assigned —
        divergent per-store volume/clone ids would silently route every
        subsequent read/write of that volume to different data on each
        replica (the id returned here names the volume engine-wide)."""
        if len(set(ids)) != 1:
            raise RuntimeError(f"replica stores diverged on id: {ids}")
        return ids[0]

    def create_volume(self) -> int:
        return self._agree([s.create_volume() for s in self.stores])

    def snapshot(self, vol: int) -> None:
        for s in self.stores:
            s.snapshot(vol)

    def clone(self, vol: int) -> int:
        return self._agree([s.clone(vol) for s in self.stores])

    def unmap(self, vol: int, pages) -> None:
        for s in self.stores:
            for p in pages:
                s.unmap(vol, int(p))

    def delete_volume(self, vol: int) -> None:
        for s in self.stores:
            s.delete_volume(vol)

    def write(self, vol, pages, offs, payload, mask=None) -> None:
        import numpy as _np
        vols = _np.broadcast_to(_np.asarray(vol), (len(pages),))
        for s in self.stores:
            for i in range(len(pages)):
                if mask is not None and not bool(mask[i]):
                    continue
                s.write(int(vols[i]), int(pages[i]), int(offs[i]), payload[i])

    def read(self, vol, pages, offs):
        import numpy as _np
        if self.cfg.null_storage:
            # no store serves anything: do NOT advance the rr cursor — the
            # layer-cut row must not skew the read distribution the real
            # stores would see (ReplicaGroup.read holds the same contract)
            return None
        s = self.stores[self._rr % len(self.stores)]
        self._rr += 1
        vols = _np.broadcast_to(_np.asarray(vol), (len(pages),))
        return [s.read(int(vols[i]), int(pages[i]), int(offs[i]))
                for i in range(len(pages))]


# ---------------------------------------------------------------------------
# upstream baseline
# ---------------------------------------------------------------------------
class ChainedStore:
    """Sparse-file-style backing store: per-snapshot page maps; reads walk
    the snapshot chain newest->oldest (paper: 'Reads in volumes with many
    snapshots may have to go through the whole chain')."""

    def __init__(self, payload_shape=(64,)):
        self.chains: Dict[int, List[Dict[int, jnp.ndarray]]] = {}
        self.payload_shape = tuple(payload_shape)
        self._next = 0
        self.layers_walked = 0      # instrumentation: chain-walk depth
        self.reads = 0

    def create_volume(self) -> int:
        vid = self._next
        self._next += 1
        self.chains[vid] = [{}]
        return vid

    # control ops are no-op-on-miss (clone: -1), like the DBS path they are
    # compared against — a deleted/unknown volume must not diverge the
    # reference baseline into a KeyError where dbs completes harmlessly
    def snapshot(self, vol: int) -> None:
        if vol in self.chains:
            self.chains[vol].append({})     # new live layer

    def clone(self, vol: int) -> int:
        """Fork: freeze src (snapshot), share its frozen layers (the dicts
        themselves — CoW at layer granularity), own a fresh live layer."""
        if vol not in self.chains:
            return -1
        self.snapshot(vol)
        vid = self._next
        self._next += 1
        self.chains[vid] = list(self.chains[vol][:-1]) + [{}]
        return vid

    def unmap(self, vol: int, page: int) -> None:
        """TRIM a page: a tombstone in the live layer shadows older layers;
        same-layer writes to the page are dropped (trim-after-write wins,
        and a later write re-creates the key, so write-after-trim wins)."""
        if vol not in self.chains:
            return
        live = self.chains[vol][-1]
        for key in [k for k in live if k[0] == page]:
            del live[key]
        live[("TRIM", page)] = True

    def delete_volume(self, vol: int) -> None:
        self.chains.pop(vol, None)      # clones keep their shared layers

    def write(self, vol: int, page: int, block: int, payload) -> None:
        live = self.chains[vol][-1]
        key = (page, block)
        live[key] = payload             # delegated allocation (dict = fs)

    def read(self, vol: int, page: int, block: int):
        self.reads += 1
        for layer in reversed(self.chains.get(vol, ())):   # walk the chain
            self.layers_walked += 1
            if (page, block) in layer:
                return layer[(page, block)]
            if ("TRIM", page) in layer:
                return None             # unmapped above any older data
        return None


class UpstreamEngine(ControlDispatch):
    """TGT-style frontend + loop-function dispatch + chained sparse store.

    Registered as ``backend="upstream"`` (core/backends.py): the measured
    baseline satisfies the same protocol as every optimized backend, so the
    public block-device API can run byte-for-byte equivalence against it.
    """

    is_pool = False
    data_kinds = frozenset({"read", "write"})
    storage = None                  # no replica-group-shaped storage object

    def __init__(self, cfg: EngineConfig):
        self.cfg = cfg
        self.frontend = UpstreamFrontend(max_inflight=cfg.n_slots)
        self.stores = (None if cfg.null_backend else
                       [ChainedStore(cfg.payload_shape)
                        for _ in range(cfg.n_replicas)])
        self._rr = 0
        self.completed = 0

    def _agree(self, ids) -> int:
        if len(set(ids)) != 1:          # same hazard as ChainedReplicas
            raise RuntimeError(f"replica stores diverged on id: {ids}")
        return ids[0]

    def create_volume(self) -> int:
        if self.stores is None:
            return 0
        return self._agree([s.create_volume() for s in self.stores])

    def snapshot(self, vol: int) -> None:
        if self.stores is not None:
            for s in self.stores:
                s.snapshot(vol)

    def clone(self, vol: int) -> int:
        if self.stores is None:
            return -1
        return self._agree([s.clone(vol) for s in self.stores])

    def unmap(self, vol: int, pages) -> None:
        if self.stores is not None:
            for s in self.stores:
                for p in pages:
                    s.unmap(vol, int(p))

    def delete_volume(self, vol: int) -> None:
        if self.stores is not None:
            for s in self.stores:
                s.delete_volume(vol)

    def depth(self) -> int:
        return len(self.frontend)

    def submit(self, req: Request) -> None:
        # submission-boundary validation: historically the upstream path
        # enqueued ANY kind and silently executed it as a read — validate
        # before enqueue like every registered backend
        if req.kind not in self.data_kinds:
            raise ValueError(
                f"kind={req.kind!r} requests need backend='ring'; the "
                "upstream baseline carries data ops only")
        self.frontend.submit(req)

    def pump(self) -> int:
        got = self.frontend.poll_one()      # ONE request per loop iteration
        if got is None:
            return 0
        mid, req = got
        if self.stores is not None and not self.cfg.null_storage:
            if req.kind == "write":
                for s in self.stores:       # mirrored, sequential
                    s.write(req.volume, req.page, req.block, req.payload)
            else:
                s = self.stores[self._rr % len(self.stores)]
                self._rr += 1
                req.result = s.read(req.volume, req.page, req.block)
        self.frontend.complete(mid)
        req.status = 0
        self.completed += 1
        return 1

    def drain(self, max_iters: int = 1_000_000) -> int:
        n = 0
        for _ in range(max_iters):
            got = self.pump()
            if got == 0 and len(self.frontend) == 0:
                break
            n += got
        return n
