"""The backend registry: named engine backends behind one protocol.

Before this module, picking an engine meant string-branching inside
``Engine.__init__`` on ``EngineConfig.comm`` — six hard-coded modes, each
with its own construction path, and no way to add a seventh without editing
the engine. This is the io_uring/ublk-style fix applied to *construction*:
every engine variant is a **backend** registered by name, and ``Engine``
(core/engine.py) plus the public block-device API
(``blockdev.VolumeManager``) are thin façades that look the name up here.

The **Backend protocol** (duck-typed; ``Backend`` below is the typing
reference) is the four-verb surface the paper's ublk frontend needs from an
engine plus lifecycle plumbing:

- ``submit(req)``  — enqueue one request; MUST validate ``req.kind`` against
  ``data_kinds`` and raise *before* touching any queue (a drain-time
  rejection would pop — and then lose — innocent requests batched alongside
  the offending one),
- ``pump()``       — one engine iteration; returns completions,
- ``drain()``      — pump to empty (pipelined where the backend supports it),
- ``control(kind, ...)`` — snapshot / clone / unmap / delete / fail /
  rebuild, executed however the backend likes (in-band SQEs on the ring,
  host-side dispatch elsewhere),

plus ``create_volume()``, ``depth()``, ``completed`` (get/set), a
``storage`` attribute naming the replica storage (or None), ``is_pool``
(True when the backend IS a shard pool — ``Engine.pool`` compatibility),
and ``data_kinds`` (the request kinds ``submit`` accepts).

Registered backends:

| name       | class                          | submission path          |
| ---------- | ------------------------------ | ------------------------ |
| ``loop``   | ``HostDispatchBackend``        | one host dispatch per op |
| ``slots``  | ``HostDispatchBackend``        | batched slot admission   |
| ``fused``  | ``FusedBackend``               | ONE program per pump     |
| ``sharded``| ``sharded.EnginePool``         | vmapped pool, pipelined  |
| ``ring``   | ``ring.RingEngine``            | opcode-tagged SQ/CQ      |
| ``upstream``| ``engine.UpstreamEngine``     | TGT-style baseline       |
| ``host``   | ``HostStateBackend``           | sequential host oracle   |

``host`` is the registry-extensibility demo and does double duty: it is the
sequential single-state oracle the byte-API tests compare engines against,
and the control plane the paged-KV serving engine embeds (``alloc_pages``
exposes DBS ``WriteOps`` so an external data plane can mirror the CoW
copies — serving/engine.py).
"""
from __future__ import annotations

import collections
from typing import (Any, Callable, Dict, FrozenSet, List, Optional, Protocol,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dbs
from repro.core.control import ControlDispatch
from repro.core.frontend import MultiQueueFrontend, Request
from repro.core.fused import (fused_step, fused_step_read,
                              fused_step_read_tiered, fused_step_tiered)
from repro.core.replication import ReplicaGroup


class Backend(Protocol):
    """Typing reference for the duck-typed backend protocol (docstring
    above). Concrete backends do not need to inherit from this."""

    cfg: Any
    storage: Any
    is_pool: bool
    data_kinds: FrozenSet[str]
    completed: int

    def create_volume(self) -> int: ...
    def submit(self, req: Request) -> None: ...
    def pump(self) -> int: ...
    def drain(self, max_iters: int = 100_000) -> int: ...
    def depth(self) -> int: ...
    def control(self, kind: str, *, volume: int = -1, pages=None,
                shard: Optional[int] = None, replica: int = -1) -> Any: ...


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Callable[[Any], Any]] = {}


def register_backend(name: str, factory: Optional[Callable] = None, *,
                     override: bool = False):
    """Register ``factory(cfg) -> Backend`` under ``name``. Usable directly
    (``register_backend("slots", HostDispatchBackend)``) or as a decorator
    (``@register_backend("mybackend")``). Duplicate names raise (the uniform
    registry contract — backends/transports/kernels/storage fns all match);
    embedders that mean to shadow a built-in pass ``override=True``."""
    def _put(f):
        if name in _REGISTRY and not override:
            raise ValueError(
                f"duplicate backend {name!r} (registered: "
                f"{', '.join(available_backends())}); pass override=True "
                "to replace")
        _REGISTRY[name] = f
        return f
    if factory is None:
        return _put
    return _put(factory)


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def make_backend(name: str, cfg) -> Any:
    """Instantiate the backend registered under ``name`` for ``cfg``."""
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r} (registered: "
            f"{', '.join(available_backends())})") from None
    return factory(cfg)


# ---------------------------------------------------------------------------
# host-dispatch backends (the pre-fused engine paths)
# ---------------------------------------------------------------------------
class _FrontendBackendBase(ControlDispatch):
    """Shared construction for the MultiQueueFrontend-fed backends: the
    frontend, the replica storage (DBS ReplicaGroup, the chained sparse-file
    baseline, or None for the null-backend layer cut), and host-side
    control dispatch (ControlDispatch over the storage-delegating methods
    below; null-backend rows keep the engines' historical surface —
    snapshot None, clone -1)."""

    is_pool = False
    data_kinds = frozenset({"read", "write"})

    def __init__(self, cfg):
        self.cfg = cfg
        self.frontend = MultiQueueFrontend(cfg.n_queues, cfg.n_slots,
                                           cfg.batch)
        if cfg.null_backend:
            self.storage = None
        elif cfg.storage == "chained":
            from repro.core.engine import ChainedReplicas
            self.storage = ChainedReplicas(cfg)
        else:
            self.storage = ReplicaGroup(
                cfg.n_replicas, cfg.n_extents, cfg.max_volumes, cfg.max_pages,
                cfg.page_blocks, cfg.payload_shape,
                null_storage=cfg.null_storage, transport=cfg.transport,
                write_policy=cfg.write_policy, read_policy=cfg.read_policy,
                transport_opts=cfg.transport_opts)
        from repro.kernels.dbs.registry import resolve_kernel_name
        self._kernel = resolve_kernel_name(cfg)
        self.completed = 0

    def create_volume(self) -> int:
        if self.storage is None:
            return 0
        return self.storage.create_volume()

    def submit(self, req: Request) -> None:
        # submission-boundary validation: reject BEFORE enqueue, so a mixed
        # batch never loses its innocent data requests to a drain-time error
        if req.kind not in self.data_kinds:
            raise ValueError(
                f"kind={req.kind!r} requests need backend='ring' (the "
                "opcode-tagged SQ/CQ path); this backend carries data ops "
                "only — use control() for host-side control ops")
        self.frontend.submit(req)

    def depth(self) -> int:
        return self.frontend.depth()

    def snapshot(self, volume: int):
        return None if self.storage is None else self.storage.snapshot(volume)

    def clone(self, volume: int) -> int:
        return -1 if self.storage is None else self.storage.clone(volume)

    def unmap(self, volume: int, pages) -> None:
        if self.storage is not None:
            self.storage.unmap(volume, pages)

    def delete_volume(self, volume: int) -> None:
        if self.storage is not None:
            self.storage.delete_volume(volume)

    def _control_repl(self, kind, shard, replica):
        if self.storage is None:
            return None
        fn = getattr(self.storage, kind, None)     # ReplicaGroup.fail/rebuild
        if fn is None:
            raise ValueError(f"storage {type(self.storage).__name__} has no "
                             f"{kind!r} control op")
        return fn(replica)

    def drain(self, max_iters: int = 100_000) -> int:
        n = 0
        for _ in range(max_iters):
            got = self.pump()
            if got == 0 and self.frontend.depth() == 0:
                break
            n += got
        return n

    def pump(self) -> int:                         # pragma: no cover
        raise NotImplementedError


@register_backend("loop")
@register_backend("slots")
class HostDispatchBackend(_FrontendBackendBase):
    """The unfused engine iteration: batched slot admission (``slots``) or
    the per-request loop (``loop``), with separate host dispatches for
    admission, writes, reads and completion — the benchmark ladder's
    ``+comm``/``+dbs`` columns and the ``+frontend`` loop baseline."""

    def _exec_write_batch(self, rs: List[Request]) -> None:
        if self.cfg.storage == "chained":
            for r in rs:
                self.storage.write(r.volume, [r.page], [r.block],
                                   [r.payload])
            return
        # fixed-shape vectorized write (padded to the admission batch)
        n, cap = len(rs), self.cfg.batch
        pad = cap - (n % cap) if n % cap else 0
        vols = jnp.asarray([r.volume for r in rs] + [0] * pad, jnp.int32)
        pages = jnp.asarray([r.page for r in rs] + [0] * pad, jnp.int32)
        offs = jnp.asarray([r.block for r in rs] + [0] * pad, jnp.int32)
        payload = jnp.stack(
            [r.payload if r.payload is not None
             else jnp.zeros(self.cfg.payload_shape) for r in rs]
            + [jnp.zeros(self.cfg.payload_shape)] * pad)
        mask = jnp.arange(n + pad) < n
        for i in range(0, n + pad, cap):
            s = slice(i, i + cap)
            self.storage.write(vols[s], pages[s], offs[s], payload[s],
                               mask=mask[s])

    def pump(self) -> int:
        """One controller iteration: admit a batch, execute it against the
        replicas (writes mirrored / reads round-robin), complete the slots.
        Returns the number of completed requests."""
        slot_ids, reqs = self.frontend.poll_batch()
        if not reqs:
            return 0
        if self.storage is not None:
            if self.cfg.comm == "loop":
                # the single loop function: one request at a time
                for r in reqs:
                    if r.kind == "write":
                        self._exec_write_batch([r])
                    else:
                        out = self.storage.read(
                            r.volume, jnp.asarray([r.page], jnp.int32),
                            jnp.asarray([r.block], jnp.int32))
                        if out is not None:
                            r.result = np.asarray(jax.device_get(out))[0]
            else:
                writes = [r for r in reqs if r.kind == "write"]
                reads = [r for r in reqs if r.kind == "read"]
                if writes:
                    self._exec_write_batch(writes)
                if reads:
                    if self.cfg.storage == "chained":
                        out = self.storage.read(
                            [r.volume for r in reads],
                            [r.page for r in reads],
                            [r.block for r in reads])
                        if out is not None:
                            for r, v in zip(reads, out):
                                r.result = v
                    else:
                        n, cap = len(reads), self.cfg.batch
                        pad = cap - (n % cap) if n % cap else 0
                        vols = jnp.asarray(
                            [r.volume for r in reads] + [0] * pad, jnp.int32)
                        pages = jnp.asarray(
                            [r.page for r in reads] + [0] * pad, jnp.int32)
                        offs = jnp.asarray(
                            [r.block for r in reads] + [0] * pad, jnp.int32)
                        for i in range(0, n + pad, cap):
                            s = slice(i, i + cap)
                            out = self.storage.read(vols[s], pages[s],
                                                    offs[s])
                            # one fetch per chunk, host indexing after:
                            # per-lane out[j] would put O(B) tiny device
                            # gathers on the pump (and deliver device
                            # arrays where every other comm mode delivers
                            # host numpy)
                            out = np.asarray(jax.device_get(out))
                            for j, r in enumerate(reads[i:i + cap]):
                                r.result = out[j]
        done = self.frontend.complete(slot_ids)
        for r in done:
            # unified completion semantics across backends: every completed
            # request carries a status (0 = OK) and a latency in pump ticks
            # (stamped at drain); reads carry their payload in ``result``
            r.status = 0
        self.completed += len(done)
        return len(done)


@register_backend("fused")
class FusedBackend(_FrontendBackendBase):
    """The single-program engine step (core/fused.py): admission -> CoW
    writes -> mirrored stores -> rr reads -> retirement in ONE compiled
    program per batch geometry, one ``device_get`` per pump."""

    def __init__(self, cfg):
        if cfg.storage != "dbs":
            raise ValueError("backend='fused' requires storage='dbs'")
        if cfg.write_policy != "all" or cfg.read_policy != "rr":
            raise ValueError(
                "backend='fused' serves the data plane IN-PROGRAM "
                "(mirror-to-all writes, in-program rr reads); write_policy="
                f"{cfg.write_policy!r}/read_policy={cfg.read_policy!r} "
                "need a host-dispatch backend (loop | slots)")
        super().__init__(cfg)
        # cold-extent spill tier (repro/durability/tier.py): bounded
        # device-resident hot set, host-memory capacity tier, spill/fill at
        # the pump boundary. Needs the real DBS storage plane.
        self.tier = None
        if getattr(cfg, "tier", None) is not None:
            if cfg.null_backend or cfg.null_storage:
                raise ValueError("tier= needs the real storage plane "
                                 "(null_backend/null_storage hold no pools)")
            from repro.durability.tier import as_tier
            self.tier = as_tier(cfg.tier, cfg.n_extents)

    def pump(self) -> int:
        """One controller iteration as ONE compiled program (core/fused.py).

        The host drains raw request arrays in, launches ``fused_step``, and
        performs exactly one ``device_get`` — at completion, to learn which
        lanes were admitted and to carry read payloads out. Between admission
        and completion nothing crosses the host: the slot table, replica
        DBS states and payload pools round-trip device-side.

        With a tier, spill/fill rides the pump boundary: spilled extents the
        batch touches fault in (one batched row-scatter per replica) before
        the step, the step itself is the *tiered* single program (it also
        stamps per-extent access ticks), and an over-budget resident set is
        rebalanced after — the in-program hot path is unchanged.
        """
        reqs, batch = self.frontend.drain_batch(self.cfg.payload_shape)
        if not reqs:
            return 0
        if self.storage is None:
            states, pools, page_revs = (), (), ()
            rr = 0
        else:
            states, pools = self.storage.device_state()
            page_revs = self.storage.device_page_revs()
            rr = self.storage.bump_rr()
        tier = self.tier
        if tier is not None:
            table_host = np.asarray(jax.device_get(states[0].table))
            pools, touched = tier.fault_in(table_host, reqs, pools)
            if any(r.kind == "write" for r in reqs):
                (table, states, pools, page_revs, stamps, ok,
                 reads) = fused_step_tiered(
                    self.frontend.table, states, pools, page_revs,
                    tier.stamps, batch, rr, kernel=self._kernel)
                self.storage.set_device_page_revs(page_revs)
            else:
                table, stamps, ok, reads = fused_step_read_tiered(
                    self.frontend.table, states, pools, tier.stamps, batch,
                    rr, kernel=self._kernel)
            tier.stamps = stamps
            pools = tier.balance(pools, protect=touched)
            self.storage.set_device_state(states, pools)
        elif any(r.kind == "write" for r in reqs):
            table, states, pools, page_revs, ok, reads = fused_step(
                self.frontend.table, states, pools, page_revs, batch, rr,
                null_backend=self.cfg.null_backend,
                null_storage=self.cfg.null_storage, kernel=self._kernel)
            if self.storage is not None:
                self.storage.set_device_state(states, pools)
                self.storage.set_device_page_revs(page_revs)
        else:
            # read-only batch: replica state is untouched, so dispatch the
            # input-only variant (no pool pass-through copies)
            table, ok, reads = fused_step_read(
                self.frontend.table, states, pools, batch, rr,
                null_backend=self.cfg.null_backend,
                null_storage=self.cfg.null_storage, kernel=self._kernel)
        self.frontend.table = table
        # the single host hop: completion flags + completed read payloads
        ok_host, reads_host = jax.device_get((ok, reads))
        done = 0
        requeues = []
        for i, r in enumerate(reqs):
            if ok_host[i]:
                r.status = 0
                if r.kind == "read":
                    r.result = reads_host[i]
                done += 1
            else:
                requeues.append(r)
        self.frontend.ring.requeue_all(requeues)
        self.completed += done
        return done


# ---------------------------------------------------------------------------
# the host-state oracle backend (+ the serving engine's control plane)
# ---------------------------------------------------------------------------
@register_backend("host")
class HostStateBackend(ControlDispatch):
    """ONE host-driven DBSState + payload pool, strictly sequential.

    Three jobs: (1) the reference oracle the byte-API equivalence tests
    compare engine backends against, (2) the registry-extensibility demo —
    ~80 lines is all a new backend needs, (3) the control plane embedders
    with an external data plane drive: ``alloc_pages`` runs the DBS
    control-plane resolution on this backend's state and returns the
    ``WriteOps`` (dst extents, CoW sources) so the embedder can mirror the
    copies onto its own pools — the paged-KV serving engine allocates its
    cache pages through exactly this (serving/engine.py via
    ``blockdev.VolumeManager``)."""

    is_pool = False
    data_kinds = frozenset({"read", "write", "compute"})

    def __init__(self, cfg):
        self.cfg = cfg
        self.frontend = None                 # no admission machinery at all
        self.storage = None
        self.state = dbs.make_state(cfg.n_extents, cfg.max_volumes,
                                    cfg.max_pages)
        self.pool = (None if (cfg.null_storage or cfg.null_backend) else
                     jnp.zeros((cfg.n_extents + 1, cfg.page_blocks)
                               + tuple(cfg.payload_shape), jnp.float32))
        self.queue: collections.deque = collections.deque()
        self.step = 0                        # pump tick (latency accounting)
        self.completed = 0

    def create_volume(self) -> int:
        self.state, vid = dbs.create_volume(self.state)
        return int(vid)

    def submit(self, req: Request) -> None:
        if req.kind not in self.data_kinds:
            raise ValueError(
                f"kind={req.kind!r} requests need backend='ring'; the host "
                "oracle carries data and compute ops only — use control()")
        req.tick = self.step
        self.queue.append(req)

    def depth(self) -> int:
        return len(self.queue)

    def pump(self) -> int:
        """Execute ONE queued request (strictly sequential — the oracle's
        whole point is per-op submission-order semantics)."""
        if not self.queue:
            return 0
        r = self.queue.popleft()
        status = 0
        if r.kind == "write":
            self.state, ops = dbs.write_pages(
                self.state, jnp.int32(r.volume),
                jnp.asarray([r.page], jnp.int32),
                jnp.asarray([1 << r.block], jnp.uint32),
                jnp.asarray([True]))
            if self.pool is not None:
                self.pool = dbs.apply_write_ops(
                    self.pool, ops, jnp.asarray(r.payload)[None],
                    jnp.asarray([r.block], jnp.int32))
        elif r.kind == "compute":
            # the sequential host_ref — the reference every in-program
            # backend's storage-function results are gated against
            if self.pool is not None:
                from repro.compute.exec import host_compute
                val, status, out, self.state, self.pool = host_compute(
                    self.state, self.pool, r, self.cfg.payload_shape)
                r.result = (val, out)
        elif self.pool is not None:
            ext = int(self.state.table[r.volume, r.page])
            r.result = (np.zeros(tuple(self.cfg.payload_shape), np.float32)
                        if ext < 0 else
                        np.asarray(self.pool[ext, r.block]))
        r.status = status
        r.latency = self.step - getattr(r, "tick", 0) + 1
        self.step += 1
        self.completed += 1
        return 1

    def drain(self, max_iters: int = 1_000_000) -> int:
        n = 0
        for _ in range(max_iters):
            if not self.pump():
                break
            n += 1
        return n

    def snapshot(self, volume: int) -> int:
        self.state, sid = dbs.snapshot(self.state, jnp.int32(volume))
        return int(sid)

    def clone(self, volume: int) -> int:
        self.state, vid = dbs.clone(self.state, jnp.int32(volume))
        return int(vid)

    def unmap(self, volume: int, pages) -> None:
        ps = np.asarray(list(pages), np.int32)
        if ps.size:
            self.state = dbs.unmap(self.state, jnp.int32(volume),
                                   jnp.asarray(ps))

    def delete_volume(self, volume: int) -> None:
        self.state = dbs.delete_volume(self.state, jnp.int32(volume))

    # -- the external-data-plane hook (serving/engine.py) -------------------
    def alloc_pages(self, vols, pages, mask=None, bits=None) -> dbs.WriteOps:
        """Control-plane page allocation/CoW on this backend's state; the
        returned WriteOps drive the embedder's own data plane."""
        if bits is None:
            bits = jnp.ones(jnp.asarray(pages).shape, jnp.uint32)
        self.state, ops = dbs.write_pages(self.state, vols, pages, bits,
                                          mask)
        return ops


# ---------------------------------------------------------------------------
# pool / baseline backends (classes live in their own modules)
# ---------------------------------------------------------------------------
@register_backend("sharded")
def _make_sharded(cfg):
    from repro.core.sharded import EnginePool
    return EnginePool(cfg)


@register_backend("ring")
def _make_ring(cfg):
    from repro.core.ring import RingEngine
    return RingEngine(cfg)


@register_backend("upstream")
def _make_upstream(cfg):
    from repro.core.engine import UpstreamEngine
    return UpstreamEngine(cfg)
