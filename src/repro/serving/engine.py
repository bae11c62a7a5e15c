"""ServeEngine: continuous batching on top of the optimized engine layers.

One running engine = one "Longhorn node":

- admission goes through the **multi-queue frontend** (ublk analogue),
- live requests own **slots** in a fixed SlotTable (Messages Array) — the
  decode batch is always the full slot array, inactive lanes masked,
- each request's KV state is a **DBS volume** owned by a
  ``blockdev.VolumeManager``. On the default **zero-copy** backends
  (``kv_backend="fused"`` / ``"sharded"``) the engine's payload pool *is*
  the KV cache: one block holds one token's K/V for every layer
  (``payload_shape=(n_planes, KV, hd)``, plane ``2l`` = layer l keys,
  ``2l+1`` = values), page allocation and CoW ride ordinary write SQEs
  batched into ONE pump per step, and the paged-attention kernel gathers
  K/V straight out of the extent pool through the volume's extent map
  (``kernels/paged_attention``) — no staging copy of the KV cache ever
  exists,
- **forking** a session is ``VolumeManager.clone`` — prefix extents
  shared, diverging writes CoW'd in-kernel by the DBS write step — O(1)
  in context length,
- completion retires the slot and ``VolumeManager.delete`` frees the
  extents.

``kv_backend="host"`` keeps the pre-zero-copy data path (model-owned KV
pools driven by host ``alloc_pages`` + per-layer ``dbs_copy`` CoW) as the
measured copy-based baseline — ``benchmarks/ladder.py run_serve`` gates
zero-copy throughput against it.

Single-host execution here (smoke/bench scale); the multi-pod data plane of
the same decode step is exercised by launch/dryrun.py via shard_map.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ArchConfig, ATTN_MLA, ATTN_RWKV,
                                ExecutionPlan)
from repro.core import slots
from repro.core.blockdev import VolumeManager
from repro.core.frontend import MultiQueueFrontend, Request
from repro.core.ring import OP_CLONE, ST_OK
from repro.kernels.paged_attention.kernel import paged_attention_pool_fwd
from repro.kernels.paged_attention.ref import paged_attention_pool_ref
from repro.kernels.platform import default_interpret
from repro.models import blocks as B
from repro.models import model as M


@dataclass
class GenRequest:
    req_id: int
    prompt: np.ndarray            # (S,) int32 (or (S,K) for codebooks)
    max_new: int = 16
    out_tokens: List[int] = field(default_factory=list)
    slot: int = -1
    volume: int = -1
    done: bool = False
    # per-decode-step logits, recorded only when the engine was built with
    # record_logits=True (the fork bit-identity tests)
    logit_trace: List[np.ndarray] = field(default_factory=list)


def _paged_layer_info(cfg: ArchConfig, sig) -> Optional[Tuple[int, int, int]]:
    """(kd, vd, n_kv) for layers whose decode cache is paged (pool-backed),
    mirroring ``blocks.init_layer_cache``; None for ring/recurrent layers."""
    if sig.attn == ATTN_RWKV or sig.window:
        return None
    if sig.attn == ATTN_MLA:
        m = cfg.mla
        return m.kv_lora_rank + m.rope_head_dim, m.kv_lora_rank, 1
    hd = cfg.resolved_head_dim
    return hd, hd, cfg.n_kv_heads


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, *, n_slots: int = 4,
                 max_len: int = 256, n_queues: int = 2,
                 plan: Optional[ExecutionPlan] = None, seed: int = 0,
                 kv_backend: str = "fused", kv_shards: int = 1,
                 kv_replicas: int = 2, kernel: str = "auto",
                 record_logits: bool = False):
        self.cfg = cfg
        self.params = params
        self.plan = plan or ExecutionPlan(remat="none", attn_impl="chunked",
                                          compute_dtype="float32")
        self.n_slots = n_slots
        self.max_len = max_len
        self.kv_backend = kv_backend
        self.record_logits = record_logits
        page = cfg.page_blocks
        self.n_pages = math.ceil(max_len / page)
        dtype = jnp.dtype(self.plan.compute_dtype)

        self.frontend = MultiQueueFrontend(n_queues, n_slots, batch=n_slots)
        # DBS metadata: volumes = sessions; extents shared across layers
        # (one extent row holds every layer's K/V for its page of tokens).
        n_extents = n_slots * self.n_pages * 2 + 8   # headroom for forks/CoW
        self._zero_copy = kv_backend != "host"
        if self._zero_copy:
            infos = [_paged_layer_info(cfg, s) for s in B.layer_sigs(cfg)]
            self._paged = [(li,) + info for li, info in enumerate(infos)
                           if info is not None]
            if not self._paged:
                raise ValueError("zero-copy serving needs at least one "
                                 "paged-attention layer; use "
                                 "kv_backend='host' for pure-recurrent nets")
            kvs = {info[3] for info in self._paged}
            if len(kvs) > 1:
                raise ValueError(f"mixed KV head counts {sorted(kvs)} not "
                                 "supported by the pooled KV layout")
            self._n_kv = kvs.pop()
            self._dmax = max(max(kd, vd) for _, kd, vd, _ in self._paged)
            n_planes = 2 * len(self._paged)
            self._payload_shape = (n_planes, self._n_kv, self._dmax)
            # the engine extent pool IS the KV cache: the volume manager's
            # write SQEs allocate/CoW its rows, the paged-attention kernel
            # reads them through the extent map
            self.volumes = VolumeManager(
                backend=kv_backend, n_shards=kv_shards,
                n_replicas=kv_replicas, kernel=kernel,
                n_extents=n_extents, max_volumes=2 * n_slots,
                max_pages=self.n_pages, page_blocks=page,
                batch=max(2 * n_slots, 16),
                payload_shape=self._payload_shape)
            self.caches = M.init_cache(cfg, n_slots, max_len, paged=True,
                                       dtype=dtype)
            # the model-owned pools are vestigial in zero-copy mode (the
            # paged_decode_fn reads the engine pool instead); shrink them to
            # one dummy extent so they cost nothing to thread through jit
            self.caches = [self._shrink_pool(c) for c in self.caches]
            # device-resident views of the engine's KV store; refreshed
            # after every pump that may move extents (_pump_writes)
            self._pools = self.volumes.device_pools()
            self._table = self.volumes.device_extent_map()
            self._attn_pallas = (kernel == "pallas" or (
                kernel == "auto" and not default_interpret()))
            self._cow_pending: set = set()
            self._step_fn = jax.jit(self._decode_program)
        else:
            # copy-based baseline: host control plane + model-owned pools
            self.volumes = VolumeManager(
                backend="host", null_storage=True, n_extents=n_extents,
                max_volumes=2 * n_slots, max_pages=self.n_pages,
                page_blocks=page, payload_elems=1)
            self.caches = M.init_cache(cfg, n_slots, max_len, paged=True,
                                       dtype=dtype)
            # paged pools must span the DBS extent space
            self.caches = [self._grow_pool(c, n_extents) for c in self.caches]
        self.pos = np.zeros((n_slots,), np.int32)
        self.slot_vol = np.full((n_slots,), -1, np.int64)
        self.live: Dict[int, GenRequest] = {}
        self._steps = 0

    @property
    def state(self):
        """The DBS metadata behind the session volumes (``state.table`` is
        the paged-attention block table). host: the oracle state; fused:
        replica 0's; sharded: replica 0's stacked (S, ...) state."""
        if not self._zero_copy:
            return self.volumes.state
        storage = self.volumes.engine.backend
        if hasattr(storage, "states"):               # sharded (stacked)
            return storage.states[0]
        return storage.device_state()[0][0]          # fused replica 0

    def _grow_pool(self, cache, n_extents):
        if cache is None or "pool_k" not in cache:
            return cache
        c = dict(cache)
        for key in ("pool_k", "pool_v"):
            p = cache[key]
            c[key] = jnp.zeros((n_extents,) + p.shape[1:], p.dtype)
        return c

    def _shrink_pool(self, cache):
        if cache is None or "pool_k" not in cache:
            return cache
        c = dict(cache)
        for key in ("pool_k", "pool_v"):
            p = cache[key]
            c[key] = jnp.zeros((1,) + p.shape[1:], p.dtype)
        return c

    # ------------------------------------------------------------------ API
    def submit(self, req: GenRequest) -> None:
        self.frontend.submit(Request(req_id=req.req_id, kind="write",
                                     volume=-1, page=0, payload=req))

    def fork(self, req_id: int, new_req_id: int, max_new: int = 16
             ) -> Optional[GenRequest]:
        """Fork a live session: clone its DBS volume. O(1) in context
        length — prefix extents are shared, not copied; the parent's and
        child's next writes to the shared frontier page CoW in-kernel."""
        src = self.live.get(req_id)
        if src is None or src.slot < 0:
            return None
        child_vol = self.volumes.clone(src.volume)
        if child_vol is None:
            return None
        vid = child_vol.vid
        child = GenRequest(req_id=new_req_id,
                           prompt=np.zeros((0,), np.int64), max_new=max_new)
        child.out_tokens = list(src.out_tokens)
        # claim a slot directly (fork bypasses the admission queue); the
        # Messages Array records the op that owns the slot (ring opcode lane)
        self.frontend.table, ids, ok = slots.admit(
            self.frontend.table, jnp.array([True]),
            jnp.array([vid], jnp.int32), jnp.array([0], jnp.int32),
            jnp.int32(self._steps),
            opcodes=jnp.array([OP_CLONE], jnp.int32))
        if not bool(ok[0]):
            self.volumes.delete(vid)
            return None
        child.slot = int(ids[0])
        child.volume = vid
        self.slot_vol[child.slot] = vid
        self.pos[child.slot] = self.pos[src.slot]
        self.live[new_req_id] = child
        if self._zero_copy:
            # both sides' next write to the shared frontier page must ride a
            # write SQE so the in-kernel CoW un-shares it before the decode
            # scatter touches it
            self._cow_pending.add(req_id)
            self._cow_pending.add(new_req_id)
            self._table = self.volumes.device_extent_map()
        return child

    def control(self, kind: str, **kw):
        """Replica-plane control (fail/rebuild/...) on the KV store. The
        engine's pool copy is synced to the live KV first — a rebuild donor
        must see every decode scatter, not just the last pumped state."""
        if self._zero_copy:
            self.volumes.set_device_pools(self._pools)
        out = self.volumes.engine.control(kind, **kw)
        if self._zero_copy:
            self._pools = self.volumes.device_pools()
            self._table = self.volumes.device_extent_map()
        return out

    # ------------------------------------------------------- engine stepping
    def _admit(self) -> List[GenRequest]:
        slot_ids, reqs = self.frontend.poll_batch()
        admitted = []
        for sid, r in zip(jax.device_get(slot_ids), reqs):
            g: GenRequest = r.payload
            g.slot = int(sid)
            g.volume = self.volumes.create().vid
            self.slot_vol[g.slot] = g.volume
            self.live[g.req_id] = g
            admitted.append(g)
        return admitted

    # ---------------------------------------------- zero-copy KV data plane
    def _pump_writes(self) -> None:
        """Complete every queued write SQE in ONE batched pump: page
        allocation and CoW for all lanes resolve inside the engine's fused
        step. The engine's pool copy is synced with ours around the pump
        (the decode program's scatters live in ``self._pools`` between
        pumps), and the extent-map view is refreshed after."""
        self.volumes.set_device_pools(self._pools)
        self.volumes.flush()
        self._pools = self.volumes.device_pools()
        self._table = self.volumes.device_extent_map()

    def _submit_kv_write(self, vid: int, pos: int, payload=None) -> None:
        page = self.cfg.page_blocks
        if payload is None:
            payload = np.zeros(self._payload_shape, np.float32)
        self.volumes.submit(Request(
            req_id=self.volumes._rid(vid), kind="write", volume=vid,
            page=pos // page, block=pos % page, payload=payload))

    def _decode_program(self, params, last, pos, active, bt, pools, caches):
        """One fully-fused decode step over the engine's KV pools: per paged
        layer, scatter the new token's K/V into every replica pool at its
        extent row and attend straight off the pool through the extent map.
        Returns (logits, next tokens, caches, mutated pools)."""
        caches = M.with_block_tables(caches, bt)
        page = self.cfg.page_blocks
        cell = {"pools": tuple(pools), "j": 0}
        lanes = jnp.arange(bt.shape[0])
        dmax = self._dmax

        def paged_fn(q, k_new, v_new, pk, pv, bt_, q_pos, *, window=0,
                     logit_cap=0.0, scale=None):
            j = cell["j"]
            cell["j"] += 1
            _, kd, vd, _ = self._paged[j]
            kp, vp = 2 * j, 2 * j + 1
            p = q_pos[:, 0]
            ext = bt_[lanes, p // page]
            off = p % page
            # inactive lanes and holes scatter nowhere (mode="drop" at -1 —
            # the DBS hole sentinel)
            extw = jnp.where(active & (ext >= 0), ext, -1)
            kn, vn = k_new[:, 0], v_new[:, 0]
            if kn.shape[-1] < dmax:
                kn = jnp.pad(kn, ((0, 0), (0, 0), (0, dmax - kn.shape[-1])))
            if vn.shape[-1] < dmax:
                vn = jnp.pad(vn, ((0, 0), (0, 0), (0, dmax - vn.shape[-1])))
            new_pools = []
            for pool in cell["pools"]:
                pool = pool.at[extw, off, kp].set(kn.astype(pool.dtype),
                                                  mode="drop")
                pool = pool.at[extw, off, vp].set(vn.astype(pool.dtype),
                                                  mode="drop")
                new_pools.append(pool)
            cell["pools"] = tuple(new_pools)
            qk = q[:, 0]                         # (B, H, hd): one token
            if qk.shape[-1] < dmax:
                qk = jnp.pad(qk, ((0, 0), (0, 0), (0, dmax - qk.shape[-1])))
            # the pool's trailing dim is padded to dmax — the kernel's
            # default 1/sqrt(d) would use the padded dim, so pass the true
            # head-dim scale explicitly
            eff_scale = (float(scale) if scale is not None
                         else 1.0 / math.sqrt(kd))
            lengths = p + 1
            if self._attn_pallas:
                out = paged_attention_pool_fwd(
                    qk, cell["pools"][0], bt_, lengths, k_plane=kp,
                    v_plane=vp, window=window, logit_cap=logit_cap,
                    scale=eff_scale,
                    interpret=default_interpret())
            else:
                out = paged_attention_pool_ref(
                    qk, cell["pools"][0], bt_, lengths, k_plane=kp,
                    v_plane=vp, window=window, logit_cap=logit_cap,
                    scale=eff_scale)
            out = out[..., :vd].astype(q.dtype)[:, None]
            return out, pk, pv

        logits, caches = M.decode_step(params, last, pos, self.cfg,
                                       self.plan, caches,
                                       paged_decode_fn=paged_fn)
        nxt = jnp.argmax(logits, axis=-1)
        return logits, nxt, caches, cell["pools"]

    def _prefill_one_zero(self, g: GenRequest) -> None:
        """Prefill a prompt, then push its K/V into the engine pools as
        ordinary write SQEs (one per prompt token/block) — allocation and
        payload ride the same batched pump as every other write; the caller
        flushes once for all admitted prompts."""
        prompt = np.asarray(g.prompt)
        s = prompt.shape[0]
        if s == 0:
            return
        dtype = jnp.dtype(self.plan.compute_dtype)
        # single-sequence prefill with dense K/V caches for the paged
        # layers (their pool content goes to the ENGINE pool, not the
        # model's); recurrent/ring layer caches are the batch rows
        caches_one = []
        for c in self.caches:
            if c is None:
                caches_one.append(None)
                continue
            if "pool_k" in c:
                kd = c["pool_k"].shape[-1]
                vd = c["pool_v"].shape[-1]
                n_kv = c["pool_k"].shape[2]
                caches_one.append({
                    "k": jnp.zeros((1, s, n_kv, kd), dtype),
                    "v": jnp.zeros((1, s, n_kv, vd), dtype)})
            else:
                caches_one.append({k: v[g.slot:g.slot + 1]
                                   for k, v in c.items()})
        tok = jnp.asarray(prompt)[None]
        _logits, caches_one = M.prefill(self.params, tok, self.cfg,
                                        self.plan, caches_one)
        # one payload block per prompt token: every layer's K/V planes
        pay = np.zeros((s,) + self._payload_shape, np.float32)
        kv_host = jax.device_get([(caches_one[li]["k"], caches_one[li]["v"])
                                  for li, *_ in self._paged])
        for j, (_li, kd, vd, _) in enumerate(self._paged):
            k, v = kv_host[j]
            pay[:, 2 * j, :, :kd] = np.asarray(k[0], np.float32)
            pay[:, 2 * j + 1, :, :vd] = np.asarray(v[0], np.float32)
        for t in range(s):
            self._submit_kv_write(g.volume, t, payload=pay[t])
        # recurrent/ring rows back into the batch caches
        for li, (c, c1) in enumerate(zip(self.caches, caches_one)):
            if c is None or "pool_k" in c:
                continue
            cn = dict(c)
            for k, v in c1.items():
                cn[k] = cn[k].at[g.slot].set(v[0])
            self.caches[li] = cn
        self.pos[g.slot] = s

    # --------------------------------------------- copy-based KV data plane
    def _alloc_pages(self, vols, pages, mask):
        """Copy-based control plane: allocate/CoW through the host backend;
        the returned WriteOps drive the model-owned KV pools (one dbs_copy
        per layer pool on CoW — the copies the zero-copy path retires)."""
        ops = self.volumes.alloc_pages(vols, pages, mask=mask)
        if bool(jax.device_get(jnp.any(ops.cow_src >= 0))):
            from repro.kernels.dbs import dbs_copy
            for i, c in enumerate(self.caches):
                if c is not None and "pool_k" in c:
                    c = dict(c)
                    for key in ("pool_k", "pool_v"):
                        p = c[key]
                        flat = p.reshape(p.shape[0], p.shape[1], -1)
                        flat = dbs_copy(flat, ops.cow_src, ops.dst,
                                        ops.cow_src >= 0)
                        c[key] = flat.reshape(p.shape)
                    self.caches[i] = c
        return ops

    def _prefill_one_host(self, g: GenRequest) -> None:
        prompt = np.asarray(g.prompt)
        s = prompt.shape[0]
        if s == 0:
            return
        page = self.cfg.page_blocks
        pad = (-s) % page
        padded = np.pad(prompt, [(0, pad)] + [(0, 0)] * (prompt.ndim - 1))
        n_pages = padded.shape[0] // page
        # allocate all prompt pages up front
        vols = jnp.full((n_pages,), g.volume, jnp.int32)
        self._alloc_pages(vols, jnp.arange(n_pages, dtype=jnp.int32),
                          jnp.ones((n_pages,), bool))
        # single-sequence prefill writing into this engine's pools
        bt_row = self.state.table[g.volume][None, :]
        caches_one = []
        for c in self.caches:
            if c is None:
                caches_one.append(None)
                continue
            c1 = {}
            for k, v in c.items():
                if k.startswith("pool"):
                    c1[k] = v
                elif k == "block_table":
                    c1[k] = bt_row
                else:
                    c1[k] = v[g.slot:g.slot + 1]
            caches_one.append(c1)
        tok = jnp.asarray(padded)[None]
        logits, caches_one = M.prefill(self.params, tok, self.cfg, self.plan,
                                       caches_one)
        # scatter the per-sequence cache rows back; pools are shared already
        new_caches = []
        for c, c1 in zip(self.caches, caches_one):
            if c is None:
                new_caches.append(None)
                continue
            cn = dict(c)
            for k, v in c1.items():
                if k.startswith("pool"):
                    cn[k] = v
                elif k != "block_table":
                    cn[k] = cn[k].at[g.slot].set(v[0])
            new_caches.append(cn)
        self.caches = new_caches
        self.pos[g.slot] = s

    def _prefill_one(self, g: GenRequest) -> None:
        if self._zero_copy:
            self._prefill_one_zero(g)
        else:
            self._prefill_one_host(g)

    # ----------------------------------------------------------------- step
    def step(self) -> List[Tuple[int, int]]:
        """One continuous-batching iteration. Returns [(req_id, token)]."""
        admitted = self._admit()
        pending = False
        for g in admitted:
            self._prefill_one(g)
            pending = pending or (self._zero_copy
                                  and np.asarray(g.prompt).shape[0] > 0)
        active = np.array([self.slot_vol[i] >= 0 and any(
            r.slot == i and not r.done for r in self.live.values())
            for i in range(self.n_slots)])
        if not active.any():
            if pending:
                self._pump_writes()
            return []
        page = self.cfg.page_blocks
        if self._zero_copy:
            # control plane: lanes crossing a page boundary allocate their
            # new page, freshly-forked lanes CoW their shared frontier page
            # — all as write SQEs completed by ONE batched pump
            for i in range(self.n_slots):
                if not active[i]:
                    continue
                g = self.live_by_slot(i)
                if (self.pos[i] % page == 0
                        or g.req_id in self._cow_pending):
                    self._submit_kv_write(int(self.slot_vol[i]),
                                          int(self.pos[i]))
                    self._cow_pending.discard(g.req_id)
                    pending = True
            if pending:
                self._pump_writes()
        vols = jnp.asarray(np.where(active, self.slot_vol, 0), jnp.int32)
        last = jnp.asarray(
            [(self.live_by_slot(i).out_tokens[-1]
              if self.live_by_slot(i) and self.live_by_slot(i).out_tokens
              else self._last_prompt_token(i)) for i in range(self.n_slots)],
            jnp.int32)
        if self.cfg.n_codebooks > 1:
            last = jnp.broadcast_to(last[:, None], (self.n_slots,
                                                    self.cfg.n_codebooks))
        pos_dev = jnp.asarray(self.pos)
        if self._zero_copy:
            # data plane: one fused program — KV scatter into the engine
            # pools + paged attention through the extent map
            bt = self._table[vols]
            logits, nxt, self.caches, self._pools = self._step_fn(
                self.params, last, pos_dev, jnp.asarray(active), bt,
                self._pools, self.caches)
        else:
            pages = jnp.asarray(self.pos // page, jnp.int32)
            self._alloc_pages(vols, pages, jnp.asarray(active))
            # refresh block tables from the DBS extent maps
            bt = self.state.table[vols]
            self.caches = M.with_block_tables(self.caches, bt)
            logits, self.caches = M.decode_step(
                self.params, last, pos_dev, self.cfg, self.plan, self.caches)
            nxt = jnp.argmax(logits, axis=-1)
        if self.cfg.n_codebooks > 1:
            nxt = nxt[:, 0]
        if self.record_logits:
            nxt_host, logits_host = jax.device_get((nxt, logits))
            logits_host = np.asarray(logits_host)
        else:
            nxt_host = np.asarray(jax.device_get(nxt))
            logits_host = None
        self.pos = self.pos + active.astype(np.int32)
        out = []
        self._steps += 1
        for i in range(self.n_slots):
            if not active[i]:
                continue
            g = self.live_by_slot(i)
            g.out_tokens.append(int(nxt_host[i]))
            if logits_host is not None:
                g.logit_trace.append(logits_host[i].copy())
            out.append((g.req_id, int(nxt_host[i])))
            if len(g.out_tokens) >= g.max_new or \
                    int(self.pos[i]) >= self.max_len:
                self._finish(g)
        return out

    def live_by_slot(self, slot: int) -> Optional[GenRequest]:
        for g in self.live.values():
            if g.slot == slot and not g.done:
                return g
        return None

    def _last_prompt_token(self, slot: int) -> int:
        g = self.live_by_slot(slot)
        if g is None or g.prompt.shape[0] == 0:
            return 0
        t = g.prompt[-1]
        return int(t if np.ndim(t) == 0 else t.flat[0])

    def _finish(self, g: GenRequest) -> None:
        g.done = True
        self.frontend.table = slots.retire(
            self.frontend.table, jnp.asarray([g.slot], jnp.int32),
            statuses=jnp.int32(ST_OK))
        self.volumes.delete(g.volume)
        if self._zero_copy:
            self._cow_pending.discard(g.req_id)
        self.slot_vol[g.slot] = -1
        g.slot = -1

    def run(self, max_steps: int = 64) -> Dict[int, List[int]]:
        for _ in range(max_steps):
            self.step()
            if all(g.done for g in self.live.values()) and \
                    self.frontend.depth() == 0:
                break
        return {rid: g.out_tokens for rid, g in self.live.items()}


class ServePool:
    """The serve path over a pool of engine shards (core/sharded.py's scale
    axis applied to serving): S independent ServeEngine "nodes", requests
    hash-sharded by ``req_id % S``, stepped together.

    Each shard keeps its own slot table, DBS metadata and KV pools — the
    same isolation the block-engine ``EnginePool`` gives its shards — so a
    heavy tenant saturates one shard's slots without starving the others.
    Forking stays shard-local (``dbs.clone`` shares extents only within one
    DBS state), so a forked child lives on its parent's shard regardless of
    its req_id; ``_home`` tracks that routing.

    ``**kw`` forwards to ``ServeEngine`` — in particular ``kv_backend=``,
    ``kv_shards=``, ``kv_replicas=`` and ``kernel=``, so a pool of serve
    nodes can each run its KV store on the sharded replicated engine.
    """

    def __init__(self, cfg: ArchConfig, params, *, n_shards: int = 2, **kw):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.shards = [ServeEngine(cfg, params, **kw)
                       for _ in range(n_shards)]
        self._home: Dict[int, int] = {}

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    def shard_of(self, req_id: int) -> int:
        return self._home.get(req_id, req_id % self.n_shards)

    def submit(self, req: GenRequest) -> None:
        # hash routing only — recording it in _home would let a later
        # submit clobber a live forked child's off-hash home
        self.shards[req.req_id % self.n_shards].submit(req)

    def fork(self, req_id: int, new_req_id: int, max_new: int = 16
             ) -> Optional[GenRequest]:
        shard = self.shard_of(req_id)
        child = self.shards[shard].fork(req_id, new_req_id, max_new)
        if child is not None and shard != new_req_id % self.n_shards:
            self._home[new_req_id] = shard       # off-hash: remember it
        return child

    def step(self) -> List[Tuple[int, int]]:
        """One pool iteration: every shard's continuous-batching step."""
        out: List[Tuple[int, int]] = []
        for sh in self.shards:
            out.extend(sh.step())
        for rid in [r for r, s in self._home.items()
                    if self.shards[s].live.get(r) is not None
                    and self.shards[s].live[r].done]:
            del self._home[rid]                  # finished forks: unpin
        return out

    def run(self, max_steps: int = 64) -> Dict[int, List[int]]:
        for _ in range(max_steps):
            self.step()
            if all(all(g.done for g in sh.live.values())
                   and sh.frontend.depth() == 0 for sh in self.shards):
                break
        out: Dict[int, List[int]] = {}
        for sh in self.shards:
            out.update({rid: g.out_tokens for rid, g in sh.live.items()})
        return out
