"""Device-side Direct Block Store (paper §IV-D), with HBM as the medium.

Faithful structure (see Fig. 5 of the paper):

- the *storage medium* is a fixed pool of **extents** (KV pages); payload
  arrays live alongside and are indexed by extent id,
- the *extent-status region* is ``extent_owner`` (owning snapshot per extent)
  plus a per-extent **block bitmap** (paper: 32 × 4 KB blocks per 1 MB extent;
  here: ``page_blocks`` tokens per page, bitmap in one uint32),
- *volume & snapshot metadata* are fixed tables (``vol_head``,
  ``snap_parent``, ``snap_vol``),
- the *superblock allocation mark* becomes the free-extent **SlotRing** — the
  Messages-Array idiom applied to allocation, so only actual allocations
  serialize (paper: "Only writes to unallocated space require serialization"),
- the **in-memory extent map** that makes reads O(1) and snapshot-count
  independent is ``table[vol, page] -> extent`` — never stored on the medium,
  rebuilt from the chain on restart (host store) exactly like DBS.

Semantics implemented on device (everything jit-traceable, functional state):
create/delete volume, snapshot, clone(=fork), copy-on-write writes, O(1)
reads, unmap. Snapshot *merge-deletion* is host-side only (checkpoint store),
as it is an offline maintenance path in the paper too.

``write_pages`` is the control plane; the data plane is either
``apply_write_ops`` (gather/scatter reference) or the Pallas ``dbs_copy``
kernel on the fused hot path (core/fused.py, docs/KERNELS.md).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.slots import SlotRing, acquire, make_ring, release

NULL = jnp.int32(-1)


@jax.tree_util.register_dataclass
@dataclass
class DBSState:
    # extent-status region
    extent_owner: jnp.ndarray   # (E,) int32 snapshot id, -1 = free
    bitmap: jnp.ndarray         # (E,) uint32 allocated-block bits
    free: SlotRing              # available extent ids (superblock mark analogue)
    # volume / snapshot metadata region
    vol_head: jnp.ndarray       # (V,) int32 head snapshot, -1 = unused volume
    snap_parent: jnp.ndarray    # (S,) int32 parent snapshot, -1 root, -2 unused
    snap_vol: jnp.ndarray       # (S,) int32 owning volume
    n_snaps: jnp.ndarray        # () int32 next snapshot id (monotone)
    # in-memory flattened extent maps (one per volume)
    table: jnp.ndarray          # (V, P) int32 page -> extent, -1 = hole
    # mirroring metadata (paper §III: replica consistency "version")
    revision: jnp.ndarray       # () int32 bumped on every mutating op

    @property
    def n_extents(self) -> int:
        return self.extent_owner.shape[0]


def make_state(n_extents: int, max_volumes: int, max_pages: int,
               max_snapshots: int = 0) -> DBSState:
    s = max_snapshots or (4 * max_volumes)
    return DBSState(
        extent_owner=jnp.full((n_extents,), NULL, jnp.int32),
        bitmap=jnp.zeros((n_extents,), jnp.uint32),
        free=make_ring(n_extents),
        vol_head=jnp.full((max_volumes,), NULL, jnp.int32),
        snap_parent=jnp.full((s,), -2, jnp.int32),
        snap_vol=jnp.full((s,), NULL, jnp.int32),
        n_snaps=jnp.zeros((), jnp.int32),
        table=jnp.full((max_volumes, max_pages), NULL, jnp.int32),
        revision=jnp.zeros((), jnp.int32),
    )


def _bump(st: DBSState) -> DBSState:
    return dataclasses.replace(st, revision=st.revision + 1)


# ---------------------------------------------------------------------------
# volume lifecycle
# ---------------------------------------------------------------------------
def create_volume(st: DBSState) -> Tuple[DBSState, jnp.ndarray]:
    """New empty volume (fresh root snapshot). Returns (state, vol_id|-1)."""
    vid = jnp.argmin(st.vol_head >= 0).astype(jnp.int32)      # first -1 slot
    sid = st.n_snaps
    ok = (st.vol_head[vid] < 0) & (sid < st.snap_parent.shape[0])
    st = dataclasses.replace(
        st,
        vol_head=st.vol_head.at[vid].set(jnp.where(ok, sid, st.vol_head[vid])),
        snap_parent=st.snap_parent.at[sid].set(
            jnp.where(ok, NULL, st.snap_parent[sid])),
        snap_vol=st.snap_vol.at[sid].set(jnp.where(ok, vid, st.snap_vol[sid])),
        n_snaps=st.n_snaps + ok.astype(jnp.int32),
        table=st.table.at[vid].set(jnp.where(ok, NULL, st.table[vid])),
    )
    return _bump(st), jnp.where(ok, vid, NULL)


def snapshot(st: DBSState, vol: jnp.ndarray) -> Tuple[DBSState, jnp.ndarray]:
    """Freeze the volume head; subsequent writes copy-on-write."""
    sid = st.n_snaps
    ok = (st.vol_head[vol] >= 0) & (sid < st.snap_parent.shape[0])
    st = dataclasses.replace(
        st,
        snap_parent=st.snap_parent.at[sid].set(
            jnp.where(ok, st.vol_head[vol], st.snap_parent[sid])),
        snap_vol=st.snap_vol.at[sid].set(jnp.where(ok, vol, st.snap_vol[sid])),
        vol_head=st.vol_head.at[vol].set(
            jnp.where(ok, sid, st.vol_head[vol])),
        n_snaps=st.n_snaps + ok.astype(jnp.int32),
    )
    return _bump(st), jnp.where(ok, sid, NULL)


def clone(st: DBSState, src_vol: jnp.ndarray) -> Tuple[DBSState, jnp.ndarray]:
    """Fork a new volume from src's current state (prefix sharing).

    Implemented as: snapshot(src) (freezing shared pages), then a new volume
    whose root snapshot's parent is that snapshot and whose flattened extent
    map is a copy of src's — both volumes now CoW against the shared extents.
    """
    st, frozen = snapshot(st, src_vol)
    vid = jnp.argmin(st.vol_head >= 0).astype(jnp.int32)
    sid = st.n_snaps
    ok = ((st.vol_head[vid] < 0) & (frozen >= 0)
          & (sid < st.snap_parent.shape[0]))
    st = dataclasses.replace(
        st,
        vol_head=st.vol_head.at[vid].set(jnp.where(ok, sid, st.vol_head[vid])),
        snap_parent=st.snap_parent.at[sid].set(
            jnp.where(ok, frozen, st.snap_parent[sid])),
        snap_vol=st.snap_vol.at[sid].set(jnp.where(ok, vid, st.snap_vol[sid])),
        n_snaps=st.n_snaps + ok.astype(jnp.int32),
        table=st.table.at[vid].set(
            jnp.where(ok, st.table[src_vol], st.table[vid])),
    )
    return _bump(st), jnp.where(ok, vid, NULL)


def _free_extents(st: DBSState, mask: jnp.ndarray) -> DBSState:
    """Return masked extents to the free ring, clear their status."""
    e = st.n_extents
    ids = jnp.where(mask, jnp.arange(e, dtype=jnp.int32), -1)
    ring = release(st.free, ids)
    return dataclasses.replace(
        st, free=ring,
        extent_owner=jnp.where(mask, NULL, st.extent_owner),
        bitmap=jnp.where(mask, jnp.uint32(0), st.bitmap))


def delete_volume(st: DBSState, vol: jnp.ndarray) -> DBSState:
    """Delete the volume's snapshot chain and free all its extents.

    Extents are shared with clones via *other volumes'* snapshots, so only
    extents whose owning snapshot belongs to this volume are freed; clone
    chains keep their frozen parents (their snap_vol is the ancestor volume —
    matching Longhorn, where a volume can only be deleted once rebuilt/
    detached clones no longer reference its snapshots; the serving layer
    tracks child references and retargets snap_vol on fork).
    """
    ok = st.vol_head[vol] >= 0
    owner_vol = jnp.where(st.extent_owner >= 0,
                          st.snap_vol[st.extent_owner], NULL)
    # extents owned by this volume's snapshots, minus those referenced by any
    # other live volume's flattened table (prefix sharing from clones)
    mine = ok & (owner_vol == vol)
    live_vols = (st.vol_head >= 0) & (jnp.arange(st.vol_head.shape[0]) != vol)
    referenced = jnp.zeros((st.n_extents + 1,), bool).at[
        jnp.where(live_vols[:, None], st.table + 1, 0)].max(True)[1:]
    st = _free_extents(st, mine & ~referenced)
    snaps_of_vol = st.snap_vol == vol
    st = dataclasses.replace(
        st,
        vol_head=st.vol_head.at[vol].set(jnp.where(ok, NULL, st.vol_head[vol])),
        table=st.table.at[vol].set(jnp.where(ok, NULL, st.table[vol])),
        snap_parent=jnp.where(snaps_of_vol & ok, -2, st.snap_parent),
    )
    return _bump(st)


# ---------------------------------------------------------------------------
# I/O path
# ---------------------------------------------------------------------------
def read_resolve(st: DBSState, vol: jnp.ndarray, pages: jnp.ndarray
                 ) -> jnp.ndarray:
    """(B,) page ids -> (B,) extent ids (-1 for holes). O(1) per page and
    independent of snapshot-chain depth — the paper's headline DBS property
    (validated by tests/test_dbs_properties.py and benchmarks/table1)."""
    return st.table[vol, pages]


def _group_lanes(vol: jnp.ndarray, pages: jnp.ndarray,
                 block_bits: jnp.ndarray, mask: jnp.ndarray, max_pages: int):
    """Group write lanes that target the same (vol, page) pair.

    Returns (leader (B,) int32 — the first live lane of each group,
    is_leader (B,) bool, group_bits (B,) uint32 — the OR of the group's
    block bitmaps, meaningful on leader lanes). The (B, B) comparison is
    tiny next to the extent pools and keeps everything vmap-safe.
    """
    b = pages.shape[0]
    volb = jnp.broadcast_to(vol, pages.shape).astype(jnp.int32)
    key = volb * jnp.int32(max_pages) + pages
    same = mask[:, None] & mask[None, :] & (key[:, None] == key[None, :])
    leader = jnp.argmax(same, axis=1).astype(jnp.int32)
    is_leader = mask & (leader == jnp.arange(b, dtype=jnp.int32))
    group_bits = jax.lax.reduce(
        jnp.where(same, block_bits[None, :], jnp.uint32(0)),
        jnp.uint32(0), jax.lax.bitwise_or, (1,))
    return leader, is_leader, group_bits


def write_pages(st: DBSState, vol: jnp.ndarray, pages: jnp.ndarray,
                block_bits: jnp.ndarray, mask=None):
    """Write blocks in (possibly new) pages.

    vol: scalar volume id, or (B,) vector (one volume per lane — the serving
    engine's "one write per active sequence per step"). pages: (B,) page
    indices; block_bits: (B,) uint32 masks of blocks written. Returns
    (state, WriteOps) where WriteOps tells the data plane which extents to
    touch and which CoW copies to perform.

    Lanes targeting the same (vol, page) pair are GROUPED: the group's first
    live lane (the leader) resolves allocation/CoW once with the OR of the
    group's block bitmaps, and every member lane inherits the leader's
    destination extent — so a byte-addressed span that fans out to many
    blocks of one page (core/blockdev.py) is one allocation plus N block
    stores, exactly like the sequential one-write-per-call reference.
    Duplicate (vol, page, *block*) lanes remain undefined-order (scatter
    semantics); callers serialize overlapping-block writes across batches.
    """
    vol = jnp.asarray(vol)
    if mask is None:
        mask = jnp.ones(pages.shape, bool)
    leader, is_leader, group_bits = _group_lanes(
        vol, pages, block_bits, mask, st.table.shape[1])
    head = st.vol_head[vol]                                     # scalar or (B,)
    ext = st.table[vol, pages]                                  # (B,)
    owner = jnp.where(ext >= 0, st.extent_owner[jnp.maximum(ext, 0)], NULL)
    in_place = (ext >= 0) & (owner == head) & is_leader
    need_alloc = is_leader & ~in_place                          # hole or CoW
    ring, new_ids, got = acquire(st.free, pages.shape[0], need_alloc)
    dst = jnp.where(in_place, ext, new_ids)                     # -1 if starved
    ok = (in_place | got) & is_leader
    is_cow = ok & (~in_place) & (ext >= 0)

    safe_dst = jnp.maximum(dst, 0)
    old_bits = jnp.where(is_cow, st.bitmap[jnp.maximum(ext, 0)], jnp.uint32(0))
    new_bits = (st.bitmap[safe_dst] * in_place.astype(jnp.uint32)
                | old_bits | group_bits)
    # lanes that perform no write scatter to an out-of-bounds index and are
    # dropped: a write-back of the "current" value is NOT inert when another
    # lane targets the same slot in the batch (duplicate-index scatter order
    # is undefined, so the stale write-back can win) — e.g. the fused step
    # routes read lanes through here with mask=False, and only group leaders
    # may touch the metadata scatters at all.
    drop_ext = jnp.where(ok, safe_dst, st.n_extents)
    drop_page = jnp.where(ok, pages, st.table.shape[1])
    st = dataclasses.replace(
        st, free=ring,
        extent_owner=st.extent_owner.at[drop_ext].set(
            jnp.broadcast_to(head, drop_ext.shape), mode="drop"),
        bitmap=st.bitmap.at[drop_ext].set(new_bits, mode="drop"),
        table=st.table.at[vol, drop_page].set(dst, mode="drop"),
    )
    # expand leader results to every member lane: the data plane stores each
    # lane's block into its group's destination extent (one CoW copy per
    # group — cow_src stays leader-only)
    ok_all = mask & ok[leader]
    dst_all = dst[leader]
    ops = WriteOps(dst=jnp.where(ok_all, dst_all, NULL),
                   cow_src=jnp.where(is_cow, ext, NULL),
                   ok=ok_all)
    return _bump(st), ops


@jax.tree_util.register_dataclass
@dataclass
class WriteOps:
    """One write batch's data-plane orders, as ``write_pages`` emits them.

    Two conventions every data plane may rely on: ``cow_src`` sits only on
    the first live lane of each ``dst`` group (the group leader), and a CoW
    source is never a live lane's destination in the same batch — sources
    are owned by a frozen snapshot, destinations by the live head or the
    free ring. The ``xla`` reference reads every source before writing any
    row; the compiled Pallas kernels (``dbs_rw_write``, ``dbs_copy``)
    stream rows, so a source written earlier in the same batch would read
    back the new bytes.
    """
    dst: jnp.ndarray       # (B,) destination extents (-1 = failed/starved)
    cow_src: jnp.ndarray   # (B,) source extents to copy first (-1 = none)
    ok: jnp.ndarray        # (B,) bool

    def live(self) -> jnp.ndarray:
        """Lanes that write: ``ok`` AND a real destination. ``dst = -1``
        marks a failed or starved lane and wins over ``ok``, so such a lane
        writes nothing — no CoW copy, no block store. ``write_pages`` never
        emits one; hand-built batches may, and every data plane (the
        ``xla`` reference and the kernels' routing) reads them this way."""
        return self.ok & (self.dst >= 0)


def apply_write_ops(pool: jnp.ndarray, ops: WriteOps,
                    payload: jnp.ndarray, block_offsets: jnp.ndarray
                    ) -> jnp.ndarray:
    """Data-plane half of a write: CoW copies then payload stores.

    pool: (E, page, ...); payload: (B, ...) one block per lane;
    block_offsets: (B,) position of the written block within its page.
    """
    live = ops.live()
    safe_dst = jnp.maximum(ops.dst, 0)
    safe_src = jnp.maximum(ops.cow_src, 0)
    do_copy = (ops.cow_src >= 0) & live
    # only COPY lanes touch the whole-extent scatter: a write-back of the
    # "current" extent value is NOT inert when another lane of the batch
    # shares the destination (grouped same-page writes, see write_pages) —
    # the stale write-back could clobber the leader's CoW copy. Failed and
    # non-copy lanes scatter out of bounds and are dropped.
    drop_copy = jnp.where(do_copy, safe_dst, pool.shape[0])
    pool = pool.at[drop_copy].set(pool[safe_src], mode="drop")
    drop_dst = jnp.where(live, safe_dst, pool.shape[0])
    pool = pool.at[drop_dst, block_offsets].set(payload, mode="drop")
    return pool


def unmap(st: DBSState, vol: jnp.ndarray, pages: jnp.ndarray) -> DBSState:
    """Drop pages from a volume (TRIM). Extents owned by the live head are
    freed; snapshot-owned extents just unlink (data stays for the snapshot).
    Sliding-window layers use this to retire pages behind the window."""
    head = st.vol_head[vol]
    ext = st.table[vol, pages]
    valid = ext >= 0
    safe = jnp.maximum(ext, 0)
    owned_by_head = valid & (st.extent_owner[safe] == head)
    e = st.n_extents
    # scatter through a dump slot (index e) so non-owned lanes cannot clobber
    free_mask = jnp.zeros((e + 1,), bool).at[
        jnp.where(owned_by_head, ext, e)].set(True)[:e]
    st = _free_extents(st, free_mask)
    st = dataclasses.replace(
        st, table=st.table.at[vol, pages].set(jnp.where(valid, NULL, ext)))
    return _bump(st)


# ---------------------------------------------------------------------------
# introspection (host-side convenience, used by tests/engine)
# ---------------------------------------------------------------------------
def stats(st: DBSState) -> dict:
    return {
        "extents_free": int(jax.device_get(st.free.tail - st.free.head)),
        "extents_used": int(jax.device_get(jnp.sum(st.extent_owner >= 0))),
        "volumes": int(jax.device_get(jnp.sum(st.vol_head >= 0))),
        "snapshots": int(jax.device_get(st.n_snaps)),
        "revision": int(jax.device_get(st.revision)),
    }
