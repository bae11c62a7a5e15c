"""The DBS kernel family's shared ops surface.

One module serves both kernels: ``default_interpret`` (the interpret rule
of ``repro.kernels.platform``), the pure shape-adapting pool wrappers the
engine step traces inline (``dbs_copy_pool``, ``dbs_rw_write_pool``,
``dbs_rw_read_pool``), and the nominal-bytes accounting the roofline gate
charges each kernel with. See docs/KERNELS.md for the grid/BlockSpec design
and the interpret-mode staleness rule the routing here exists to satisfy.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.dbs.copy_kernel import dbs_copy as _dbs_copy_kernel
from repro.kernels.dbs.ref import dbs_copy_ref
from repro.kernels.dbs.rw_kernel import dbs_rw_read, dbs_rw_write
from repro.kernels.platform import default_interpret


@partial(jax.jit, static_argnames=("interpret",))
def _dbs_copy_jit(pool, src, dst, mask, interpret):
    return _dbs_copy_kernel(pool, src, dst, mask, interpret=interpret)


def dbs_copy(pool, src, dst, mask):
    """Copy pool[src[i]] -> pool[dst[i]] where mask[i] (CoW data plane).

    pool: (E, page, D); trailing payload dims must be pre-flattened to D.
    The interpret mode is resolved per CALL and keys the jit cache as a
    static arg — a backend change after the first call re-dispatches to the
    right specialization instead of silently reusing the mode captured at
    first trace (the bug the old module-level ``@jax.jit`` had).
    """
    return _dbs_copy_jit(pool, src, dst, mask, default_interpret())


def dbs_copy_pool(pool, src, dst, mask, *, interpret=None, scratch=False):
    """Extent CoW copy over an (E, page, *payload) engine pool.

    Flattens the trailing payload dims to the kernel's (E, page, D) layout
    and restores them. Not jitted itself — it is traced inside the caller's
    program (the fused engine step), which is the whole point: the copy
    happens device-side with no intervening dispatch.

    Masked-off lanes are redirected to a scratch extent rather than clamped
    into the live range: grid steps run sequentially against the aliased
    output, but interpret mode reads each step's inputs from the *original*
    buffer, so a masked lane clamped onto a real lane's dst would overwrite
    the copy with stale contents. With ``scratch=True`` the pool's LAST row
    is that dump — the caller guarantees the allocator never hands it out
    (ReplicaGroup sizes pools to n_extents+1), keeping the kernel fully
    aliased. With ``scratch=False`` a zero row is appended and sliced off
    instead (two pool copies — fine for ad-hoc use, not the hot path).
    src/dst may be -1 on masked lanes (the WriteOps NULL convention); real
    lanes must be in range.
    """
    if interpret is None:
        interpret = default_interpret()
    e, page = pool.shape[:2]
    flat = pool.reshape(e, page, -1)
    m = mask.astype(bool)
    if scratch:
        dump = e - 1                 # reserved row, never allocator-visible
        padded = flat
    else:
        dump = e
        padded = jnp.concatenate(
            [flat, jnp.zeros((1,) + flat.shape[1:], flat.dtype)])
    src_r = jnp.where(m, jnp.maximum(src, 0), dump)  # masked: dump->dump
    dst_r = jnp.where(m, jnp.maximum(dst, 0), dump)
    out = _dbs_copy_kernel(padded, src_r, dst_r, m, interpret=interpret)
    return out[:e].reshape(pool.shape)


def _route_writes(ops, page, block_offsets, dump):
    """Route a WriteOps batch into the write kernel's one-row-per-lane form.

    ``write_pages`` groups duplicate (volume, page) lanes under one leader
    that allocated/CoW'd the shared destination extent; the kernel needs the
    inverse view — per ROW, which lane writes which block. Elect the first
    live lane of each dst group leader (for control-plane ops that is
    exactly write_pages' leader, the lane carrying ``cow_src``; hand-built
    batches must follow the same convention), build its (page,) block ->
    writing-lane map with a scatter-max (the HIGHEST lane wins a block, the
    order XLA's sequential scatter applies duplicate updates in), and park
    every other lane on the ``dump`` row with ``src == dst`` so its write is
    a bit-identical no-op. Returns (src, dst, lane_of) for ``dbs_rw_write``.
    """
    b = ops.dst.shape[0]
    arange = jnp.arange(b, dtype=jnp.int32)
    ok = ops.live()
    same = ok[None, :] & ok[:, None] & (ops.dst[None, :] == ops.dst[:, None])
    leader = jnp.argmax(same, axis=1)       # first live lane sharing my dst
    is_leader = ok & (leader == arange)
    blk = jnp.full((b + 1, page), -1, jnp.int32)
    blk = blk.at[jnp.where(ok, leader, b), block_offsets].max(arange)[:b]
    lane_of = jnp.where(is_leader[:, None], blk, -1)
    src = jnp.where(is_leader,
                    jnp.where(ops.cow_src >= 0, ops.cow_src, ops.dst), dump)
    dst = jnp.where(is_leader, ops.dst, dump)
    return src, dst, lane_of


def _row_moves(rows):
    """Row DMAs a 1-D grid's index map requests over the per-step row ids
    ``rows``: one per run of equal ids, since the Pallas pipeline neither
    re-fetches an input block nor writes back an output block whose index
    repeats the previous grid step's."""
    return 1 + jnp.sum(rows[1:] != rows[:-1]).astype(jnp.int32)


def dbs_rw_write_rows(pool, ops, block_offsets):
    """Extent rows one engine-convention ``dbs_rw_write_pool`` call moves:
    source-row fetches plus destination-row write-backs, counted from the
    same routed ``src``/``dst`` vectors the kernel receives. Each row is
    ``page * D`` pool elements; the payload is fetched once per call on top
    (its index map is constant)."""
    e, page = pool.shape[:2]
    src, dst, _ = _route_writes(ops, page, block_offsets, e - 1)
    return _row_moves(src) + _row_moves(dst)


def dbs_rw_write_pool(pool, ops, payload, block_offsets, *, interpret=None,
                      scratch=True):
    """The whole write data plane — CoW copy + payload block stores — as one
    ``dbs_rw_write`` pass over an (E, page, *payload) engine pool.

    Bit-identical to ``dbs.apply_write_ops`` (the ``kernel="xla"``
    reference) excluding the dump row. ``scratch=True`` reuses the pool's
    reserved last row as the dump (the engine convention — the kernel stays
    fully input/output-aliased); ``scratch=False`` appends and slices off a
    throwaway row for ad-hoc pools.
    """
    if interpret is None:
        interpret = default_interpret()
    e, page = pool.shape[:2]
    flat = pool.reshape(e, page, -1)
    pay = payload.reshape(payload.shape[0], -1)
    if scratch:
        dump = e - 1
        padded = flat
    else:
        dump = e
        padded = jnp.concatenate(
            [flat, jnp.zeros((1,) + flat.shape[1:], flat.dtype)])
    src, dst, lane_of = _route_writes(ops, page, block_offsets, dump)
    out = dbs_rw_write(padded, src, dst, lane_of, pay, interpret=interpret)
    return out[:e].reshape(pool.shape)


def dbs_rw_read_pool(pool, ext, block_offsets, *, interpret=None):
    """Hole-masked block gather over an (E, page, *payload) engine pool:
    returns (B, *payload); lanes with ``ext < 0`` read as zeros."""
    if interpret is None:
        interpret = default_interpret()
    e, page = pool.shape[:2]
    flat = pool.reshape(e, page, -1)
    out = dbs_rw_read(flat, ext, block_offsets, interpret=interpret)
    return out.reshape((ext.shape[0],) + pool.shape[2:])


# ---------------------------------------------------------------------------
# nominal-bytes accounting (the roofline gate's numerator)
# ---------------------------------------------------------------------------
def dbs_write_bytes(n_lanes: int, n_cow: int, page_blocks: int,
                    block_elems: int, itemsize: int) -> int:
    """Bytes a write batch SEMANTICALLY moves (implementation-independent,
    so achieved-bytes/s ratios compare across kernels): each CoW lane reads
    + writes one whole extent row, each live lane writes one block."""
    row = page_blocks * block_elems * itemsize
    return n_cow * 2 * row + n_lanes * block_elems * itemsize


def dbs_read_bytes(n_lanes: int, block_elems: int, itemsize: int) -> int:
    """Bytes a read batch semantically moves: one block read + written out
    per lane."""
    return 2 * n_lanes * block_elems * itemsize


dbs_copy_reference = dbs_copy_ref
