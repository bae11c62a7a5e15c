"""The ring step's COMPUTE phase: storage functions executed in-program.

Sits between the data phase and the control tail in ``ring_step_core``
(core/ring.py). The drain policy guarantees compute lanes are contiguous
and never share a batch with control lanes (compute is its own batch rank:
data < compute < control, cut on every rank change), so — exactly like the
``_apply_vol_ops`` control tail — a ``compute_tail``-lane dynamic-slice
window anchored at the first compute lane covers all of them, and a
``lax.scan`` over the window applies submission order with a fixed trace
structure. Each lane is a masked ``lax.switch`` over the registered
storage-function table (registration order = SQE ``fn``-lane id; padding
and non-compute lanes take the noop branch).

The function input is the hole-masked full-volume lane view gathered from
the FIRST healthy replica (replicas are bit-identical by the mirrored-write
invariant, so first-healthy needs no rr fairness; the one-hot ``where``
chain is the vmap-safe selection idiom of ``_rr_gather``). The gather is a
plain XLA take — compute scans the whole volume, and the registry kernels'
paged read path buys nothing for a full-table gather.

Writes (``compare_and_write``): the drain admits at most ONE writing
compute per batch (it closes the compute window), so the commit is a single
batch-shaped mirrored CoW write using the configured registry kernel —
literally the data phase's write machinery with a one-hot mask, which is
what "riding the CoW write path" means here. The scan itself never carries
the pools.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.compute import registry as sfns
from repro.core import dbs
from repro.core.fused import _cow_apply, _cow_work
from repro.core.transport import stamp_page_rev


def volume_content(state: dbs.DBSState, pool: jnp.ndarray,
                   vol: jnp.ndarray) -> jnp.ndarray:
    """Hole-masked (P, page_blocks, *S) lane view of one volume: never-written
    and unmapped pages (ext < 0) read as zeros, like OP_READ."""
    n_vols = state.table.shape[0]
    ext = state.table[jnp.clip(vol, 0, n_vols - 1)]          # (P,)
    got = pool[jnp.maximum(ext, 0)]                          # (P, pb, *S)
    mask = (ext >= 0).reshape((-1,) + (1,) * (got.ndim - 1))
    return jnp.where(mask, got, jnp.zeros((), pool.dtype))


def apply_compute_ops(states, pools, page_revs, healthy, batch, mask,
                      value, status, reads, *, kernel: str, tail: int):
    """Apply the batch's compute lanes in lane order. ``mask`` is
    ``ok & (op == OP_COMPUTE)``. Returns updated
    ``(states, pools, page_revs, value, status, reads)`` and the int32
    ``(rows, calls)`` the CAS commit's write kernel adds to ``CQ.work``."""
    table = sfns.device_table()
    n_fns = len(table)
    b_n = batch.op.shape[0]
    k = min(tail, b_n)
    start = jnp.clip(jnp.argmax(mask), 0, b_n - k)
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, k)
    vol_w, page_w, blk_w = sl(batch.volume), sl(batch.page), sl(batch.block)
    fn_w, arg_w, pay_w = sl(batch.fn), sl(batch.arg), sl(batch.payload)
    live_w = sl(mask)            # edge-clamped data lanes are masked out

    # first-healthy replica selection (one-hot where chain: vmap-safe)
    h = healthy
    sel = h & (jnp.cumsum(h.astype(jnp.int32)) - 1 == 0)

    def content_of(vol):
        out = jnp.zeros_like(volume_content(states[0], pools[0], vol))
        for r in range(len(states)):
            out = jnp.where(sel[r],
                            volume_content(states[r], pools[r], vol), out)
        return out

    n_vols = states[0].table.shape[0]

    def lane(carry, xs):
        vol, page, blk, fid, arg, pay, live = xs
        live = live & (vol >= 0) & (vol < n_vols)
        content = content_of(vol)
        branch = jnp.where(live, jnp.clip(fid, 0, n_fns - 1) + 1, 0)

        def b_noop(_):
            return (jnp.int32(-1), jnp.int32(0), jnp.zeros_like(pay),
                    jnp.asarray(False))

        def b_fn(entry):
            def b(_):
                v, st, out, dw = entry.apply(content, page, blk, arg, pay)
                return (v.astype(jnp.int32), st.astype(jnp.int32),
                        out.astype(pay.dtype), jnp.asarray(dw))
            return b

        v, st, out, dw = jax.lax.switch(
            branch, [b_noop] + [b_fn(e) for e in table], None)
        return carry, (v, st, out, dw & live)

    _, (vals, stts, outs, do_ws) = jax.lax.scan(
        lane, None, (vol_w, page_w, blk_w, fn_w, arg_w, pay_w, live_w))

    value = jax.lax.dynamic_update_slice_in_dim(
        value, jnp.where(live_w, vals, sl(value)), start, axis=0)
    status = jax.lax.dynamic_update_slice_in_dim(
        status, jnp.where(live_w, stts, sl(status)), start, axis=0)
    live_b = live_w.reshape((-1,) + (1,) * (outs.ndim - 1))
    reads = jax.lax.dynamic_update_slice_in_dim(
        reads, jnp.where(live_b, outs, sl(reads)), start, axis=0)

    work = jnp.zeros((2,), jnp.int32)
    if any(e.writes for e in table):
        # single CAS commit (at most one do_write lane per batch): scatter
        # the window's one-hot write mask back to batch shape and run the
        # data phase's mirrored CoW write against it
        first_w = do_ws & (jnp.cumsum(do_ws.astype(jnp.int32)) == 1)
        wmask = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros((b_n,), bool), first_w, start, axis=0)
        bits = jnp.uint32(1) << batch.block.astype(jnp.uint32)
        out_states, out_pools, out_prs = [], [], []
        for i, st in enumerate(states):
            st, wops = dbs.write_pages(st, batch.volume, batch.page, bits,
                                       wmask & healthy[i])
            work = work + _cow_work(pools[i], wops, batch.block, kernel)
            out_pools.append(_cow_apply(pools[i], wops, batch.payload,
                                        batch.block, kernel))
            out_prs.append(stamp_page_rev(page_revs[i], batch.volume,
                                          batch.page, wops.ok, st.revision))
            out_states.append(st)
        states, pools, page_revs = (tuple(out_states), tuple(out_pools),
                                    tuple(out_prs))

    return states, pools, page_revs, value, status, reads, work
