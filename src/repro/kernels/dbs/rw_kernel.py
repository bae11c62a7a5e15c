"""``dbs_rw``: the DBS write-scatter / read-gather pair as Pallas kernels.

The write kernel owns the WHOLE write data plane of a batch — CoW extent
copy AND payload block stores in one pass — where ``dbs_copy`` only ran the
copy half and left the block scatter to XLA. The read kernel owns the
round-robin gather, hole masking included. Both follow the jetstream
ragged-attention model: a 1-D grid with scalar-prefetch operands driving
the BlockSpec index maps, so each grid step's HBM<->VMEM DMAs are issued
from data-dependent extent ids and double-buffered by the Pallas pipeline
emitter (step i+1's row fetch overlaps step i's compute/write-back).

Write grid: one step per batch lane, but only GROUP LEADER lanes touch a
real extent row — a leader composes its destination row per block from
either a member lane's payload (``lane_of``) or the source row (the CoW
source when copying, the destination itself when writing in place) and
writes the row ONCE. Routing every non-leader/masked lane to a reserved
dump row is what makes the kernel agree between interpret mode (each step
reads the original buffer) and compiled execution (each step reads HBM as
earlier steps left it): no two grid steps ever write the same live row,
and no step reads a row another step wrote — CoW sources are never
destinations of the same batch (the ``dbs.WriteOps`` contract).

Read grid: one step per read lane; the index map fetches the aligned tile
of 8 blocks (``read_tile_rows``) holding the lane's block from the clamped
extent id — the TPU tiles the pool's (page, D) dims in (8, 128), so a
single-block fetch is not a legal DMA — and the kernel selects the block
in VMEM, masking holes (``ext < 0``) to zeros with the RAW extent id.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _write_kernel(src_ref, dst_ref, lane_ref, src_row, payload, o_ref):
    i = pl.program_id(0)
    page = o_ref.shape[1]
    o_ref[...] = src_row[...]

    # SMEM yields one scalar per load, so the block -> lane map is walked
    # block by block; each taken block is one (1, D) row copy out of the
    # VMEM-resident payload
    def put(j, carry):
        lane = lane_ref[i * page + j]

        @pl.when(lane >= 0)
        def _():
            o_ref[0, pl.ds(j, 1), :] = payload[pl.ds(lane, 1), :]
        return carry

    jax.lax.fori_loop(0, page, put, 0)


def dbs_rw_write(pool, src, dst, lane_of, payload, *, interpret):
    """pool: (E, page, D); src/dst: (B,) int32 extent ids; lane_of: (B, page)
    int32 block -> payload lane (-1 keeps the source block); payload: (B, D).

    src/dst must be PRE-ROUTED (ops.py ``_route_writes``): every live row is
    named by exactly one lane, and inert lanes point src == dst at a dump
    row so their write is a bit-identical no-op.
    """
    e, page, d = pool.shape
    b = src.shape[0]
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,          # src, dst, flat lane_of
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, page, d),
                             lambda i, s, dt, ln: (s[i], 0, 0)),
                # whole payload: constant index map, so the pipeline keeps
                # it resident in VMEM instead of re-fetching per step
                pl.BlockSpec((b, d), lambda i, s, dt, ln: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, page, d),
                                   lambda i, s, dt, ln: (dt[i], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},        # pool (first tensor arg) -> out
        interpret=interpret,
        name="dbs_rw_write",
    )(src, dst, lane_of.reshape(-1), pool, payload)


def _read_kernel(ext_ref, blk_ref, tile, o_ref):
    i = pl.program_id(0)
    row = blk_ref[i] % tile.shape[1]
    got = tile[0, pl.ds(row, 1), :]
    o_ref[0] = jnp.where(ext_ref[i] >= 0, got, 0)


def read_tile_rows(page: int) -> int:
    """Blocks per read-kernel DMA: the TPU tiles an (E, page, D) pool's
    (page, D) dims in (8, 128) tiles, so the smallest legal row slice is
    8 blocks (the whole page when page is not a multiple of 8)."""
    return 8 if page % 8 == 0 else page


def dbs_rw_read(pool, ext, block, *, interpret):
    """pool: (E, page, D); ext: (B,) int32, -1 = hole (reads as zeros);
    block: (B,) int32 block offset within the page. Returns (B, D)."""
    e, page, d = pool.shape
    b = ext.shape[0]
    rows = read_tile_rows(page)
    return pl.pallas_call(
        _read_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # ext (raw), block
            grid=(b,),
            # the aligned tile of `rows` blocks holding the lane's block,
            # from the clamped extent id (the raw id masks the hole)
            in_specs=[pl.BlockSpec(
                (1, rows, d),
                lambda i, ex, bk: (jnp.clip(ex[i], 0, e - 1),
                                   bk[i] // rows, 0))],
            # one (1, D) output block per lane, streamed back as the grid
            # runs: its last two dims are the (B, 1, D) array's own
            out_specs=pl.BlockSpec((1, 1, d), lambda i, ex, bk: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, d), pool.dtype),
        interpret=interpret,
        name="dbs_rw_read",
    )(ext, jnp.clip(block, 0, page - 1), pool).reshape(b, d)
