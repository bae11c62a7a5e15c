"""The plain reference: what every byte of every prefilled volume holds.

A volume starts as its prefill bytes (``data.prefill_bytes_np``); every
write, in submission order, replaces whole blocks with its payload
(``data.Payloads``). The byte API promises that order per volume (a write
that overlaps an in-flight call is fenced behind it), so replaying the op
log in submission order gives what each read had to return and what each
replica's pool has to hold once the run is drained. Nothing here imports
the program: it reads only the op log, the reads' returned bytes, and —
for the replica check — each replica's pool and extent map as arrays.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import data

# op log entry: (is_read, volume index, byte offset, nbytes, call index)
Op = Tuple[bool, int, int, int, int]


class Replay:
    """The op log replayed at block granularity.

    ``last[(vol, block)] = (call, j)``: the write that last covered the
    block (block ``j`` of call ``call``). ``expected_reads`` holds, for each
    sampled read, the writer of each of its blocks at its submission."""

    def __init__(self, log: Sequence[Op], block_bytes: int,
                 sampled: Dict[int, bytes]):
        bb = block_bytes
        last: Dict[Tuple[int, int], Tuple[int, int]] = {}
        reads: List[Tuple[int, int, int, List[Optional[Tuple[int, int]]]]] = []
        for is_read, vol, off, n, call in log:
            b0, nb = off // bb, n // bb
            if is_read:
                if call in sampled:
                    reads.append((call, vol, b0, [last.get((vol, b0 + j))
                                                  for j in range(nb)]))
            else:
                for j in range(nb):
                    last[(vol, b0 + j)] = (call, j)
        self.block_bytes = bb
        self.last = last
        self.reads = reads


def expected_block(replay: Replay, payloads: np.ndarray, key: int,
                   block: int, writer: Optional[Tuple[int, int]]
                   ) -> np.ndarray:
    bb = replay.block_bytes
    if writer is None:
        return data.prefill_bytes_np(key, block * bb + np.arange(bb))
    call, j = writer
    row = payloads[data.pool_row(call, j)].copy()
    row[:data.STAMP_BYTES] = np.frombuffer(
        np.array([data.stamp(call, j)], "<u8").tobytes(), np.uint8)
    return row


def read_mismatch_bytes(replay: Replay, sampled: Dict[int, bytes],
                        payloads: np.ndarray, keys: Sequence[int]
                        ) -> Tuple[int, int]:
    """(bytes of sampled reads that differ from the reference, reads
    compared). A read whose returned length is wrong counts every byte."""
    bb = replay.block_bytes
    bad = 0
    for call, vol, b0, writers in replay.reads:
        got = np.frombuffer(sampled[call], np.uint8)
        want = np.concatenate([expected_block(replay, payloads, keys[vol],
                                              b0 + j, w)
                               for j, w in enumerate(writers)])
        if got.shape != want.shape:
            bad += max(len(got), len(want))
            continue
        bad += int(np.count_nonzero(got != want))
    return bad, len(replay.reads)


def volume_overlay(replay: Replay, vol: int, n_blocks: int
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The written blocks of one volume as fixed-size arrays (padding
    points past the volume, so the device scatter drops it): block ids,
    payload pool rows, and stamps as (n_blocks, 8) uint8."""
    blk = np.full(n_blocks, n_blocks, np.int32)
    rows = np.zeros(n_blocks, np.int32)
    stamps = np.zeros(n_blocks, "<u8")
    i = 0
    for (v, b), (call, j) in replay.last.items():
        if v == vol:
            blk[i], rows[i], stamps[i] = b, data.pool_row(call, j), \
                data.stamp(call, j)
            i += 1
    return blk, rows, stamps.view(np.uint8).reshape(n_blocks, 8)


@partial(jax.jit, static_argnames=("n_pages",))
def _replica_volume_mismatch(pool, table, vid, key, wblk, wrow, wstamp,
                             payloads, *, n_pages):
    """Bytes of one volume that differ between one replica's pool (read
    through its own (1, V, P) extent map; an unmapped page reads as -1,
    which no byte equals) and the reference image, and the volume's
    unmapped pages."""
    _, _, pb, bb = pool.shape
    ext = table[0, vid, :n_pages]
    rows = pool[0, jnp.maximum(ext, 0)]
    got = jnp.where((ext >= 0)[:, None, None], rows, -1.0)
    offs = ((jnp.arange(n_pages, dtype=jnp.uint32)[:, None, None] * pb
             + jnp.arange(pb, dtype=jnp.uint32)[None, :, None]) * bb
            + jnp.arange(bb, dtype=jnp.uint32)[None, None, :])
    want = data.prefill_bytes_jnp(key, offs).reshape(n_pages * pb, bb)
    over = payloads[wrow].at[:, :data.STAMP_BYTES].set(wstamp)
    want = want.at[wblk].set(over, mode="drop")
    bad = jnp.sum(got.reshape(n_pages * pb, bb) != want.astype(pool.dtype),
                  dtype=jnp.int32)
    return bad, jnp.sum(ext < 0, dtype=jnp.int32)


def replica_mismatch_bytes(pools, tables, vids: Sequence[int],
                           keys: Sequence[int], replay: Replay,
                           payloads: np.ndarray, n_pages: int, page_blocks: int
                           ) -> Tuple[List[int], List[int]]:
    """Per replica: (bytes that differ from the reference over every
    prefilled volume, unmapped pages of those volumes)."""
    n_blocks = n_pages * page_blocks
    pay = jnp.asarray(payloads)
    overlays = [tuple(jnp.asarray(a) for a in
                      volume_overlay(replay, v, n_blocks))
                for v in range(len(vids))]
    bad, holes = [], []
    for pool, table in zip(pools, tables):
        b = h = 0
        for v, (vid, key) in enumerate(zip(vids, keys)):
            got_b, got_h = _replica_volume_mismatch(
                pool, table, jnp.int32(vid), jnp.uint32(key), *overlays[v],
                pay, n_pages=n_pages)
            b += int(got_b)
            h += int(got_h)
        bad.append(b)
        holes.append(h)
    return bad, holes
