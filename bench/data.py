"""The benchmark's data, made from ``--seed``: prefilled volume bytes and
write payloads.

Prefill bytes are a 32-bit integer hash of (seed, volume, byte offset), so
the device fill (``prefill_rows_jnp``) and the host reference
(``prefill_bytes_np``) compute the same bytes with no table between them.
Write payloads are rows of a seeded pool of random blocks, each block
stamped with its call and block index in its first 8 bytes, so no two
written blocks carry the same bytes and a lost, stale or misplaced write
shows.
"""
from __future__ import annotations

import numpy as np

M1, M2 = 0x7FEB352D, 0x846CA68B      # lowbias32 multipliers
MASK32 = 0xFFFFFFFF
STAMP_BYTES = 8
POOL_ROWS = 4096                      # payload pool rows (16 MiB of 4 KiB)


def _mix_int(x: int) -> int:
    x &= MASK32
    x ^= x >> 16
    x = (x * M1) & MASK32
    x ^= x >> 15
    x = (x * M2) & MASK32
    x ^= x >> 16
    return x


def volume_key(seed: int, vol: int) -> int:
    """The 32-bit key of one volume's prefill bytes."""
    s = seed % (1 << 64)
    return _mix_int(_mix_int((s ^ (s >> 32)) & MASK32)
                    ^ ((vol * 0x9E3779B9) & MASK32))


def _mix_np(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(M1)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(M2)
    return x ^ (x >> np.uint32(16))


def prefill_bytes_np(key: int, offsets: np.ndarray) -> np.ndarray:
    """Prefill bytes of one volume at byte ``offsets`` (host reference)."""
    x = np.asarray(offsets).astype(np.uint32) ^ np.uint32(key)
    return (_mix_np(x) >> np.uint32(24)).astype(np.uint8)


def prefill_bytes_jnp(key, offsets):
    """``prefill_bytes_np`` in jax.numpy (uint32 arithmetic wraps alike)."""
    import jax.numpy as jnp
    u = jnp.uint32
    x = offsets.astype(u) ^ key.astype(u)
    x = x ^ (x >> u(16))
    x = x * u(M1)
    x = x ^ (x >> u(15))
    x = x * u(M2)
    x = x ^ (x >> u(16))
    return (x >> u(24)).astype(jnp.uint8)


def payload_pool(seed: int, block_bytes: int) -> np.ndarray:
    """(POOL_ROWS, block_bytes) uint8 random rows, from the seed."""
    rng = np.random.default_rng([seed % (1 << 64), 0x9A71])
    return rng.integers(0, 256, (POOL_ROWS, block_bytes), dtype=np.uint8)


def pool_row(call: int, block: int) -> int:
    """The payload pool row of block ``block`` of call ``call``."""
    return (call * 33 + block) % POOL_ROWS


def stamp(call: int, block: int) -> int:
    return call * 65536 + block


class Payloads:
    """Builds write payloads: block j of call c is pool row
    ``pool_row(c, j)`` with ``stamp(c, j)`` in its first 8 bytes."""

    def __init__(self, seed: int, block_bytes: int):
        self.pool = payload_pool(seed, block_bytes)
        self.block_bytes = block_bytes
        self._j = np.arange(1 << 12, dtype=np.int64)

    def rows(self, call: int, nblocks: int) -> np.ndarray:
        j = self._j[:nblocks]
        out = self.pool[(call * 33 + j) % POOL_ROWS]
        out[:, :STAMP_BYTES] = (call * 65536 + j).astype("<u8").view(
            np.uint8).reshape(nblocks, STAMP_BYTES)
        return out

    def data(self, call: int, nblocks: int) -> bytes:
        return self.rows(call, nblocks).tobytes()
