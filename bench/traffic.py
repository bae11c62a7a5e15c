"""The one general generator of closed-loop traffic, driven by a mix's
parameters (``bench/traffic/<mix>.json``).

A mix names its queue depth (``qd``: calls kept in flight, as fio's
``iodepth``), the bytes per call (``call_bytes``, a whole number of
blocks), the share of reads (``read_share``) and the access pattern:
``random`` draws a volume uniformly and a ``call_bytes``-aligned offset
uniformly inside it; ``sequential`` keeps one cursor per volume, starting
at a seeded aligned offset, takes the volumes in turn and wraps at the
volume's end. Every draw comes from the seed; a seed changes where calls
go, never their number, size or mix.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

PATTERNS = ("random", "sequential")
CHUNK = 1 << 14          # draws made at a time


@dataclass(frozen=True)
class Mix:
    name: str
    qd: int
    call_bytes: int
    read_share: float
    pattern: str

    @classmethod
    def from_spec(cls, name: str, spec: Dict) -> "Mix":
        mix = cls(name=name, qd=int(spec["qd"]),
                  call_bytes=int(spec["call_bytes"]),
                  read_share=float(spec["read_share"]),
                  pattern=str(spec["pattern"]))
        if mix.qd < 1 or mix.call_bytes < 1:
            raise ValueError(f"mix {name}: qd and call_bytes must be >= 1")
        if not 0.0 <= mix.read_share <= 1.0:
            raise ValueError(f"mix {name}: read_share outside [0, 1]")
        if mix.pattern not in PATTERNS:
            raise ValueError(f"mix {name}: pattern {mix.pattern!r} not one "
                             f"of {PATTERNS}")
        return mix

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The call kinds this mix issues."""
        out = []
        if self.read_share < 1.0:
            out.append("write")
        if self.read_share > 0.0:
            out.append("read")
        return tuple(out)


class Generator:
    """Yields ``(is_read, volume index, byte offset, nbytes)`` calls."""

    def __init__(self, mix: Mix, *, seed: int, n_volumes: int,
                 volume_bytes: int, block_bytes: int):
        if mix.call_bytes % block_bytes:
            raise ValueError(f"mix {mix.name}: call_bytes {mix.call_bytes} "
                             f"is not a whole number of {block_bytes}-byte "
                             "blocks")
        if mix.call_bytes > volume_bytes:
            raise ValueError(f"mix {mix.name}: call_bytes exceeds the "
                             "volume")
        self.mix = mix
        self.n_volumes = n_volumes
        self.slots = volume_bytes // mix.call_bytes
        self.rng = np.random.default_rng([seed % (1 << 64), 0x7AFF])
        self.cursors = [int(s) * mix.call_bytes for s in
                        self.rng.integers(self.slots, size=n_volumes)]
        self.turn = 0
        self._buf: List[Tuple[bool, int, int, int]] = []
        self._i = 0

    def _refill(self) -> None:
        m, rng = self.mix, self.rng
        reads = (rng.random(CHUNK) < m.read_share).tolist()
        if m.pattern == "random":
            vols = rng.integers(self.n_volumes, size=CHUNK).tolist()
            offs = (rng.integers(self.slots, size=CHUNK)
                    * m.call_bytes).tolist()
        else:
            vols, offs = [], []
            for _ in range(CHUNK):
                v = self.turn
                self.turn = (v + 1) % self.n_volumes
                vols.append(v)
                offs.append(self.cursors[v])
                self.cursors[v] = ((self.cursors[v] + m.call_bytes)
                                   % (self.slots * m.call_bytes))
        n = m.call_bytes
        self._buf = [(r, v, o, n) for r, v, o in zip(reads, vols, offs)]
        self._i = 0

    def next(self) -> Tuple[bool, int, int, int]:
        if self._i >= len(self._buf):
            self._refill()
        call = self._buf[self._i]
        self._i += 1
        return call
