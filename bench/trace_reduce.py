"""Reduce a JAX profiler trace of a run's traced window to numbers.

The trace is read with ``jax.profiler.ProfileData`` (an ``.xplane.pb``).
Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; the traced window is the host span
``bench.window`` that the harness opens and closes around it; the host
spans ``bench.submit``, ``bench.pump`` and ``bench.harvest`` say what the
load generator was doing. The reduction gives:

- ``busy_s``: the union of the intervals in which an operation ran on a
  chip, inside the window, averaged over the chips that ran any;
- ``window_s``: the window's length;
- ``op_seconds``: device seconds per operation name, summed over chips;
- ``gaps``: the longest idle gaps of the first chip, each named by the
  host span that overlaps it most (``"none"`` when none does).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.submit", "bench.pump", "bench.harvest")

Interval = Tuple[float, float]


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    op_seconds: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)
    n_ops: int = 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_of(self, pattern) -> float:
        """Device seconds of the operations whose name ``pattern`` (a
        compiled regular expression) finds."""
        return sum(s for op, s in self.op_seconds.items()
                   if pattern.search(op))

    def top_ops(self, k: int = 10,
                label: Callable[[str], str] = lambda op: op) -> List[List]:
        """The ``k`` operations that took most device time, as
        ``[label(name), seconds]``."""
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:k]
        return [[label(name), s] for name, s in ops]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def idle_gaps(busy: List[Interval], w0: float, w1: float) -> List[Interval]:
    """The idle stretches of [w0, w1] between disjoint sorted busy
    intervals."""
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, min(a, w1)))
        t = max(t, b)
        if t >= w1:
            break
    if t < w1:
        gaps.append((t, w1))
    return [(a, b) for a, b in gaps if b > a]


def name_gap(gap: Interval, spans: List[Tuple[float, float, str]]) -> str:
    """The host span that overlaps ``gap`` most (``spans`` sorted by
    start)."""
    best, best_name = 0.0, "none"
    a, b = gap
    for s0, s1, name in spans:
        if s0 >= b:
            break
        ov = min(b, s1) - max(a, s0)
        if ov > best:
            best, best_name = ov, name
    return best_name


def _tpu_ops(plane_name: str, line_name: str, event) -> bool:
    return plane_name.startswith(DEVICE_PLANE_PREFIX) and line_name == OPS_LINE


def reduce_profile(profile, *, is_device_op: Callable = _tpu_ops,
                   n_gaps: int = 10) -> Reduction:
    """Reduce a ``ProfileData``. ``is_device_op(plane, line, event)`` picks
    the device operations (the TPU planes' ``XLA Ops`` by default)."""
    window: Optional[Interval] = None
    spans: List[Tuple[float, float, str]] = []
    chips: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                t0 = ev.start_ns * 1e-9
                t1 = t0 + ev.duration_ns * 1e-9
                if is_device_op(plane.name, line.name, ev):
                    chips.setdefault(plane.name, []).append((ev.name, t0, t1))
                elif ev.name == WINDOW_SPAN:
                    window = (t0, t1)
                elif ev.name in HOST_SPANS:
                    spans.append((t0, t1, ev.name))
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w0, w1 = window
    spans = sorted((max(a, w0), min(b, w1), n) for a, b, n in spans
                   if b > w0 and a < w1)
    op_seconds: Dict[str, float] = {}
    busy_per_chip, first_busy, n_ops = [], None, 0
    for plane_name in sorted(chips):
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in chips[plane_name]
                   if b > w0 and a < w1]
        if not clipped:
            continue
        n_ops += len(clipped)
        for n, a, b in clipped:
            op_seconds[n] = op_seconds.get(n, 0.0) + (b - a)
        busy = union([(a, b) for _, a, b in clipped])
        busy_per_chip.append(sum(b - a for a, b in busy))
        if first_busy is None:
            first_busy = busy
    busy_s = (sum(busy_per_chip) / len(busy_per_chip)) if busy_per_chip \
        else 0.0
    gaps = sorted(idle_gaps(first_busy or [], w0, w1),
                  key=lambda g: g[0] - g[1])[:n_gaps]
    return Reduction(window_s=w1 - w0, busy_s=busy_s, op_seconds=op_seconds,
                     gaps=[(name_gap(g, spans), g[1] - g[0]) for g in gaps],
                     n_ops=n_ops)


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(found)}")
    return found[0]


def reduce_dir(trace_dir: str, **kw) -> Reduction:
    import jax
    return reduce_profile(
        jax.profiler.ProfileData.from_file(find_xplane(trace_dir)), **kw)
