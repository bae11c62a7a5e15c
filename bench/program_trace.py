"""The program's own host spans in a profiler trace, and idle gaps named
down to them.

The program records ``jax.profiler.TraceAnnotation`` spans where its host
work happens: ``ring.admit``, ``ring.stage``, ``ring.upload``,
``ring.dispatch``, ``ring.fetch`` and ``ring.deliver`` once per ring step
(core/ring.py), ``vm.fence`` around a hazard-fence flush and ``py.gc``
around each Python collection (core/blockdev.py). ``reduce_profile`` is
``trace_reduce.reduce_profile`` with two additions:

- ``Reduction.spans``: ``{name: (count, seconds)}`` of the program's
  spans (names starting ``ring.``, ``vm.`` or ``py.``), clipped to the
  ``bench.window`` span;
- each idle gap's name keeps ``trace_reduce``'s ``bench.*`` name, then
  appends ``>`` and the program spans that each cover at least half of the
  gap, outer to inner: ``bench.pump>ring.fetch``,
  ``bench.submit>vm.fence>ring.fetch``.

A trace without program spans reduces to exactly ``trace_reduce``'s
result, with ``spans`` empty. The per-layer readers of the ring pump's
parts, the hazard fence and the write kernel's bytes (``bench/metrics/``)
read ``spans`` here and the program's ``VolumeManager.stats()`` counters
(``fence_steps``, ``write_rows``, ``write_kernel_calls``) and the calls
completed, as counter deltas over the traced stretch. ``bench/harness.py``
does not call ``reduce_profile`` or take those counters yet (PERF.md §7
lists the edits), so until then the readers read nothing.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

from bench import trace_reduce as tr

PROGRAM_PREFIXES = ("ring.", "vm.", "py.")
LANE_BYTES = 4                  # one float32 pool lane per user byte

Span = Tuple[float, float, str]


def chain(name: str, gap: tr.Interval, spans: List[Span]) -> str:
    """``name`` followed by ``>`` and each span of ``spans`` that covers at
    least half of ``gap``, outer to inner (earlier start, then longer,
    first)."""
    a, b = gap
    half = (b - a) / 2
    inner = sorted((s0, s0 - s1, n) for s0, s1, n in spans
                   if min(b, s1) - max(a, s0) >= half)
    return ">".join([name] + [n for _, _, n in inner])


def reduce_profile(profile, *, is_device_op: Callable = tr._tpu_ops,
                   n_gaps: int = 10) -> tr.Reduction:
    """``trace_reduce.reduce_profile`` of ``profile`` with the program's
    spans totalled in ``spans`` and chained into the gaps' names."""
    device: Dict[str, List[tr.Interval]] = {}

    def seen(plane_name, line_name, ev) -> bool:
        hit = is_device_op(plane_name, line_name, ev)
        if hit:
            t0 = ev.start_ns * 1e-9
            device.setdefault(plane_name, []).append(
                (t0, t0 + ev.duration_ns * 1e-9))
        return hit

    red = tr.reduce_profile(profile, is_device_op=seen, n_gaps=n_gaps)
    window: Optional[tr.Interval] = None
    spans: List[Span] = []
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tr.WINDOW_SPAN:
                    t0 = ev.start_ns * 1e-9
                    window = (t0, t0 + ev.duration_ns * 1e-9)
                elif ev.name.startswith(PROGRAM_PREFIXES):
                    t0 = ev.start_ns * 1e-9
                    spans.append((t0, t0 + ev.duration_ns * 1e-9, ev.name))
    w0, w1 = window
    spans = [(max(a, w0), min(b, w1), n) for a, b, n in spans
             if b > w0 and a < w1]
    totals: Dict[str, Tuple[int, float]] = {}
    for a, b, n in spans:
        c, s = totals.get(n, (0, 0.0))
        totals[n] = (c + 1, s + (b - a))
    red.spans = totals
    if spans:
        # the same gaps, in the same order, as the reduction named
        busy = next((tr.union([(max(a, w0), min(b, w1)) for a, b in ivs
                               if b > w0 and a < w1])
                     for _, ivs in sorted(device.items())
                     if any(b > w0 and a < w1 for a, b in ivs)), [])
        gaps = sorted(tr.idle_gaps(busy, w0, w1),
                      key=lambda g: g[0] - g[1])[:n_gaps]
        red.gaps = [(chain(name, g, spans), secs)
                    for (name, secs), g in zip(red.gaps, gaps)]
    return red


# ---------------------------------------------------------------- readers
def per_step_ms(ctx, *names: str) -> Optional[float]:
    """Host milliseconds per ring step in the program spans ``names``:
    their seconds over the traced stretch ÷ the ``ring.dispatch`` count.
    None where the trace holds no program spans."""
    spans = getattr(ctx.trace, "spans", None) or {}
    steps = spans.get("ring.dispatch", (0, 0.0))[0]
    if not steps:
        return None
    return 1e3 * sum(spans.get(n, (0, 0.0))[1] for n in names) / steps


def fence_steps_per_kcall(ctx) -> Optional[float]:
    """Ring steps the hazard fence dispatched per thousand byte-API calls
    completed, from the counter deltas ``fence_steps`` and ``calls``."""
    steps, calls = ctx.counters.get("fence_steps"), ctx.counters.get("calls")
    if steps is None or not calls:
        return None
    return 1e3 * steps / calls


def write_bytes_per_user_byte(ctx) -> Optional[float]:
    """HBM bytes the ``dbs_rw_write`` kernel moved per user byte written:
    ``write_rows`` extent rows of ``page_blocks`` blocks and one payload
    of ``batch`` blocks per ``write_kernel_calls``, in float32 lanes, over
    the blocks of write calls completed."""
    c, g = ctx.counters, ctx.geometry
    rows, calls = c.get("write_rows"), c.get("write_kernel_calls")
    blocks = c.get("write_blocks", 0)
    if rows is None or calls is None or not blocks:
        return None
    block = int(g["payload_elems"])
    moved = LANE_BYTES * block * (rows * int(g["page_blocks"])
                                  + calls * int(g["batch"]))
    return moved / (blocks * block)
